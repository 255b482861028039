"""Backend-aware buffer donation.

Donation (`jit(..., donate_argnums=...)`) is an HBM-reuse optimization:
on an accelerator it lets XLA write step outputs into the input
buffers, which is what lets `params, ... = step(params, ...)` train at
the memory high-water mark of ONE copy. On XLA:CPU it buys nothing
(host allocator, no HBM pressure) and was stripped after donated
programs corrupted the heap under an older jaxlib; whether jaxlib 0.9
still needs that is not established, so the CPU stays stripped — which
also means the CPU suite never runs a donated program: "Array has been
deleted" bugs show only on the chip (`chip_smoke.py` checks donation is
in effect there and the path survives it). Every jit site in the
framework routes its donate_argnums through here; the answer is the
live backend's platform, never a guess from environment variables — a
site that asks before any backend exists initializes it.
"""

from __future__ import annotations

import functools
import threading
from typing import Tuple


def donation_safe() -> bool:
    """True on every backend but XLA:CPU (see module docstring)."""
    import jax

    return jax.default_backend() != "cpu"


def donate_argnums(*nums: int) -> Tuple[int, ...]:
    """`donate_argnums=donate_argnums(0, 1, 2)` — the given argnums on
    accelerator backends, `()` on CPU. For jit sites built at run time;
    module-level decorators use `jit_donated`, which defers the
    decision (and with it backend initialization) to first call."""
    return tuple(nums) if donation_safe() else ()


def jit_donated(fn=None, *, donate: Tuple[int, ...], **jit_kwargs):
    """`jax.jit` whose donate_argnums resolve at FIRST CALL, not at
    decoration time.

    Module-level `@partial(jax.jit, donate_argnums=...)` decorators
    evaluate during import; asking for the platform there would
    initialize the backend at import time (and ahead of
    `jax.distributed.initialize()` on multi-host). By first invocation
    the caller is about to execute a device program anyway.

    The wrapper delegates attribute access (`.lower`, `._cache_size`,
    ...) to the resolved jit function."""
    if fn is None:
        return lambda f: jit_donated(f, donate=donate, **jit_kwargs)

    lock = threading.Lock()

    class _LazyJit:
        def _resolve(self):
            jitted = self.__dict__.get("_jitted")
            if jitted is None:
                with lock:
                    jitted = self.__dict__.get("_jitted")
                    if jitted is None:
                        import jax
                        nums = tuple(donate) if donation_safe() else ()
                        jitted = jax.jit(fn, donate_argnums=nums,
                                         **jit_kwargs)
                        self.__dict__["_jitted"] = jitted
            return jitted

        def __call__(self, *args, **kwargs):
            return self._resolve()(*args, **kwargs)

        def __getattr__(self, name):
            return getattr(self._resolve(), name)

    return functools.update_wrapper(_LazyJit(), fn)
