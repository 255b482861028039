"""Persistent XLA compilation cache — one rule for where it lives.

The reference pays no compile step (libnd4j kernels are prebuilt); the
XLA equivalent cost is jit compilation — minutes for a cold serving
warmup grid or a ResNet-class train step, paid again in every new
process. JAX's persistent compilation cache makes that a one-time cost
per (program, backend) pair.

The rule: where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads
it and this module sets NO directory in code — the operator (or the
chip tool) placed the cache and it stays there. Where it is unset, the
cache is one fixed directory inside the checkout (`CACHE_ROOT`,
git-ignored): the path is part of what makes a later process find the
entries again, so it must not move with `$HOME` or the run. Entry
points call `enable_compilation_cache()` once, before they compile
(`chip_smoke.py`, `bench.main`, the replica worker, the test conftest,
the loadtest scripts); library code never re-points the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — listed in .gitignore and .chiprunignore
CACHE_ROOT = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache(subdir: str = "",
                             min_compile_time_secs: float = 1.0) -> str:
    """Turn the persistent compilation cache on and return its
    directory. With `JAX_COMPILATION_CACHE_DIR` set (or a directory
    already in effect from an earlier call) the directory is left
    alone; otherwise it becomes `CACHE_ROOT / subdir` (`subdir` is the
    test suite's per-machine namespace — XLA:CPU executables are not
    portable across host CPUs).

    Programs whose compile took at least `min_compile_time_secs` are
    cached: serving grids are many small programs, so serving entry
    points pass 0; the default keeps trivial compiles off the disk."""
    import jax

    if (not os.environ.get("JAX_COMPILATION_CACHE_DIR")
            and jax.config.jax_compilation_cache_dir is None):
        path = CACHE_ROOT / subdir
        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    # cache everything the backend can serialize, not only large entries
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(jax.config.jax_compilation_cache_dir)
