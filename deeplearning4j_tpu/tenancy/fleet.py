"""TenantFleet — N tenants served from ONE in-memory base model.

The multi-tenant shape of the serving tier: every tenant is a
`FleetServer` deployment (own `GenerationServer`, own hot-swap lock,
own gauges), but what a deployment resolves is an ADAPTER version
from the per-tenant adapter store (`ModelRegistry.resolve_adapter`)
composed over a single shared base net held in this process:

- **One base copy.** The base model's params are resolved once at
  fleet construction (and pinned). Every tenant's serving params are
  `tenancy.lora.compose_params(base, adapter)` — `LoRAWeight` nodes
  whose `base` leaves are the SAME array objects across all tenants;
  composing a tenant allocates the rank-r factors and a tree spine,
  nothing else. With `quantize="int8"` the base is quantized ONCE
  (`quant.serving_params` on the base net) and tenants share the int8
  copy — int8 base + fp adapter, composed inside the matmul. A base
  whose policy is mixed is cast to its compute dtype ONCE the same way,
  and a tenant's own serving tree adds only its factors' cast: a base
  leaf already in the compute dtype is the same object there.
- **Composed-params cache.** Keyed on
  `(base version, adapter version, quantize mode)` and on the
  IDENTITY of the base net's params tree (the
  `quant.serving_params` invalidation pattern): a base fit()/restore
  reassigns that tree, so every tenant's next composition sees the
  fresh base instead of silently serving stale weights.
- **Per-tenant hot-swap = adapter pointer flip.** `swap(tenant)` is
  the inherited FleetServer discipline — warm the successor, flip,
  migrate queued, drain the incumbent — where "successor" differs
  from the incumbent only by its adapter factors. In-flight streams
  finish on the adapter version they started with (version-tagged
  parity, the PR-12 drain contract); retention can never collect a
  served adapter (`pin_adapter` before resolve, released through the
  `_release_version` seam).
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from deeplearning4j_tpu.serving.fleet import FleetServer
from deeplearning4j_tpu.serving.registry import ModelRegistry
from deeplearning4j_tpu.serving.server import GenerationServer
from deeplearning4j_tpu.nd import quant
from deeplearning4j_tpu.tenancy import lora

log = logging.getLogger("deeplearning4j_tpu.tenancy.fleet")


class _TenantNetView:
    """A per-tenant view of the shared base net: its OWN `params`
    (the composed tree) and its own `__dict__` (so nothing caches
    onto the base), everything else — conf, layers, net_state, dtype
    — delegated to the one base net. The engine treats it as an
    ordinary net."""

    def __init__(self, base_net, params):
        self._base_net = base_net
        self.params = params
        # the serving jit caches key on `net.__dict__` directly
        # (engine._shared_jit, zoo.transformer.get_prefill_bucketed),
        # which `__getattr__` delegation can't intercept — alias the
        # base net's cache dicts into this view so every tenant server
        # and every adapter-swap successor reuses ONE compile instead
        # of paying the full decode/prefill compile per flip
        for cache_attr in ("_serving_jit_cache", "_transformer_gen_jit"):
            self.__dict__[cache_attr] = base_net.__dict__.setdefault(
                cache_attr, {})

    def __getattr__(self, name):
        return getattr(self.__dict__["_base_net"], name)


class TenantFleet(FleetServer):
    """FleetServer whose deployment names are TENANTS of one shared
    base model: deploy/swap/scale/undeploy, gauges, drain discipline
    and the router interface (`has`/`active`/`names`) are all
    inherited — only what a "version" means (a per-tenant adapter
    version) and what a server is built from (composed shared-base
    params) change."""

    def __init__(self, registry: ModelRegistry, model: str, *,
                 base_version="latest", quantize: Optional[str] = None,
                 gauge_interval_s: float = 0.25):
        super().__init__(registry, gauge_interval_s=gauge_interval_s)
        self.model = model
        self.quantize = quantize
        target = (registry.latest(model) if base_version == "latest"
                  else int(base_version))
        if target is None:
            raise FileNotFoundError(
                f"no published versions of {model!r} to base a tenant "
                f"fleet on")
        registry.pin(model, target)
        try:
            self.base_net, self.base_version = registry.resolve(
                model, base_version)
            if self.base_version != target:
                registry.pin(model, self.base_version)
                registry.unpin(model, target)
        except Exception:
            registry.unpin(model, target)
            raise
        # {tenant: {"source": <base params identity>, "key": (base_v,
        #  adapter_v, mode), "tree": composed}} — one entry per tenant
        self._composed_cache: dict = {}
        self._compose_lock = threading.Lock()

    # ------------------------------------------------------- composition
    def composed_params(self, tenant: str, adapter: dict,
                        adapter_version: int, *, rank: int,
                        alpha: float, quantize: Optional[str] = None):
        """The tenant's serving params: shared (possibly int8) base +
        this adapter version, cached per tenant and invalidated when
        EITHER the key changes (new adapter/base version, different
        quantize mode) or the base net's params tree is reassigned
        (fit()/restore — the identity check)."""
        key = (self.base_version, int(adapter_version), quantize)
        base_src = self.base_net.params
        with self._compose_lock:
            ent = self._composed_cache.get(tenant)
            if (ent is not None and ent["source"] is base_src
                    and ent["key"] == key):
                return ent["tree"]
            base_tree = quant.serving_params(self.base_net, quantize)
            tree = lora.compose_params(base_tree, adapter, rank=rank,
                                       alpha=alpha)
            self._composed_cache[tenant] = {
                "source": base_src, "key": key, "tree": tree}
            return tree

    def shared_base_copies(self) -> int:
        """Distinct in-memory base-weight copies across every deployed
        tenant — the one-base-copy evidence probe. Every adapted
        leaf's `base` object must be an object of the base net's ONE
        serving tree; returns 1 when that holds, else 1 + the number
        of stray copies found."""
        stray = set()
        base_tree = quant.serving_params(self.base_net, self.quantize)
        base_ids = {id(w) for lv in base_tree.values()
                    for w in lv.values()}
        for tenant in self.names():
            server, _ = self.active(tenant)
            params = server.engine.net.params
            for lv in params.values():
                for w in lv.values():
                    if isinstance(w, lora.LoRAWeight) \
                            and id(w.base) not in base_ids:
                        stray.add(id(w.base))
        return 1 + len(stray)

    # ----------------------------------------------------------- versions
    def _release_version(self, tenant: str, version: int):
        self.registry.unpin_adapter(self.model, tenant, version)

    def _build_server(self, tenant: str, version, server_kw: dict,
                      warm_len, warm_tokens: int):
        """Resolve + compose + warm + start one tenant server. The
        target ADAPTER version is pinned before resolve (the
        FleetServer pin-before-resolve rule applied to the adapter
        store); pins taken here are released on failure."""
        reg = self.registry
        model = self.model
        target = (reg.latest_adapter(model, tenant)
                  if version == "latest" else int(version))
        if target is None:
            raise FileNotFoundError(
                f"no published adapters for {model!r} tenant "
                f"{tenant!r}")
        pinned_here = []

        def pin(v):
            reg.pin_adapter(model, tenant, v)
            pinned_here.append(v)

        pin(target)
        try:
            adapter, meta, v = reg.resolve_adapter(model, tenant,
                                                   version)
            if v != target:
                pin(v)
                reg.unpin_adapter(model, tenant, target)
                pinned_here.remove(target)
            server_kw = dict(server_kw)
            server_kw.setdefault("name", tenant)
            # quantization is a FLEET concern: the base quantizes once
            # and is shared, so the engine gets pre-composed params
            # and must not re-quantize per tenant
            qmode = server_kw.pop("quantize", self.quantize)
            params = self.composed_params(
                tenant, adapter, v, rank=int(meta["rank"]),
                alpha=float(meta["alpha"]), quantize=qmode)
            view = _TenantNetView(self.base_net, params)
            server = GenerationServer(view, **server_kw)
            with self._lock:
                prefixes = list(self._prefixes.get(tenant, ()))
            for ids in prefixes:
                server.register_prefix(ids)
            if warm_len is not None:
                server.warmup(int(warm_len), warm_tokens)
            server.start()
            return server, v
        except Exception:
            for v_ in pinned_here:
                reg.unpin_adapter(model, tenant, v_)
            raise

    # ----------------------------------------------------------- teardown
    def stop(self, *, drain: bool = False, drain_timeout: float = 600.0):
        try:
            super().stop(drain=drain, drain_timeout=drain_timeout)
        finally:
            self.registry.unpin(self.model, self.base_version)
