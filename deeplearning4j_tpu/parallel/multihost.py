"""Multi-host initialization and teardown.

Reference equivalence: the Spark driver/executor bootstrap +  Aeron
parameter-server wiring (`SharedTrainingMaster.java:423-443`,
`VoidConfiguration` unicast/shard config) collapse on TPU into ONE
call: `jax.distributed.initialize` — after which every host sees the
global device set, meshes span hosts, and the same pjit/shard_map
programs run SPMD over ICI (intra-slice) and DCN (cross-slice) with
XLA-inserted collectives replacing the PS gossip protocol.

Elastic lifecycle (parallel/elastic.py): the runtime is no longer
initialize-once. `shutdown_multihost()` tears the distributed client /
service down AND clears every cache that pins the old topology (the
xla_bridge backend registry, the `process_count`/`process_index`
lru_caches, jit executable caches), so a following
`initialize_multihost(...)` with a DIFFERENT process set or coordinator
address builds a fresh world — the mesh re-formation primitive the
membership coordinator drives on join/leave.
"""

from __future__ import annotations

import logging
import time
from typing import Optional

import jax

log = logging.getLogger("deeplearning4j_tpu.parallel.multihost")

# which exceptions the bounded-retry path treats as "the coordinator is
# not up yet / transient RPC failure" — jax surfaces them as RuntimeError
# (DEADLINE_EXCEEDED / UNAVAILABLE grpc statuses stringified) and
# XlaRuntimeError subclasses of it
_TRANSIENT_MARKERS = ("DEADLINE_EXCEEDED", "UNAVAILABLE", "timed out",
                      "Timed out", "failed to connect", "Connection refused",
                      "connection attempt", "Socket closed",
                      "Address already in use")


def _transient(err: BaseException) -> bool:
    msg = str(err)
    return any(m in msg for m in _TRANSIENT_MARKERS)


def _raw_initialize(coordinator_address, num_processes, process_id, *,
                    initialization_timeout: Optional[float],
                    heartbeat_interval_s: Optional[float],
                    max_missing_heartbeats: Optional[int]):
    """One initialization attempt. Prefers the internal
    `global_state.initialize` entry point when heartbeat tuning is
    requested (the public API grew those knobs only later): elastic
    recovery needs peer death detected in seconds, not the default
    10 s x 10 misses."""
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if initialization_timeout is not None:
        kwargs["initialization_timeout"] = int(initialization_timeout)
    if heartbeat_interval_s is None and max_missing_heartbeats is None:
        jax.distributed.initialize(**kwargs)
        return
    hb = {}
    if heartbeat_interval_s is not None:
        hb["service_heartbeat_interval_seconds"] = int(
            max(1, heartbeat_interval_s))
        hb["client_heartbeat_interval_seconds"] = int(
            max(1, heartbeat_interval_s))
    if max_missing_heartbeats is not None:
        hb["service_max_missing_heartbeats"] = int(max_missing_heartbeats)
        hb["client_max_missing_heartbeats"] = int(max_missing_heartbeats)
    try:
        from jax._src import distributed as _dist
        _dist.global_state.initialize(**kwargs, **hb)
    except TypeError:
        # jax version without tunable heartbeats: fall back to defaults
        # (elastic recovery still works, peer-death detection is slower)
        log.warning("this jax version does not expose heartbeat tuning; "
                    "using default heartbeat intervals")
        jax.distributed.initialize(**kwargs)


def _reset_distributed_state():
    """Best-effort teardown of a half-initialized distributed runtime
    (a failed initialize attempt can leave a dangling client/service
    that would make the next attempt fail with 'already initialized')."""
    try:
        from jax._src import distributed as _dist
        state = _dist.global_state
        if state.client is not None or state.service is not None:
            state.shutdown()
    except Exception as e:  # noqa: BLE001 — peers may already be gone
        log.warning("distributed-state reset during retry raised %s "
                    "(continuing)", e)
        try:
            from jax._src import distributed as _dist
            _dist.global_state.client = None
            _dist.global_state.service = None
            _dist.global_state.preemption_sync_manager = None
        except Exception:  # noqa: BLE001
            pass


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None, *,
                         initialization_timeout: Optional[float] = None,
                         heartbeat_interval_s: Optional[float] = None,
                         max_missing_heartbeats: Optional[int] = None,
                         max_attempts: int = 3,
                         backoff_s: float = 1.0) -> None:
    """Bring up the multi-host runtime (idempotent while up). On TPU
    pods with standard env (TPU_WORKER_HOSTNAMES etc.) all args
    auto-detect; on GPU/CPU clusters pass coordinator host:port +
    process counts (the reference's `controller address`
    `SharedTrainingMaster.java:443`).

    Connection setup retries with bounded exponential backoff: the
    coordinator process routinely comes up AFTER its workers (elastic
    re-formation, CI process races) and the raw failure mode is an
    opaque RPC timeout. `max_attempts` attempts, `backoff_s * 2**k`
    sleep between them; non-transient errors raise immediately.

    After `shutdown_multihost()` a new call re-initializes — with a
    different process set / coordinator address if the topology
    changed (the elastic membership path)."""
    if getattr(initialize_multihost, "_done", False):
        return
    last_err: Optional[BaseException] = None
    for attempt in range(max(1, int(max_attempts))):
        try:
            _raw_initialize(
                coordinator_address, num_processes, process_id,
                initialization_timeout=initialization_timeout,
                heartbeat_interval_s=heartbeat_interval_s,
                max_missing_heartbeats=max_missing_heartbeats)
            initialize_multihost._done = True
            return
        except Exception as e:  # noqa: BLE001 — inspect + classify
            last_err = e
            _reset_distributed_state()
            if not _transient(e):
                raise
            if attempt + 1 < max(1, int(max_attempts)):
                delay = backoff_s * (2 ** attempt)
                log.warning(
                    "jax.distributed.initialize attempt %d/%d failed "
                    "(coordinator %s not reachable yet?): %s — retrying "
                    "in %.1fs", attempt + 1, max_attempts,
                    coordinator_address, str(e)[:200], delay)
                time.sleep(delay)
    raise RuntimeError(
        f"initialize_multihost: all {max_attempts} attempts failed "
        f"(transient coordinator race?)") from last_err


def multihost_active() -> bool:
    """True between a successful `initialize_multihost` and the next
    `shutdown_multihost`."""
    return bool(getattr(initialize_multihost, "_done", False))


def shutdown_multihost() -> None:
    """Tear down the distributed runtime so it can be re-initialized
    with a DIFFERENT topology (elastic membership change).

    Clears, in order: the `jax.distributed` client/service, the
    initialize latch, every cached backend (the CPU/TPU client bakes
    the world size in at creation), the `process_count`/`process_index`
    lru_caches (they would keep answering for the dead world), and the
    jit executable caches (compiled programs pin devices of the old
    backend). No-op when the runtime was never initialized."""
    if not multihost_active():
        return
    try:
        jax.distributed.shutdown()
    except Exception as e:  # noqa: BLE001 — a dead peer can fail the
        # shutdown barrier; the local teardown below must still run
        log.warning("jax.distributed.shutdown raised %s (continuing "
                    "with local teardown)", e)
        _reset_distributed_state()
    finally:
        initialize_multihost._done = False
        _clear_topology_caches()


def _clear_topology_caches():
    """Drop every cache that pins the previous process set. Split out
    so tests can exercise the latch lifecycle without a real
    distributed runtime."""
    from jax._src import api as _api
    from jax._src import xla_bridge as xb

    _api.clear_caches()
    try:
        xb._clear_backends()
    except Exception as e:  # noqa: BLE001
        log.warning("backend-cache clear raised %s", e)
    for fn_name in ("process_count", "process_index", "device_count",
                    "local_device_count"):
        fn = getattr(xb, fn_name, None)
        if fn is not None and hasattr(fn, "cache_clear"):
            fn.cache_clear()


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def is_main_process() -> bool:
    return jax.process_index() == 0
