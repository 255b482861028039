"""ParallelInference — high-throughput batched serving.

Reference: `ParallelInference.java:32` (worker pool; `ObservablesProvider`
dynamic batching :84): many small `output()` requests are coalesced into
device-sized batches.

TPU-native version: ONE jitted forward sharded over the mesh replaces
the worker pool (replica threads are a GPU idiom); dynamic batching
survives as request coalescing with pad-to-bucket so XLA sees a few
static shapes instead of one compile per request size.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import device_mesh


class ParallelInference:
    def __init__(self, model, mesh: Optional[Mesh] = None, *,
                 batch_limit: int = 64, queue_limit_ms: float = 5.0,
                 data_axis: str = "data"):
        self.model = model
        self.mesh = mesh if mesh is not None else device_mesh()
        self.batch_limit = batch_limit
        self.queue_limit_ms = queue_limit_ms
        self.data_axis = data_axis
        self._fwd = None
        self._lock = threading.Lock()
        self._buckets = [1, 2, 4, 8, 16, 32, 64, 128, 256]
        # background coalescing loop (ObservablesProvider role)
        self._queue: "queue.Queue" = queue.Queue()
        self._collector: Optional[threading.Thread] = None
        self._running = False
        # executed device-batch sizes — the observable proof that
        # concurrent callers were actually coalesced (bounded: a
        # long-lived server must not leak one int per batch forever)
        from collections import deque
        self.batch_size_history = deque(maxlen=1024)

    def _build(self):
        model = self.model
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        sharded = NamedSharding(mesh, P(self.data_axis))

        self._fwd = jax.jit(model._forward_output,
                            in_shardings=(repl, repl, sharded),
                            out_shardings=sharded)

    def _ensure_built(self):
        """Build the jitted forward + init the model exactly once, even
        under concurrent cold starts: two threads racing a cold
        `output()` would both trace/compile the forward (and could both
        run `model.init()`, one clobbering params the other is already
        using). Double-checked under `self._lock`; the publish of
        `self._fwd` is the release point."""
        if self._fwd is not None and self.model._initialized:
            return
        with self._lock:
            if not self.model._initialized:
                self.model.init()
            if self._fwd is None:
                self._build()

    def _resolve_metrics(self, cache_attr, build):
        """Shared resolve-and-cache for hot-loop metric families (this
        collector and the GenerationServer scheduler) — the ONE memo
        rule lives in `monitor.resolve_cached_metrics`."""
        from deeplearning4j_tpu import monitor
        return monitor.resolve_cached_metrics(self, cache_attr, build)

    def _metrics(self):
        """The coalescing signal plane (ROADMAP names these as the
        shedding inputs)."""
        return self._resolve_metrics("_metrics_by_registry", lambda reg: (
            reg.timer("inference_request_latency_seconds",
                      "enqueue-to-result latency per output_async "
                      "request"),
            reg.gauge("inference_queue_depth",
                      "requests waiting to join a coalesced batch"),
            reg.histogram("inference_batch_size",
                          "rows per executed device batch",
                          buckets=(1, 2, 4, 8, 16, 32, 64, 128,
                                   256, 512))))

    def _bucket(self, n: int) -> int:
        mesh_n = self.mesh.shape[self.data_axis]
        for b in self._buckets:
            if b >= n and b % mesh_n == 0:
                return b
        return ((n + mesh_n - 1) // mesh_n) * mesh_n

    def output(self, x):
        """Single-call inference; pads the batch to a bucket size that
        divides the mesh, trims the result."""
        self._ensure_built()
        model = self.model
        x = np.asarray(x)
        n = x.shape[0]
        b = self._bucket(n)
        if b != n:
            pad = np.zeros((b - n,) + x.shape[1:], x.dtype)
            x = np.concatenate([x, pad], axis=0)
        out = self._fwd(model.params, model.net_state, jnp.asarray(x))
        return np.asarray(out)[:n]

    # -------------------------------------------- background batching loop
    def start(self) -> "ParallelInference":
        """Start the collector thread: concurrent `output()` callers are
        coalesced into one device batch within `queue_limit_ms`
        (reference `ObservablesProvider` :84 — requests observable until
        the batch fires)."""
        if getattr(self, "_shutdown", False):
            raise RuntimeError("ParallelInference is shut down")
        if self._running:
            return self
        self._ensure_built()
        self._running = True
        self._collector = threading.Thread(target=self._collect_loop,
                                           daemon=True)
        self._collector.start()
        return self

    def stop(self):
        self._running = False
        if self._collector is not None:
            self._queue.put(None)  # wake the collector
            self._collector.join(timeout=5)
            self._collector = None
        self._fail_pending()

    def _fail_pending(self):
        """Drain requests that never made it into a batch: leaving
        their Futures unresolved would hang callers blocked in
        `.result()`."""
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None and not item[1].done():
                item[1].set_exception(
                    RuntimeError("ParallelInference stopped before this "
                                 "request was executed"))

    def shutdown(self):
        """Terminal teardown: stop the collector thread, fail every
        pending Future, and refuse further `output_async` calls. Unlike
        `stop()` (which a later `start()` can undo), shutdown closes
        the enqueue side FIRST, so a request racing with teardown
        either gets the terminal error immediately or is drained and
        failed — nothing can hang at `.result()`."""
        self._shutdown = True
        self.stop()
        # a racing output_async may have enqueued between the drain and
        # the flag becoming visible — sweep once more
        self._fail_pending()

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.stop()

    def output_async(self, x) -> Future:
        """Enqueue one request; the Future resolves with this request's
        rows once the coalesced batch it joined has executed."""
        if getattr(self, "_shutdown", False):
            raise RuntimeError("ParallelInference is shut down")
        if not self._running:
            raise RuntimeError("call start() before output_async()")
        fut: Future = Future()
        self._queue.put((np.asarray(x), fut, time.monotonic()))
        # enqueue/teardown race: shutdown() may have completed between
        # the flag check and the put — no collector will ever drain this
        # request, so fail it ourselves (the queue is the sync point; a
        # request the collector DID take resolves normally)
        if getattr(self, "_shutdown", False):
            self._fail_pending()
        return fut

    def _collect_loop(self):
        while self._running:
            m = self._metrics()
            if m is not None:
                m[1].set(self._queue.qsize())
            try:
                first = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            if first is None:
                continue
            batch = [first]
            total = first[0].shape[0]
            deadline = time.monotonic() + self.queue_limit_ms / 1000.0
            while total < self.batch_limit:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is None:
                    break
                batch.append(nxt)
                total += nxt[0].shape[0]
            self._execute(batch)

    def _execute(self, batch):
        futs = [item[1] for item in batch]
        try:
            n_rows = sum(item[0].shape[0] for item in batch)
            self.batch_size_history.append(n_rows)
            outs = self.output_batched([item[0] for item in batch])
            done_t = time.monotonic()
            # collector-thread metric emission: wall-clock math on
            # already-materialized host arrays — ZERO added device syncs
            # (the monitor overhead contract, docs/OBSERVABILITY.md)
            m = self._metrics()
            if m is not None:
                m[2].observe(n_rows)
            for item, o in zip(batch, outs):
                item[1].set_result(o)
                if m is not None and len(item) > 2:
                    m[0].observe(done_t - item[2])
        except Exception as e:  # propagate to every waiting caller
            for f in futs:
                if not f.done():
                    f.set_exception(e)

    def output_batched(self, requests: List[np.ndarray]):
        """Coalesce many requests into one device batch (ObservablesProvider
        semantics) and split the results back out."""
        sizes = [np.asarray(r).shape[0] for r in requests]
        merged = np.concatenate([np.asarray(r) for r in requests], axis=0)
        out = self.output(merged)
        result, off = [], 0
        for s in sizes:
            result.append(out[off:off + s])
            off += s
        return result
