"""Continuous-batching generation server.

The request plane of the serving tier (the device programs live in
serving/engine.py): `GenerationServer` EXTENDS `ParallelInference` —
same request queue, Future resolution, start/stop/shutdown lifecycle
and drain-on-teardown semantics — but replaces the coalesce-one-batch
collector with a continuous-batching scheduler: every loop iteration
admits newly queued prompts into free slots (prefill), advances ALL
active slots one token (one jitted dispatch), streams the new tokens
out per request, and retires finished/cancelled sequences so their
pool blocks serve the next admission. A single long generation no
longer blocks the batch — this is what TF-Serving's async batching
added on top of the TF runtime (PAPERS.md §serving), rebuilt over a
paged KV pool.

SLO-aware shedding: with `slo_ttft_s` set, a request whose PROJECTED
queue delay (outstanding decode work / measured token throughput)
exceeds the SLO is fast-failed with `ShedError` at admission time
instead of queueing into certain lateness; `max_queue` is the hard
backstop when no throughput estimate exists yet. Both fire the
`serving_shed_total` counter — the registry is the signal plane
(docs/OBSERVABILITY.md "Serving").
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Iterator, List, Optional

import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor.flightrec import GLOBAL_FLIGHT_RECORDER
from deeplearning4j_tpu.monitor.goodput import (
    GOODPUT_COUNTER_FAMILIES, GOODPUT_FRACTION_GAUGE, ttft_decomposition)
from deeplearning4j_tpu.monitor.reqtrace import RequestTrace
from deeplearning4j_tpu.monitor.slo import SLOObjective, SLOTracker
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.serving.engine import PagedDecodeEngine
from deeplearning4j_tpu.serving.paged import blocks_needed

_DONE = object()


class ShedError(RuntimeError):
    """Request fast-failed by the SLO admission policy (shed, not
    queued): retry against another replica or with backoff."""


class ServerDrainingError(RuntimeError):
    """Admission refused because the server is draining (`drain()` —
    the hot-swap handoff): in-flight streams finish, new requests
    belong on the successor. A `FleetRouter` retries against the
    freshly-resolved active server; direct callers should re-resolve."""


class ServerStoppedError(RuntimeError):
    """`start()` after `stop()`: a stopped GenerationServer's engine
    has failed its in-flight streams and retired their slots —
    restarting the scheduler over that state would corrupt the
    allocator bookkeeping. Build a fresh server instead."""


class TokenStream:
    """Per-request token stream: iterate for tokens as they decode, or
    block on `result()` for the full array (the Future face —
    `ParallelInference.output_async` compatibility)."""

    def __init__(self, fut, prompt_len: int, n_tokens: int,
                 on_close=None):
        self._fut = fut
        self._q: "queue.Queue" = queue.Queue()
        self.prompt_len = prompt_len
        self.n_tokens = n_tokens
        self.tokens: List[int] = []
        self.cancelled = False
        # per-request lifecycle trace (None when monitoring is off and
        # no upstream trace context arrived): the scheduler stamps
        # phases onto it; finish/fail seal it
        self.trace: Optional[RequestTrace] = None
        self.t_submit = time.monotonic()
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        # close hook (fires exactly once, on finish OR failure): the
        # server's open-stream accounting — what makes drain() a
        # zero-dropped-streams barrier instead of a scheduler-state
        # guess (a request between queue.get and _pending.append is
        # visible nowhere else)
        self._on_close = on_close
        self._closed = False

    # ------------------------------------------------------------ consumer
    def __iter__(self) -> Iterator[int]:
        while True:
            item = self._q.get()
            if item is _DONE:
                # surface a shed/teardown error to iterating consumers
                # too, not only result() callers
                exc = self._fut.exception(timeout=0)
                if exc is not None and not self.cancelled:
                    raise exc
                return
            # tokens arrive in per-dispatch batches: one queue wakeup
            # per CHUNK, not per token — with many iterating consumer
            # threads, per-token wakeups were measured to collapse
            # aggregate throughput ~20x (GIL convoy against the
            # scheduler thread)
            yield from item

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Full generated-id array [n_emitted]; raises ShedError /
        teardown errors like a Future."""
        return self._fut.result(timeout)

    def cancel(self):
        """Evict this request mid-stream: the scheduler frees its slot
        and pool blocks at the next loop iteration; `result()` resolves
        with the tokens emitted so far."""
        self.cancelled = True

    # ----------------------------------------------------------- producer
    def _emit(self, token: int, now: float):
        self._emit_many([token], now)

    def _emit_many(self, toks, now: float):
        if not toks:
            return
        if self.t_first is None:
            self.t_first = now
        self.t_last = now
        toks = [int(t) for t in toks]
        self.tokens.extend(toks)
        self._q.put(toks)

    def _close(self):
        if self._closed:
            return
        self._closed = True
        if self._on_close is not None:
            self._on_close()

    def _finish(self):
        if not self._fut.done():
            self._fut.set_result(np.asarray(self.tokens, np.int32))
        self._q.put(_DONE)
        if self.trace is not None:
            # idempotent: the scheduler's richer finish (ttft/slo args)
            # already sealed it on the normal path
            self.trace.finish(
                status="cancelled" if self.cancelled else "ok",
                tokens=len(self.tokens))
        self._close()

    def _fail(self, exc: BaseException):
        if not self._fut.done():
            self._fut.set_exception(exc)
        self._q.put(_DONE)
        if self.trace is not None:
            self.trace.finish(
                status="shed" if isinstance(exc, ShedError) else "error",
                error=type(exc).__name__)
        self._close()


class _Request:
    __slots__ = ("prompt", "n_tokens", "temperature", "top_p", "rng",
                 "stream", "slot", "emit_base")

    def __init__(self, prompt, n_tokens, temperature, top_p, rng, stream,
                 emit_base: int = 0):
        self.prompt = prompt
        self.n_tokens = n_tokens
        self.temperature = temperature
        self.top_p = top_p
        self.rng = rng
        self.stream = stream
        self.slot = None
        # rng fold offset carried in from OUTSIDE this server: a
        # cross-replica continuation (migration after a replica died
        # mid-stream) arrives as prompt+received with emit_start =
        # tokens already emitted elsewhere — sampling must keep folding
        # at the original stream's positions, not restart at 0
        self.emit_base = int(emit_base)

    # ---- preempt-and-requeue continuation (incremental allocation):
    # a pool-pressure eviction re-admits the request as its original
    # prompt EXTENDED by every token already streamed, generating only
    # the remainder — greedy continuations are bit-consistent (prefill
    # of the extended prompt reproduces the decode-path numerics, the
    # parity contract) and sampled ones keep their fold_in(key, t)
    # indices via emit_start.
    def effective_prompt(self):
        import numpy as np
        done = self.stream.tokens
        if not done:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(done, self.prompt.dtype)])

    @property
    def emitted(self) -> int:
        return len(self.stream.tokens)

    @property
    def n_left(self) -> int:
        return self.n_tokens - self.emitted


class GenerationServer(ParallelInference):
    """Continuous-batching autoregressive serving over a paged KV pool.

    `generate_async(prompt, n_tokens) -> TokenStream` from any thread;
    the scheduler thread (started by `start()`, the inherited
    lifecycle) owns the engine. `top_k` is server-static (one XLA
    decode program); temperature/top_p/rng are per-request.
    """

    def __init__(self, net, *, n_slots: int = 8, n_blocks: int = 64,
                 block_len: int = 16, top_k: Optional[int] = None,
                 steps_per_dispatch: int = 1,
                 slo_ttft_s: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 idle_wait_s: float = 0.05,
                 dispatch_floor_s: Optional[float] = None,
                 quantize: Optional[str] = None,
                 allocation: str = "incremental",
                 speculative: Optional[int] = None,
                 spec_accept_floor: float = 0.3,
                 spec_probe_every: int = 50,
                 spec_sampled: bool = False,
                 spec_draft_layers: Optional[int] = None,
                 prefix_cache: str = "registered",
                 max_positions: Optional[int] = None,
                 max_prefill_tokens: Optional[int] = None,
                 min_prefill_bucket: int = 1,
                 window_blocks: Optional[int] = None,
                 name: Optional[str] = None,
                 slo: Optional[SLOObjective] = None):
        super().__init__(net)
        # optional server label: `serving_*` families carry
        # `server=<name>` so two servers in one process (a fleet) don't
        # collide; the single-server path stays unlabeled (PR-12 note)
        self.name = name
        # optional SLO objective: good/bad counters + burn-rate gauge
        # evaluated per finished request (shed counts as bad)
        self._slo_tracker = (SLOTracker(slo, model=name or "default")
                            if slo is not None else None)
        self._slo_cache = None
        # shed-burst flight-recorder rate limit (≤1 event/s)
        self._shed_recent = 0
        self._shed_last_emit = 0.0
        self.engine = PagedDecodeEngine(
            net, n_slots=n_slots, n_blocks=n_blocks, block_len=block_len,
            top_k=top_k, steps_per_dispatch=steps_per_dispatch,
            quantize=quantize, allocation=allocation,
            speculative=speculative, spec_sampled=spec_sampled,
            spec_draft_layers=spec_draft_layers,
            prefix_cache=prefix_cache, max_positions=max_positions,
            max_prefill_tokens=max_prefill_tokens,
            min_prefill_bucket=min_prefill_bucket,
            window_blocks=window_blocks)
        self._metrics_cache = None
        # speculative-decoding policy: drafting is only worth its
        # k-wide scoring dispatch while the proposer's tokens actually
        # get accepted — the scheduler tracks an acceptance-rate EWMA
        # and falls back to the chunked decode program when it sinks
        # below `spec_accept_floor`, re-probing one speculative
        # dispatch every `spec_probe_every` dispatches so a workload
        # shift (e.g. traffic turning repetitive again) re-enables it
        self.spec_accept_floor = float(spec_accept_floor)
        self.spec_probe_every = max(1, int(spec_probe_every))
        self._spec_accept_ewma: Optional[float] = None
        self._spec_tpd_ewma: Optional[float] = None
        self._spec_disabled = False
        self._spec_probe_in = 0
        self._spec_proposed_seen = 0
        self._spec_accepted_seen = 0
        self._spec_emitted_seen = 0
        self._spec_dispatches_seen = 0
        # per-proposer arbitration: separate acceptance EWMAs so a
        # collapsed n-gram cache (non-repetitive traffic) hands the
        # drafting seam to the truncated-layer backend instead of
        # disabling speculation outright; the global EWMA/latch above
        # stays authoritative for the enable/disable decision
        self._spec_prop_ewma = {"ngram": None, "truncated": None}
        self._spec_prop_seen = {"ngram": (0, 0), "truncated": (0, 0)}
        self._prefix_hits_seen = 0
        self._prefix_saved_seen = 0
        # radix-cache counter mirrors (radix mode only)
        self._radix_hits_seen = 0
        self._radix_evict_seen = 0
        # goodput-ledger mirror cursors (one per classification class)
        self._goodput_seen = {}
        # prefix registrations from foreign threads ride a control
        # queue the scheduler drains at each loop top (the engine is
        # single-threaded by contract); before start() they apply
        # directly
        self._control: "queue.Queue" = queue.Queue()
        self.slo_ttft_s = slo_ttft_s
        self.max_queue = max_queue
        self.idle_wait_s = idle_wait_s
        # emulated device-step latency floor (sandbox/test seam): each
        # decode dispatch takes at least this long, with the host
        # sleeping out the remainder as if the accelerator owned the
        # step. On a CPU-only sandbox this reproduces the device-bound
        # serving regime (host idle inside the step) that replica
        # fan-out and SLO tests are really about — it must never be
        # set in production serving, so setting it requires the
        # explicit sandbox opt-in (DL4J_SANDBOX_MODEL=1): a copied
        # loadtest config can otherwise silently cap a production
        # server's throughput at 1/dispatch_floor_s dispatches/s.
        if dispatch_floor_s is not None \
                and os.environ.get("DL4J_SANDBOX_MODEL") != "1":
            raise ValueError(
                "dispatch_floor_s emulates device-step latency and is "
                "a sandbox-only seam — it must never be set in "
                "production serving. Set DL4J_SANDBOX_MODEL=1 to "
                "acknowledge this is a sandbox/loadtest process.")
        self.dispatch_floor_s = (None if dispatch_floor_s is None
                                 else float(dispatch_floor_s))
        self._pending: List = []          # admission order, after _queue
        self._slot2req = {}
        # shedding estimator: EWMA of aggregate decode throughput
        self._ewma_tok_s: Optional[float] = None
        # counter mirrors: the engine keeps host ints (it has no
        # registry); the scheduler publishes the deltas each loop
        self._grants_seen = 0
        self._requeue_seen = 0
        # seconds of the current scheduler iteration inside the
        # `serve/admit` and `serve/decode` spans that their timers
        # observe: the rest of `serve/loop` is the scheduler's own
        self._dispatch_s = 0.0
        # seconds of `serve/decode` spans that launched a step and read
        # none, until the step's own span is observed
        self._launch_s = 0.0
        # lifecycle: draining refuses admissions while in-flight
        # streams finish (the hot-swap handoff); stopped is terminal
        self._draining = False
        self._stopped = False
        self._open_streams = 0
        self._queued_tokens = 0
        self._open_lock = threading.Lock()

    # ---------------------------------------------------- open-stream book
    def _stream_closed(self):
        with self._open_lock:
            self._open_streams -= 1

    @property
    def open_streams(self) -> int:
        """Streams submitted and not yet finished/failed — counted at
        the TokenStream close hook, so a request is visible here from
        `generate_async` until its future resolves (including the
        scheduler-internal limbo between queue and pending list)."""
        with self._open_lock:
            return self._open_streams

    @property
    def queued_tokens(self) -> int:
        """Tokens owed by requests still in the SUBMIT queue (not yet
        taken by the scheduler): a running counter — incremented at
        `generate_async`, decremented when the scheduler (or teardown)
        takes the item — so an external projected-delay estimator (the
        FleetRouter) reads it O(1) instead of copying the queue under
        its mutex on every submit."""
        with self._open_lock:
            return max(0, self._queued_tokens)

    def queue_depth(self) -> int:
        """Requests awaiting admission: the submit queue plus the
        scheduler's pending list — the same value the
        `serving_queue_depth` gauge publishes, as a public seam so the
        autoscaler's live fallback and the router's shed estimator
        don't reach into scheduler internals. Lock-free reads of two
        thread-safe sizes; may be one scheduler iteration stale."""
        return len(self._pending) + self._queue.qsize()

    def _queue_item_taken(self, item):
        """Bookkeeping for every item removed from `_queue` (None
        sentinels excluded — they were never counted)."""
        if item is None:
            return
        with self._open_lock:
            self._queued_tokens -= int(getattr(item[0], "n_tokens", 0))

    def output_async(self, x):
        """Not supported here: the scheduler queue carries generation
        requests, not raw feature batches — a ParallelInference-style
        enqueue would poison the scheduler loop. Use `generate_async`
        (token streams) or a separate `ParallelInference` for
        single-shot forwards."""
        raise NotImplementedError(
            "GenerationServer serves token streams: use "
            "generate_async(prompt_ids, n_tokens); for single-shot "
            "batched forwards use ParallelInference")

    # ------------------------------------------------------ shared prefix
    def register_prefix(self, token_ids, *, timeout: Optional[float] = 600.0
                        ) -> tuple:
        """Warm a shared prompt prefix (system prompt) into the paged
        pool ONCE: later requests whose prompt starts with these ids
        map the warmed blocks copy-on-write instead of re-prefilling
        them (`PagedDecodeEngine.register_prefix`; docs/SERVING.md).
        Thread-safe: before `start()` the registration applies
        directly (the usual deploy order — register, `warmup()`,
        `start()` — so warmup can pre-compile the suffix-extension
        programs); on a RUNNING server it rides a control queue the
        scheduler drains, and this call blocks until applied."""
        if getattr(self, "_shutdown", False) or self._stopped:
            raise RuntimeError("GenerationServer is shut down")
        if not self._running:
            return self.engine.register_prefix(token_ids)
        from concurrent.futures import Future
        fut = Future()
        self._control.put(("register_prefix", token_ids, fut))
        # re-check teardown AFTER the put: a stop() landing between the
        # checks above and the enqueue has already drained the control
        # queue — our item would sit unresolved forever. Draining once
        # more here races benignly with the scheduler (get_nowait on
        # both sides) and guarantees the future resolves either way.
        if self._stopped or getattr(self, "_shutdown", False) \
                or not self._running:
            self._fail_control()
        return fut.result(timeout)

    def _drain_control(self, eng) -> bool:
        progressed = False
        while True:
            try:
                op, arg, fut = self._control.get_nowait()
            except queue.Empty:
                return progressed
            progressed = True
            try:
                if op == "register_prefix":
                    fut.set_result(eng.register_prefix(arg))
                else:
                    raise ValueError(f"unknown control op {op!r}")
            except Exception as e:  # noqa: BLE001 — surfaced to caller
                if not fut.done():
                    fut.set_exception(e)

    # ------------------------------------------------------------- warmup
    def warmup(self, prompt_len: int, n_tokens: int = 2):
        """Compile the serving programs OUTSIDE the serving path: the
        full (wave-width-pow2 x prompt-length-bucket) program grid up
        to the slot count and `bucket_len(prompt_len)` — async arrival
        means real waves take EVERY quantized width, mixed-length
        traffic takes every length bucket, and each (width, bucket)
        pair is its own batched-prefill program (the admit_finish and
        decode programs key on width alone). Call BEFORE start() — an
        XLA compile inside a live admission wave stalls every queued
        request behind ~seconds of tracing (measured as a p50==p99
        TTFT cliff on the CPU sandbox; stack sampling showed the
        scheduler thread pinned in backend_compile)."""
        from deeplearning4j_tpu.serving.engine import bucket_len
        if self._running:
            raise RuntimeError("warmup() must run before start()")
        eng = self.engine
        n_tokens = max(2, int(n_tokens))
        self.engine.check_budget(int(prompt_len), n_tokens)
        widths = []
        w = 1
        while w < eng.n_slots:
            widths.append(w)
            w *= 2
        widths.append(eng.n_slots)
        top_bucket = eng._bucket(int(prompt_len))
        buckets = []
        b = eng.min_prefill_bucket
        while b <= top_bucket:
            buckets.append(b)
            b *= 2
        if buckets[-1] != top_bucket:
            buckets.append(top_bucket)     # budget-clamped odd bucket
        # each (width, bucket) warms BOTH admit variants (all-greedy
        # and the sampling chain) — a mixed wave keys a different
        # program — and the first sampled wave also compiles the
        # sampled decode chunk, so a temperature>0 request never
        # stalls live streams on a mid-serving trace. Prefix matching
        # is suspended for the grid: a registered prefix that happens
        # to match the synthetic zero prompts would route these waves
        # through the CoW path and leave the REAL full-prefill
        # programs cold for live traffic of that shape.
        saved_prefixes, eng._prefixes = eng._prefixes, {}
        # the radix cache is suspended for the same reason — and so the
        # grid's synthetic zero prompts don't seed the tree with
        # garbage-content nodes real traffic would then "hit"
        saved_radix, eng._radix = eng._radix, None
        short_wave = None      # narrowest under-admitted wave seen
        # goodput: everything the compile grid dispatches is warmup
        # class — the ledger stays monotone (no counter reset here, so
        # registry mirrors never see negative deltas) while the useful
        # fraction keeps counting real traffic only
        eng.goodput.set_mode("warmup")
        try:
            for k in widths:
                for pl in buckets:
                    if (eng.max_prefill_tokens is not None
                            and k * pl > eng.max_prefill_tokens):
                        # admission is bounded in tokens: the engine
                        # never builds this (width, bucket) program
                        continue
                    # a bucket rounded past the prompt may leave less
                    # token headroom than requested — admission-only
                    # warmup (n=1) still compiles that bucket's
                    # prefill/admit programs
                    pw = int(pl)
                    n_b = min(n_tokens, eng.max_total_tokens - pw)
                    if n_b < 1:
                        # the budget-clamped TOP bucket: a one-shorter
                        # prompt still PADS to this bucket, so the same
                        # (width, bucket) prefill program compiles — a
                        # real budget-edge request must not be the first
                        # to trace it
                        pw, n_b = pw - 1, 1
                        if pw < 1:
                            continue
                    for sampled_head in (False, True):
                        reqs = [dict(prompt_ids=np.zeros(pw, np.int32),
                                     n_tokens=n_b)
                                for _ in range(k)]
                        if sampled_head:
                            reqs[0].update(temperature=1.0,
                                           rng=np.zeros(2, np.uint32))
                        admitted = eng.admit_many(reqs)
                        while eng.active.any():
                            # speculate=False: the grid warms the
                            # CHUNKED decode programs — the accept-rate
                            # fallback path must be as cold-start-free
                            # as the speculative one (warmed below)
                            eng.step(speculate=False)
                        eng.drain_preempted()  # warmup traffic isn't real
                        for slot, _, done in admitted:
                            if not done and eng.slots[slot] is not None:
                                eng.evict(slot)
                        if len(admitted) < k and short_wave is None:
                            short_wave = (len(admitted), k)
                if short_wave is not None:
                    # pool too small for this width (at SOME bucket)
                    # even at warmup's minimal n_tokens — real waves of
                    # this width compile mid-serving if requests ever
                    # need fewer blocks each
                    import logging
                    logging.getLogger(__name__).warning(
                        "warmup admitted only %d of a width-%d wave "
                        "(pool %d blocks): wave widths above %d are NOT "
                        "fully pre-compiled — grow n_blocks or expect a "
                        "one-off compile stall on the first wider wave",
                        short_wave[0], short_wave[1], eng.pool.n_blocks,
                        short_wave[0])
                    break
        finally:
            eng._prefixes = saved_prefixes
            eng._radix = saved_radix
            eng.goodput.set_mode(None)
        import jax.numpy as jnp
        # speculative + shared-prefix programs: the K-position score
        # program (both sampling variants), the CoW fork copy, and the
        # exact-match first-token sampler — compiled via DEAD dispatches
        # (n_valid all zero / garbage-to-garbage copies), which write
        # only the garbage block and leave every pool invariant intact
        score_ks = []
        if eng.spec_k:
            score_ks.append(eng.spec_k)
        if eng.has_prefixes or eng._radix is not None:
            # suffix-extension buckets: every pow2 up to the prompt
            # bucket (a hit's suffix is at most prompt minus prefix) —
            # radix hits ride the same suffix-extension score programs
            b = 1
            while b <= bucket_len(int(prompt_len), eng.max_total_tokens):
                score_ks.append(b)
                b *= 2
        S = eng.n_slots
        for K in sorted(set(score_ks)):
            variants = [True, False]
            if eng.spec_sampled and eng.spec_k and K == eng.spec_k:
                # rejection-sampling score variant (sampled streams)
                variants.append("rs")
            for variant in variants:
                score = eng._get_score(K, variant)
                eng.pool.kv = score(
                    eng._params, eng.net.net_state, eng.pool.kv,
                    eng._tables_arg(),
                    jnp.zeros((S, K), jnp.int32),
                    jnp.zeros(S, jnp.int32), jnp.zeros(S, jnp.int32),
                    jnp.zeros((S, 2), jnp.uint32),
                    jnp.zeros(S, jnp.int32), jnp.zeros(S, jnp.float32),
                    jnp.ones(S, jnp.float32))[0]
        if eng._draft_plan is not None:
            # truncated-layer draft program: a dead dispatch (every
            # table row garbage) compiles the k-1 micro-step scan
            eng.goodput.set_mode("warmup")
            try:
                eng._run_draft([])
            finally:
                eng.goodput.set_mode(None)
        if eng.has_prefixes:
            # fork widths up to a full wave of mid-block tails (every
            # admission in a wave can fork one) — garbage self-copies
            w = 1
            while True:
                self.engine._run_fork([(0, 0)] * w)
                if w >= S:
                    break
                w *= 2
            vocab = getattr(eng.net.layers[-1], "n_out", 0)
            # pow2 CEIL of the slot count (like the fork loop above):
            # a 5-wide exact-match wave on a 6-slot server pads to
            # width 8 — `while w <= S` would leave that width to
            # compile mid-serving, the TTFT-cliff class warmup exists
            # to prevent
            w = 1
            while True:
                for greedy in (True, False):
                    fn = eng._first_token.get(greedy)
                    if fn is None:
                        fn = eng._first_token[greedy] = \
                            eng._build_first_token(greedy)
                    fn(jnp.zeros((w, vocab),
                                 eng.net.dtype.compute_dtype),
                       jnp.zeros((w, 2), jnp.uint32),
                       jnp.zeros(w, jnp.int32),
                       jnp.zeros(w, jnp.float32), jnp.ones(w, jnp.float32))
                if w >= S:
                    break
                w *= 2
        # the warmup grid's grants/preemptions are not serving traffic:
        # reset the engine totals so the registry deltas (_drain) and
        # ledger reads count real requests only (prefix pins and their
        # hit/fork counters predate traffic too)
        eng.block_grants_total = 0
        eng.evict_requeue_total = 0
        eng.prefix_forks_total = 0
        eng.prefix_hits_total = 0
        eng.prefix_tokens_saved_total = 0
        eng.spec_draft_dispatches_total = 0
        eng.radix_hit_tokens_total = 0
        eng.radix_evictions_total = 0
        return self

    # ------------------------------------------------------------- submit
    def generate_async(self, prompt_ids, n_tokens: int, *,
                       temperature: float = 0.0,
                       top_p: Optional[float] = None,
                       rng=None, emit_start: int = 0,
                       trace: Optional[RequestTrace] = None) -> TokenStream:
        """Enqueue one generation request; returns its token stream.
        Eager validation (the `generate()` pattern): impossible
        requests fail HERE, not as a scheduler-thread error.

        `emit_start` is the continuation seam for CROSS-SERVER
        migration: a stream that died on another replica after K tokens
        resubmits as prompt+received with ``emit_start=K`` — greedy
        continuations are bit-consistent by the parity contract and
        sampled ones keep their fold_in(key, position) indices, so the
        joined stream equals the uninterrupted one.

        `trace` carries upstream trace context (a router-side
        RequestTrace or one rehydrated from the wire); with monitoring
        enabled and no upstream context, a fresh trace is minted here —
        trace-off serving emits the same tokens bit-for-bit (tracing is
        host-side timestamps only, it never touches rng or devices)."""
        if getattr(self, "_shutdown", False):
            raise RuntimeError("GenerationServer is shut down")
        if self._draining:
            raise ServerDrainingError(
                "GenerationServer is draining: in-flight streams are "
                "finishing but admissions are closed — submit to the "
                "successor (FleetRouter re-resolves automatically)")
        if not self._running:
            raise RuntimeError("call start() before generate_async()")
        prompt = np.asarray(prompt_ids)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(f"prompt must be a non-empty 1-D id "
                             f"sequence; got shape {prompt.shape}")
        self.engine.check_budget(int(prompt.shape[0]), int(n_tokens),
                                 prompt_ids=prompt)
        if top_p is not None and not (0.0 < float(top_p) <= 1.0):
            raise ValueError(f"top_p must be in (0, 1]; got {top_p}")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0; got {temperature}")
        if temperature > 0 and rng is None:
            # every no-rng sampled request must draw a DISTINCT stream:
            # the engine's deterministic default (zero key) would make
            # concurrent same-prompt requests emit identical "samples".
            # Pass rng explicitly for a reproducible stream (the
            # fold-per-position contract, docs/SERVING.md).
            rng = np.frombuffer(os.urandom(8), np.uint32).copy()
        from concurrent.futures import Future
        fut = Future()
        stream = TokenStream(fut, int(prompt.shape[0]), int(n_tokens),
                             on_close=self._stream_closed)
        if trace is None and monitor.is_enabled():
            trace = RequestTrace(model=self.name)
        if trace is not None:
            trace.annotate(prompt_len=int(prompt.shape[0]),
                           n_tokens=int(n_tokens))
            if trace.model is None:
                trace.model = self.name
        stream.trace = trace
        with self._open_lock:
            # re-check the drain flag ATOMICALLY with the open-stream
            # increment: drain() sets the flag and reads the count
            # under this same lock, so a submit either increments
            # before drain reads (drain waits for it) or sees the flag
            # and raises — it can never slip a request into a server
            # drain already declared empty (the stream would hang
            # unserviced after the subsequent stop())
            if self._draining:
                raise ServerDrainingError(
                    "GenerationServer is draining: in-flight streams "
                    "are finishing but admissions are closed — submit "
                    "to the successor (FleetRouter re-resolves "
                    "automatically)")
            self._open_streams += 1
            self._queued_tokens += int(n_tokens)
        req = _Request(prompt.astype(np.int64), int(n_tokens),
                       float(temperature), top_p, rng, stream,
                       emit_base=int(emit_start))
        self._queue.put((req, fut, stream.t_submit))
        if getattr(self, "_shutdown", False):
            self._fail_pending()
        return stream

    # -------------------------------------------- queued-request migration
    def export_queued(self) -> List:
        """Take every QUEUED-BUT-UNSTARTED request out of the submit
        queue for migration to another server (the hot-swap successor,
        or a less-loaded replica). Only the submit queue is exported —
        requests the scheduler has already seen (pending list, live
        slots) have state on THIS server and finish here; a queued item
        has emitted nothing, so it moves wholesale. Thread-safe against
        a running scheduler: both sides drain the same thread-safe
        queue, so each item lands exactly once — here or in a slot,
        never both. Returns opaque items for `adopt_queued`."""
        items = []
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            self._queue_item_taken(item)
            if item is None:
                continue
            items.append(item)
        if items:
            # the streams remain OPEN (their consumers keep waiting) but
            # no longer this server's liability: drain() must not block
            # on requests another server now owes
            with self._open_lock:
                self._open_streams -= len(items)
        return items

    def adopt_queued(self, items) -> int:
        """Adopt requests exported from another server's queue: each
        stream object is re-owned wholesale — same TokenStream, same
        consumer-held future, new server on the hook for it (the close
        hook rebinds, so open-stream accounting follows the request).
        Returns the number adopted."""
        if not items:
            return 0
        if getattr(self, "_shutdown", False) or self._stopped:
            raise RuntimeError("GenerationServer is shut down")
        if self._draining:
            raise ServerDrainingError(
                "cannot adopt migrated requests into a draining server")
        for item in items:
            req = item[0]
            req.stream._on_close = self._stream_closed
            tr = req.stream.trace
            if tr is not None:
                tr.event("migrated", to=self.name)
            with self._open_lock:
                self._open_streams += 1
                self._queued_tokens += int(req.n_tokens)
            self._queue.put(item)
        return len(items)

    # ------------------------------------------------------------ metrics
    def _serving_metrics(self):
        return self._resolve_metrics("_metrics_cache",
                                     self._build_serving_metrics)

    def _build_serving_metrics(self, reg):
        # optional `server=` label (satellite of PR 16): two servers in
        # one process (the fleet path) get distinct children; a
        # name-less server keeps the original unlabeled series
        lbl = {"server": self.name} if self.name else {}
        fams = {
            "queue": reg.gauge("serving_queue_depth",
                               "generation requests awaiting admission",
                               **lbl),
            "slots": reg.gauge("serving_active_slots",
                               "serving slots decoding right now", **lbl),
            "blocks": reg.gauge("serving_free_blocks",
                                "free KV-pool blocks", **lbl),
            "requests": reg.counter("serving_requests_total",
                                    "generation requests admitted",
                                    **lbl),
            "tokens": reg.counter("serving_tokens_total",
                                  "tokens emitted by the decode loop",
                                  **lbl),
            "shed": reg.counter("serving_shed_total",
                                "requests fast-failed by the SLO "
                                "admission policy", **lbl),
            "evicted": reg.counter("serving_evicted_total",
                                   "sequences evicted mid-stream", **lbl),
            "pool_free": reg.gauge("serving_pool_blocks_free",
                                   "free KV-pool blocks (allocator "
                                   "view)", **lbl),
            "pool_used": reg.gauge("serving_pool_blocks_used",
                                   "granted KV-pool blocks", **lbl),
            "grants": reg.counter("serving_block_grants_total",
                                  "pool blocks granted (admission + "
                                  "lazy decode growth)", **lbl),
            "requeue": reg.counter("serving_evict_requeue_total",
                                   "pool-pressure preemptions requeued "
                                   "as continuations", **lbl),
            "spec_accept": reg.gauge(
                "serving_spec_accept_rate",
                "EWMA of the draft-token acceptance rate (speculative "
                "decoding; drives the auto-disable policy)", **lbl),
            "spec_tpd": reg.gauge(
                "serving_spec_tokens_per_dispatch",
                "EWMA of tokens emitted per speculative dispatch",
                **lbl),
            "prefix_shared": reg.gauge(
                "serving_prefix_blocks_shared",
                "pool blocks currently mapped by more than one holder "
                "(shared-prefix CoW)", **lbl),
            "prefix_hits": reg.counter(
                "serving_prefix_hits_total",
                "admissions that mapped a registered shared prefix "
                "instead of prefilling it", **lbl),
            "prefix_saved": reg.counter(
                "serving_prefix_tokens_saved_total",
                "prompt tokens NOT prefilled thanks to shared-prefix "
                "block reuse", **lbl),
            "spec_accept_by": {
                p: reg.gauge(
                    "serving_spec_accept_rate",
                    "EWMA of the draft-token acceptance rate (speculative "
                    "decoding; drives the auto-disable policy)",
                    proposer=p, **lbl)
                for p in ("ngram", "truncated")},
            "spec_proposed_by": {
                p: reg.counter(
                    "serving_spec_proposed_total",
                    "draft tokens offered to the verify dispatch",
                    proposer=p, **lbl)
                for p in ("ngram", "truncated")},
            "spec_accepted_by": {
                p: reg.counter(
                    "serving_spec_accepted_total",
                    "draft tokens accepted by the verify dispatch",
                    proposer=p, **lbl)
                for p in ("ngram", "truncated")},
            "radix_nodes": reg.gauge(
                "serving_radix_nodes",
                "radix prefix-cache tree nodes currently held", **lbl),
            "radix_hits": reg.counter(
                "serving_radix_hit_tokens_total",
                "prompt tokens matched in the radix prefix cache "
                "instead of prefilled", **lbl),
            "radix_evict": reg.counter(
                "serving_radix_evictions_total",
                "radix prefix-cache nodes evicted under pool pressure",
                **lbl),
            "ttft": reg.timer("serving_ttft_seconds",
                              "submit-to-first-token latency", **lbl),
            "tpot": reg.timer("serving_tpot_seconds",
                              "mean per-token decode latency per "
                              "finished request", **lbl),
            "step": reg.timer("serving_step_seconds",
                              "one continuous-batching decode dispatch",
                              **lbl),
            # the loop from inside, observed from the `serve/*` spans'
            # own start and end: per iteration sched_host + admit_wave
            # + decode_host + decode_wait is the `serve/loop` span
            "sched_host": reg.timer(
                "serving_sched_host_seconds",
                "one scheduler iteration less its admission waves and "
                "decode dispatch", **lbl),
            "decode_host": reg.timer(
                "serving_decode_host_seconds",
                "one decode dispatch's host work: block grants, uploads "
                "and the launch, slot bookkeeping", **lbl),
            "decode_wait": reg.timer(
                "serving_decode_wait_seconds",
                "one decode dispatch's host blocked on the readback",
                **lbl),
            "admit_wave": reg.timer(
                "serving_admit_wave_seconds",
                "one admission wave, first-token fan-out included",
                **lbl),
            "admit_wait": reg.timer(
                "serving_admit_wait_seconds",
                "one admission wave's host blocked on the readback",
                **lbl),
            "admit_waves": reg.counter(
                "serving_admit_waves_total",
                "admission waves dispatched", **lbl),
            "batch_slots": reg.histogram(
                "serving_decode_batch_slots",
                "active slots at each decode dispatch",
                buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256), **lbl),
            "sample_rows": reg.histogram(
                "serving_sample_rows",
                "rows the sampling chain ran over at each decode "
                "dispatch: the live slots with a temperature; 0 for a "
                "dispatch of the greedy twin",
                buckets=(0, 1, 2, 4, 8, 16, 32), **lbl),
            "kv_read_pct": reg.histogram(
                "serving_decode_kv_read_pct",
                "100 x pool blocks a decode dispatch's attention reads "
                "/ (n_slots x max_blocks): 100 where it gathers every "
                "slot's whole table",
                buckets=(1, 2, 5, 10, 20, 35, 50, 75, 100), **lbl),
            "weight_gb": reg.histogram(
                "serving_decode_weight_gb",
                "bytes / 1e9 of the params tree a decode dispatch's "
                "program was given: a mixed net's compute-dtype copy, "
                "the net's own tree otherwise",
                buckets=(0.001, 0.01, 0.1, 0.5, 1, 2, 4, 8, 16, 32),
                **lbl),
            "overlap_pct": reg.histogram(
                "serving_decode_overlap_pct",
                "at each decode launch, 100 if an earlier decode step "
                "was still unread (the device had work while the host "
                "did its own), 0 if the loop had drained",
                buckets=(0, 100), **lbl),
            "moe_rows": reg.histogram(
                "serving_moe_rows",
                "(token, expert) rows routed to the experts this server "
                "holds, a dispatch (decode or admission), mean over "
                "its routed expert layers",
                buckets=(1, 4, 16, 64, 256, 1024, 4096, 16384, 65536),
                **lbl),
            "moe_load": reg.histogram(
                "serving_moe_load_max_over_mean",
                "rows of the fullest held expert over the mean held "
                "expert's, a dispatch, mean over its routed expert "
                "layers",
                buckets=(1, 1.25, 1.5, 2, 3, 4, 8, 16, 32), **lbl),
            "positions_read": reg.counter(
                "serving_latent_positions_read",
                "cache positions the decode dispatches' attention read, "
                "summed over the paged layers (a gathering layer reads "
                "every slot's whole budget)", **lbl),
            "goodput_frac": reg.gauge(
                GOODPUT_FRACTION_GAUGE,
                "useful token-positions / dispatched token-positions "
                "(the goodput ledger's rolling fraction)", **lbl),
            "goodput": {
                c: reg.counter(
                    fam, f"dispatched token-positions classified "
                         f"{c} by the goodput ledger", **lbl)
                for c, fam in GOODPUT_COUNTER_FAMILIES.items()
            },
            "ttft_queue": reg.timer(
                "serving_ttft_queue_wait_seconds",
                "TTFT decomposition: submit to admission wave", **lbl),
            "ttft_prefill": reg.timer(
                "serving_ttft_prefill_seconds",
                "TTFT decomposition: the admission dispatch", **lbl),
            "ttft_emit": reg.timer(
                "serving_ttft_first_emit_seconds",
                "TTFT decomposition: prefill completion to the consumer "
                "seeing the first token", **lbl),
        }
        # acceptance gauges start at 1.0, not the registry's default 0:
        # "no evidence yet" must read healthy, or the default alert
        # pack's acceptance-collapse rule (min over series < floor)
        # fires on every freshly-built server before its first
        # speculative dispatch
        if self.engine.pool.window_allocator is not None:
            # a net with window layers keeps a second pool: the unlabeled
            # pool series are then the pool of the layers that keep
            # every position, these the window layers' rings
            fams["ring_free"] = reg.gauge(
                "serving_pool_blocks_free",
                "free KV-pool blocks (allocator view)", pool="window", **lbl)
            fams["ring_used"] = reg.gauge(
                "serving_pool_blocks_used", "granted KV-pool blocks",
                pool="window", **lbl)
            fams["window_held"] = reg.histogram(
                "serving_window_kv_held_pct",
                "100 x positions the window layers hold for the "
                "decoding slots / positions those slots have reached, "
                "a decode dispatch",
                buckets=(10, 20, 30, 40, 50, 60, 70, 80, 90, 100), **lbl)
        if self.engine.state_layers:
            # a net with layers that keep a state of fixed size a slot
            fams["state_gb"] = reg.histogram(
                "serving_decode_state_gb",
                "bytes / 1e9 of per-slot recurrent state a decode "
                "dispatch's program reads and writes: every slot's row "
                "of every state layer, in and out, each micro-step",
                buckets=(0.001, 0.01, 0.1, 0.5, 1, 2, 4, 8, 16), **lbl)
            fams["scan_pad"] = reg.histogram(
                "serving_scan_pad_pct",
                "100 x positions of an admission wave's prefill (width x "
                "bucket) past their row's last token, which the state "
                "layers' scan is dispatched over all the same",
                buckets=(0, 5, 10, 20, 30, 40, 50, 60, 70, 80, 90, 100),
                **lbl)
        fams["spec_accept"].set(1.0)
        for g in fams["spec_accept_by"].values():
            g.set(1.0)
        return fams

    def _slo_metrics(self):
        return self._resolve_metrics("_slo_cache", self._build_slo_metrics)

    def _build_slo_metrics(self, reg):
        lbl = {"model": self.name or "default"}
        return {
            "good": reg.counter("slo_requests_good_total",
                                "finished requests meeting the SLO",
                                **lbl),
            "bad": reg.counter("slo_requests_bad_total",
                               "requests missing the SLO (sheds "
                               "included)", **lbl),
            "burn": reg.gauge("slo_burn_rate",
                              "rolling-window error-budget burn rate "
                              "(1.0 = sustainable)", **lbl),
        }

    # ----------------------------------------------------------- shedding
    def _outstanding_tokens(self) -> int:
        """Outstanding decode work, from ACTUAL occupancy: live slots'
        remaining tokens plus, per queued request, the tokens it still
        owes (`n_left` — a requeued continuation owes only its tail)
        and a prefill cost proxy."""
        eng = self.engine
        out = int(eng.remaining[eng.active].sum())
        for req, _, _ in self._pending:
            # a continuation's effective prompt is prompt + emitted;
            # only the LENGTH matters here — don't materialize it
            out += req.n_left + blocks_needed(
                len(req.prompt) + req.emitted, eng.block_len)
        return out

    def _should_shed(self, req) -> Optional[str]:
        if self.max_queue is not None and len(self._pending) >= self.max_queue:
            return (f"admission queue full ({len(self._pending)} >= "
                    f"max_queue {self.max_queue})")
        if self.slo_ttft_s is not None and self._ewma_tok_s:
            projected = self._outstanding_tokens() / self._ewma_tok_s
            if projected > self.slo_ttft_s:
                return (f"projected queue delay {projected:.2f}s exceeds "
                        f"the {self.slo_ttft_s:.2f}s TTFT SLO at "
                        f"{self._ewma_tok_s:.1f} tok/s")
        return None

    # ---------------------------------------------------------- scheduler
    def _collect_loop(self):
        """The scheduler loop (replaces the coalescing collector):
        admissions, one decode launch, the earlier step's readback and
        stream fan-out, eviction, gauges (`_schedule_once`) — then
        block on the queue only when fully idle. Each
        iteration is one `serve/loop` span whose number `it` every
        span and request-lane phase of the iteration carries."""
        eng = self.engine
        while self._running:
            eng.loop_it += 1
            it = eng.loop_it
            try:
                with monitor.span("serve/loop", it=it,
                                  active=eng.active_slots,
                                  pending=len(self._pending)) as loop:
                    progressed = self._schedule_once(eng)
            except Exception as e:  # noqa: BLE001 — a poisoned dispatch
                # must fail every waiting consumer, not hang them on a
                # dead scheduler (ParallelInference._execute's contract)
                self._fail_all(e)
                continue
            if progressed:
                m = self._serving_metrics()
                if m is not None:
                    m["sched_host"].observe(loop.duration_s
                                            - self._dispatch_s)
            else:
                # fully idle: park on the queue (a submit wakes us)
                with monitor.span("serve/sched/park", it=it):
                    try:
                        item = self._queue.get(timeout=self.idle_wait_s)
                    except queue.Empty:
                        continue
                self._queue_item_taken(item)
                if item is not None:
                    self._pending.append(item)
        # stopping: the step in flight has been computed — its tokens
        # go out before `stop()` fails whatever is still open
        if eng.in_flight:
            try:
                self._decode(eng, self._serving_metrics(), eng.loop_it,
                             launch=False)
            except Exception as e:  # noqa: BLE001 — as in the loop
                self._fail_all(e)

    def _fail_all(self, exc: BaseException):
        for settle in (self.engine.drain_preempted, self.engine.drain):
            # notices, and the step in flight, die with their requests
            try:
                settle()
            except Exception:  # noqa: BLE001 — engine state may be torn
                pass
        for slot, (req, fut, _) in list(self._slot2req.items()):
            try:
                self.engine.evict(slot)
            except Exception:  # noqa: BLE001 — engine state may be torn
                pass
            req.stream._fail(exc)
        self._slot2req.clear()
        for item in self._pending:
            # defensive: a foreign queue item without a stream must not
            # re-raise out of the failure path and kill the scheduler
            stream = getattr(item[0], "stream", None)
            if stream is not None:
                stream._fail(exc)
            elif len(item) > 1 and hasattr(item[1], "set_exception") \
                    and not item[1].done():
                item[1].set_exception(exc)
        self._pending.clear()

    def _schedule_once(self, eng) -> bool:
        """One iteration: intake, admission waves, one decode launch.
        The decode step runs one ahead (`engine.step_ahead`): with a
        step in flight the loop grants blocks for the next and launches
        it, and only then reads the earlier one, does its bookkeeping
        and fans its tokens out. Whatever rewrites a slot or hands one
        to another request reads the step in flight first and sends
        its tokens on (`_decode(launch=False)`): a cancellation, an
        admission wave (which would otherwise hold tokens already
        computed for as long as the prefill takes), and the engine by
        itself where it preempts or drafts."""
        m = self._serving_metrics()
        it = eng.loop_it
        self._dispatch_s = 0.0
        with monitor.span("serve/sched/intake", it=it):
            progressed = self._intake(eng, m)
            wave, requests, shed = self._next_wave(eng, m)
        if wave and eng.in_flight:
            self._decode(eng, m, it, launch=False)
        while wave:
            with monitor.span("serve/admit", it=it,
                              width=len(wave)) as sp:
                admitted = self._admit(eng, m, it, wave, requests)
                sp.set(admitted=admitted, bucket=eng.admit_bucket,
                       tokens=eng.admit_tokens)
            if not admitted:
                break
            self._dispatch_s += sp.duration_s
            if m is not None:
                m["admit_waves"].inc()
                m["admit_wave"].observe(sp.duration_s)
                m["admit_wait"].observe(eng.wait_s)
                self._observe_layer_counts(eng, m, decode=False)
            progressed = True
            with monitor.span("serve/sched/intake", it=it):
                wave, requests, more = self._next_wave(eng, m)
                shed = shed or more
        progressed = progressed or shed
        if eng.active.any() or eng.in_flight:
            self._decode(eng, m, it, launch=True)
            progressed = True
        if m is not None:
            with monitor.span("serve/sched/gauges", it=it):
                self._publish_gauges(eng, m)
        return progressed

    def _decode(self, eng, m, it, *, launch: bool):
        """One `serve/decode` span and the fan-out of what it read.
        With `launch` the engine launches the next step and then reads
        the one before (`step_ahead`; nothing to launch: it reads
        alone); without, it only reads the step in flight.

        The step's seconds (`serving_step_seconds`, its host and wait
        parts, the rate the shedding policy reads) are a period of the
        loop: a span that launched one step and read another, or, the
        loop having drained, the span that launched a step (kept in
        `_launch_s`) and the one that read it. A span that only reads
        a step whose launch an earlier period holds (before a wave or a
        cancellation, the last step of a batch) takes microseconds for
        tokens that were ready: it counts the step's tokens and what
        the step did, and no seconds."""
        t0 = time.perf_counter()
        with monitor.span("serve/decode", it=it,
                          active=eng.active_slots) as sp:
            if launch:
                emitted, finished = eng.step_ahead(
                    speculate=self._spec_policy(),
                    proposers=self._spec_proposers())
            else:
                emitted, finished = eng.drain()
        dt = time.perf_counter() - t0
        if eng.launched and self.dispatch_floor_s is not None \
                and dt < self.dispatch_floor_s:
            time.sleep(self.dispatch_floor_s - dt)
            dt = self.dispatch_floor_s   # EWMA/trace see the
            # emulated device rate, not the host-compute rate
        with monitor.span("serve/sched/fanout", it=it):
            # dispatch-level speculative deltas for trace
            # attribution — read BEFORE _spec_update advances the
            # *_seen cursors
            d_spec_prop = (eng.spec_proposed_total
                           - self._spec_proposed_seen)
            d_spec_acc = (eng.spec_accepted_total
                          - self._spec_accepted_seen)
            self._spec_update(m)
            now = time.monotonic()
            n_tok = sum(len(ts) for ts in emitted.values())
            if not n_tok:
                self._launch_s += sp.duration_s
                self._dispatch_s += sp.duration_s
            else:
                timed = eng.launched or self._launch_s > 0
                step_s, span_s = dt + self._launch_s, \
                    sp.duration_s + self._launch_s
                self._launch_s = 0.0
                if timed:
                    # an untimed span's microseconds stay the loop's own
                    self._dispatch_s += sp.duration_s
                if m is not None:
                    m["tokens"].inc(n_tok)
                    m["batch_slots"].observe(len(emitted))
                    m["sample_rows"].observe(eng.sample_rows)
                    m["kv_read_pct"].observe(eng.kv_read_pct)
                    m["weight_gb"].observe(eng.weight_gb)
                    m["overlap_pct"].observe(
                        100.0 if eng.overlapped else 0.0)
                    self._observe_layer_counts(eng, m, decode=True)
                    if timed:
                        m["step"].observe(step_s)
                        # the same step from inside: the engine's wait
                        # span against the rest of `serve/decode`
                        m["decode_wait"].observe(eng.wait_s)
                        m["decode_host"].observe(span_s - eng.wait_s)
                if timed and step_s > 0:
                    rate = n_tok / step_s
                    self._ewma_tok_s = (rate if self._ewma_tok_s is None
                                        else 0.8 * self._ewma_tok_s
                                        + 0.2 * rate)
            t1 = t0 + dt
            for slot, toks in emitted.items():
                stream = self._slot2req[slot][0].stream
                stream._emit_many(toks, now)
                tr = stream.trace
                if tr is not None:
                    args = {"tokens": len(toks), "it": it}
                    if d_spec_prop:
                        args["spec_proposed"] = d_spec_prop
                        args["spec_accepted"] = d_spec_acc
                    tr.phase("decode", t0, t1, **args)
            for slot in finished:
                req, fut, _ = self._slot2req.pop(slot)
                self._finish(req, m)
            # pool-pressure preemptions (incremental allocation):
            # requeue each evicted request as a continuation at the
            # HEAD of the admission queue — it predates everything
            # queued, and its emitted tokens stand (the engine
            # re-admits prompt+emitted at the same rng emit offset).
            # After the fan-out: the engine read the step in flight
            # before it preempted, and the victim's tokens of that
            # step have just gone to its stream
            preempted = eng.drain_preempted()
            if preempted:
                requeued = []
                for note in preempted:
                    entry = self._slot2req.pop(note["slot"], None)
                    if entry is not None:
                        requeued.append(entry)
                        tr = entry[0].stream.trace
                        if tr is not None:
                            tr.event("preempt_requeue",
                                     emitted=int(
                                         note.get("emitted", 0)))
                self._pending[:0] = requeued

    @staticmethod
    def _observe_layer_counts(eng, m, *, decode: bool):
        """What the engine read back with the last dispatch's tokens:
        the routed expert layers' rows and load (decode and admission
        dispatches alike), the positions a decode dispatch's attention
        read, the state a decode dispatch moved and the padding an
        admission wave's scan ran over.  Families of a net with no such
        layer observe nothing."""
        if eng.moe_stats is not None:
            m["moe_rows"].observe(eng.moe_stats[0])
            m["moe_load"].observe(eng.moe_stats[1])
        if decode and eng.positions_read:
            m["positions_read"].inc(eng.positions_read)
        if decode and eng.window_held_pct is not None:
            m["window_held"].observe(eng.window_held_pct)
        if eng.state_layers:
            if decode:
                m["state_gb"].observe(eng.state_gb)
            elif eng.scan_pad_pct is not None:
                m["scan_pad"].observe(eng.scan_pad_pct)

    def _intake(self, eng, m) -> bool:
        """Control requests, cancellations, and the submit queue drained
        into `_pending` (shedding what the SLO policy refuses)."""
        progressed = False
        # ------------------------------------------ control requests
        # (prefix registrations from foreign threads — the engine is
        # scheduler-thread-only by contract)
        if self._drain_control(eng):
            progressed = True
        # -------------------------------------------- cancellations
        cancelled = [slot for slot, (req, _, _) in self._slot2req.items()
                     if req.stream.cancelled]
        if cancelled and eng.in_flight:
            # the step in flight is computed: its tokens go out first
            self._decode(eng, m, eng.loop_it, launch=False)
        for slot in cancelled:
            entry = self._slot2req.pop(slot, None)
            if entry is None:
                continue      # finished in the step just read
            eng.evict(slot)
            if m is not None:
                m["evicted"].inc()
            entry[0].stream._finish()   # partial tokens, clean close
            progressed = True
        # cancelled while QUEUED: reap anywhere in line, not only at
        # the head — stranded entries otherwise keep counting toward
        # max_queue and the shed projection, shedding real requests
        # on phantom load
        if any(item[0].stream.cancelled for item in self._pending):
            kept = []
            for item in self._pending:
                if item[0].stream.cancelled:
                    item[0].stream._finish()
                    progressed = True
                else:
                    kept.append(item)
            self._pending = kept
        # ----------------------------------------------- admissions
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            self._queue_item_taken(item)
            if item is None:
                continue
            req = item[0]
            if req.stream.cancelled:
                req.stream._finish()
                continue
            reason = self._should_shed(req)
            if reason is not None:
                if m is not None:
                    m["shed"].inc()
                self._note_shed(req, reason)
                req.stream._fail(ShedError(reason))
                continue
            self._pending.append(item)
        return progressed

    def _next_wave(self, eng, m):
        """The FIFO prefix of `_pending` the engine is offered as one
        admission wave -> (wave, its request dicts, shed): the wave is
        empty when nothing is queued or the head has to wait; `shed`
        says a head that can never be admitted was failed on the way."""
        shed = False
        while self._pending:
            head = self._pending[0]
            if head[0].stream.cancelled:
                self._pending.pop(0)
                head[0].stream._finish()
                continue
            # continuation length = prompt + emitted; only the LENGTH
            # matters for the capacity check — don't materialize it
            # UNLESS prefixes are registered (a head request riding a
            # shared prefix needs far fewer fresh blocks than its
            # length suggests; judging it by length alone could stall
            # the queue forever behind a perfectly admittable head)
            if not eng.can_admit(len(head[0].prompt) + head[0].emitted,
                                 head[0].n_left,
                                 prompt_ids=(head[0].effective_prompt()
                                             if (eng.has_prefixes
                                                 or eng._radix is not None)
                                             else None)):
                # a head that can NEVER be admitted must shed, not
                # wait — waiting would wedge the FIFO queue (and
                # everything behind it) forever. Under today's sharing
                # model this cannot fire (releasing a prefix returns
                # exactly the blocks a rider stops sharing, so a
                # request accepted via check_budget stays admissible);
                # the re-check is the INVARIANT'S enforcement point, so
                # a future sharing mode that breaks the arithmetic
                # degrades to a clean ShedError instead of a hang
                try:
                    eng.check_budget(
                        len(head[0].prompt) + head[0].emitted,
                        head[0].n_left,
                        prompt_ids=head[0].effective_prompt())
                except ValueError as e:
                    self._pending.pop(0)
                    if m is not None:
                        m["shed"].inc()
                    self._note_shed(head[0], str(e))
                    head[0].stream._fail(ShedError(str(e)))
                    shed = True
                    continue
                break    # FIFO: never leapfrog the head request
            # admission WAVE: the FIFO prefix — prompt lengths may be
            # HETEROGENEOUS (the engine bucket-pads them into one
            # prefill dispatch) — goes through ONE batched prefill +
            # ONE fused pages/first-token dispatch (the engine stops
            # the wave itself at slot/block capacity)
            wave = []
            for item in self._pending:
                if item[0].stream.cancelled:
                    break
                wave.append(item)
                if len(wave) >= eng.free_slots:
                    break   # admission can never exceed free slots —
                    # don't build request dicts for a deep backlog
            return wave, [
                dict(prompt_ids=it[0].effective_prompt(),
                     n_tokens=it[0].n_left, request_id=id(it[0]),
                     temperature=it[0].temperature,
                     top_p=it[0].top_p, rng=it[0].rng,
                     emit_start=it[0].emit_base + it[0].emitted)
                for it in wave], shed
        return [], [], shed

    def _admit(self, eng, m, it, wave, requests) -> int:
        """One admission wave through the engine and its first tokens
        out to the streams -> how many of `wave` were admitted (the
        engine admits a prefix of it)."""
        t0p = time.perf_counter()
        admitted = eng.admit_many(requests)
        if not admitted:
            return 0
        if self.dispatch_floor_s is not None:
            dtp = time.perf_counter() - t0p
            if dtp < self.dispatch_floor_s:
                # the prefill wave is device work too — under the
                # emulated floor it must overlap across replicas
                # the same way decode dispatches do
                time.sleep(self.dispatch_floor_s - dtp)
        with monitor.span("serve/admit/fanout", it=it):
            t1p = time.perf_counter()
            now = time.monotonic()
            for (slot, first, done), (req, fut, t_submit) in zip(
                    admitted, wave):
                self._pending.pop(0)
                fresh = req.stream.t_first is None
                req.stream._emit(first, now)
                tr = req.stream.trace
                if tr is not None:
                    # host-side stamps only — the wave's device work is
                    # already timed by t0p/t1p, no extra syncs
                    info = eng.admit_info.get(slot) or {}
                    if fresh:
                        tr.phase("queued", tr.t_created, t0p)
                    tr.phase("prefill", t0p, t1p, it=it,
                             wave_width=len(admitted), slot=slot,
                             continuation=not fresh, **info)
                    if info.get("cow_fork"):
                        tr.event("cow_fork", slot=slot)
                if m is not None:
                    m["tokens"].inc()
                    if fresh:
                        # a requeued continuation was already counted
                        # (and its TTFT observed) at first admission
                        m["requests"].inc()
                        m["ttft"].observe(now - t_submit)
                if done:
                    self._finish(req, m)
                else:
                    req.slot = slot
                    self._slot2req[slot] = (req, fut, t_submit)
        return len(admitted)

    def _publish_gauges(self, eng, m):
        m["queue"].set(self.queue_depth())
        m["slots"].set(eng.active_slots)
        m["blocks"].set(eng.free_blocks)
        m["pool_free"].set(eng.pool.free_blocks)
        m["pool_used"].set(eng.pool.used_blocks)
        if eng.pool.window_allocator is not None:
            m["ring_free"].set(eng.pool.window_allocator.free_blocks)
            m["ring_used"].set(eng.pool.window_allocator.used_blocks)
        if eng.block_grants_total > self._grants_seen:
            m["grants"].inc(eng.block_grants_total
                            - self._grants_seen)
            self._grants_seen = eng.block_grants_total
        if eng.evict_requeue_total > self._requeue_seen:
            m["requeue"].inc(eng.evict_requeue_total
                             - self._requeue_seen)
            self._requeue_seen = eng.evict_requeue_total
        if eng.has_prefixes or eng.prefix_hits_total:
            m["prefix_shared"].set(eng.pool.allocator.shared_blocks)
            if eng.prefix_hits_total > self._prefix_hits_seen:
                m["prefix_hits"].inc(eng.prefix_hits_total
                                     - self._prefix_hits_seen)
                m["prefix_saved"].inc(eng.prefix_tokens_saved_total
                                      - self._prefix_saved_seen)
                self._prefix_saved_seen = eng.prefix_tokens_saved_total
                self._prefix_hits_seen = eng.prefix_hits_total
        if eng._radix is not None:
            m["radix_nodes"].set(eng._radix.nodes)
            if eng.radix_hit_tokens_total > self._radix_hits_seen:
                m["radix_hits"].inc(eng.radix_hit_tokens_total
                                    - self._radix_hits_seen)
                self._radix_hits_seen = eng.radix_hit_tokens_total
            if eng.radix_evictions_total > self._radix_evict_seen:
                m["radix_evict"].inc(eng.radix_evictions_total
                                     - self._radix_evict_seen)
                self._radix_evict_seen = eng.radix_evictions_total
        # goodput ledger mirror: per-class counter deltas + the
        # rolling fraction (host ints the dispatch sites already
        # wrote — zero extra syncs)
        gp = eng.goodput
        for cls, ctr in m["goodput"].items():
            total = gp.classes[cls]
            seen = self._goodput_seen.get(cls, 0)
            if total > seen:
                ctr.inc(total - seen)
                self._goodput_seen[cls] = total
        m["goodput_frac"].set(gp.goodput_fraction())

    # ------------------------------------------------ speculative policy
    def _spec_policy(self) -> Optional[bool]:
        """Whether the next dispatch drafts: None (engine default) when
        speculation is off or healthy; False while the accept-rate EWMA
        sits under `spec_accept_floor` — except for one probe dispatch
        every `spec_probe_every`, which re-measures the workload."""
        if not self.engine.spec_k:
            return None
        if not self._spec_disabled:
            return True
        self._spec_probe_in -= 1
        if self._spec_probe_in <= 0:
            self._spec_probe_in = self.spec_probe_every
            return True                      # probe dispatch
        return False

    def _spec_proposers(self) -> Optional[tuple]:
        """Per-proposer arbitration on top of `_spec_policy`'s global
        enable/disable: when the truncated-layer drafter is configured
        and the n-gram proposer's OWN acceptance EWMA has collapsed
        below the floor while the drafter's hasn't, restrict drafting
        to the truncated backend — its K-wide scan is only worth
        dispatching on lanes it can actually fill, and a dead n-gram
        cache (non-repetitive traffic) would otherwise keep winning
        the proposal race with garbage continuations. Returns None
        (engine default: all proposers) otherwise; if BOTH EWMAs sink,
        the global latch above disables speculation outright."""
        eng = self.engine
        if not eng.spec_k or eng._draft_plan is None:
            return None
        ng = self._spec_prop_ewma["ngram"]
        tr = self._spec_prop_ewma["truncated"]
        if ng is not None and ng < self.spec_accept_floor \
                and (tr is None or tr >= self.spec_accept_floor):
            return ("truncated",)
        return None

    def _spec_update(self, m):
        """Fold the engine's per-dispatch speculative counters into the
        acceptance EWMA and flip the auto-disable latch."""
        eng = self.engine
        if not eng.spec_k:
            return
        d_prop = eng.spec_proposed_total - self._spec_proposed_seen
        d_acc = eng.spec_accepted_total - self._spec_accepted_seen
        d_emit = eng.spec_emitted_total - self._spec_emitted_seen
        d_disp = eng.spec_dispatches_total - self._spec_dispatches_seen
        self._spec_proposed_seen = eng.spec_proposed_total
        self._spec_accepted_seen = eng.spec_accepted_total
        self._spec_emitted_seen = eng.spec_emitted_total
        self._spec_dispatches_seen = eng.spec_dispatches_total
        if d_disp < 1:
            return                           # chunked dispatch — no data
        # a dispatch where the proposer drafted NOTHING is also
        # evidence against speculation: it paid the K-wide score
        # program for one token per slot. Counting it as acceptance 0
        # lets the auto-disable engage on non-repetitive traffic the
        # suffix cache can't draft on — otherwise the EWMA never
        # updates and drafting runs at 1 token/dispatch forever
        rate = d_acc / d_prop if d_prop > 0 else 0.0
        self._spec_accept_ewma = (
            rate if self._spec_accept_ewma is None
            else 0.8 * self._spec_accept_ewma + 0.2 * rate)
        self._spec_tpd_ewma = (
            d_emit / d_disp if self._spec_tpd_ewma is None
            else 0.8 * self._spec_tpd_ewma + 0.2 * d_emit / d_disp)
        if not self._spec_disabled \
                and self._spec_accept_ewma < self.spec_accept_floor:
            self._spec_disabled = True
            self._spec_probe_in = self.spec_probe_every
        elif self._spec_disabled \
                and self._spec_accept_ewma >= self.spec_accept_floor:
            self._spec_disabled = False
        # per-proposer EWMAs (arbitration inputs for _spec_proposers):
        # same α, same "no data this dispatch → no update" rule — a
        # proposer that drafted nothing is judged only when it ran
        for prop in ("ngram", "truncated"):
            pp, pa = self._spec_prop_seen[prop]
            tot_p = eng.spec_proposed_by[prop]
            tot_a = eng.spec_accepted_by[prop]
            d_pp, d_pa = tot_p - pp, tot_a - pa
            self._spec_prop_seen[prop] = (tot_p, tot_a)
            if d_pp > 0:
                r = d_pa / d_pp
                prev = self._spec_prop_ewma[prop]
                self._spec_prop_ewma[prop] = (
                    r if prev is None else 0.8 * prev + 0.2 * r)
            if m is not None and d_pp > 0:
                m["spec_proposed_by"][prop].inc(d_pp)
                if d_pa > 0:
                    m["spec_accepted_by"][prop].inc(d_pa)
                m["spec_accept_by"][prop].set(self._spec_prop_ewma[prop])
        if m is not None:
            m["spec_accept"].set(self._spec_accept_ewma)
            if self._spec_tpd_ewma is not None:
                m["spec_tpd"].set(self._spec_tpd_ewma)

    def _note_shed(self, req, reason: str):
        """Shed bookkeeping beyond the counter: trace annotation (the
        router's/scheduler's decision becomes auditable per request),
        SLO budget spend, and a rate-limited flight-recorder event."""
        tr = req.stream.trace
        if tr is not None:
            tr.event("shed", reason=reason)
        slo = self._slo_tracker
        if slo is not None:
            slo.record_shed()
            sm = self._slo_metrics()
            if sm is not None:
                sm["bad"].inc()
                sm["burn"].set(slo.burn_rate())
        # shed BURSTS are a control-plane signal; single events at
        # request rate would flood the ring, so coalesce to ≤1/s
        self._shed_recent += 1
        now = time.monotonic()
        if now - self._shed_last_emit >= 1.0:
            GLOBAL_FLIGHT_RECORDER.record(
                "shed_burst", server=self.name,
                count=self._shed_recent, reason=reason)
            self._shed_recent = 0
            self._shed_last_emit = now

    def _finish(self, req, m):
        st = req.stream
        n = len(st.tokens)
        ttft = (st.t_first - st.t_submit) if st.t_first is not None \
            else None
        tpot = ((st.t_last - st.t_first) / (n - 1)
                if st.t_first is not None and n > 1 else None)
        tr = st.trace
        if tr is not None:
            if self._draining:
                tr.event("drain_at_swap")
            tr.annotate(ttft_s=ttft, tpot_s=tpot)
        slo = self._slo_tracker
        if slo is not None:
            good = slo.record(ttft=ttft, tpot=tpot)
            sm = self._slo_metrics()
            if sm is not None:
                sm["good" if good else "bad"].inc()
                sm["burn"].set(slo.burn_rate())
            if tr is not None:
                tr.annotate(slo_good=good)
        st._finish()
        if m is not None and st.t_first is not None and n > 1:
            m["tpot"].observe((st.t_last - st.t_first) / (n - 1))
        if m is not None and tr is not None:
            # TTFT decomposition from the stamps the trace already
            # carries (queued/prefill phases + the ttft annotation)
            dec = ttft_decomposition(tr)
            if dec is not None:
                m["ttft_queue"].observe(dec["queue_wait_s"])
                m["ttft_prefill"].observe(dec["prefill_s"])
                m["ttft_emit"].observe(dec["first_emit_s"])

    # ---------------------------------------------------------- lifecycle
    def start(self):
        # a restarted scheduler would run over an engine whose slots
        # were force-retired by stop() and whose streams were failed —
        # refuse loudly instead of corrupting the allocator
        if self._stopped:
            raise ServerStoppedError(
                "GenerationServer was stopped; start() cannot revive it "
                "— build a fresh server (the engine's slot/allocator "
                "state was retired at stop())")
        return super().start()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Zero-downtime handoff seam: close admissions (new
        `generate_async` raises `ServerDrainingError`) and block until
        every already-submitted stream — queued AND in-flight — has
        finished. Returns True when fully drained, False on timeout
        (admissions stay closed either way).

        The barrier is the open-stream count (TokenStream close hooks),
        not scheduler-state inspection: a request between the queue and
        the pending list is invisible to both, and declaring drained
        while it's in limbo would drop a stream at the subsequent
        stop(). The engine is never touched from here — the warmup
        counter-reset and incremental-allocation invariants
        (docs/SERVING.md) belong to the scheduler thread alone."""
        with self._open_lock:
            # flag-set and count-read share the submit path's lock:
            # see the generate_async re-check
            self._draining = True
        # goodput: dispatch work from here on belongs to the swap
        # window — delivered, but attributed to drain (the fraction
        # visibly dips during a swap, which is the operator's signal).
        # The flag flip is racy against an in-flight dispatch by one
        # dispatch at most; the ledger's mode reroute keeps every
        # counter monotone either way.
        self.engine.goodput.set_mode("drain")
        deadline = (None if timeout is None
                    else time.monotonic() + float(timeout))
        while self.open_streams > 0:
            if not self._running:
                # scheduler gone (stop() raced us): whatever is left
                # has been failed — drained in the "nothing in flight"
                # sense, but not cleanly
                return self.open_streams == 0
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(0.002)
        return True

    def stop(self):
        # idempotent: a second stop() (or stop() after shutdown()) is a
        # no-op — the first one already failed every stream and joined
        # the scheduler; re-running the teardown over cleared state
        # must not raise or double-fail anything
        if self._stopped:
            return
        self._stopped = True
        # inherited stop() joins with a 5 s cap and proceeds — here a
        # single decode chunk can legitimately run longer (large model
        # x steps_per_dispatch), and mutating engine/slot state while
        # _schedule_once is still inside eng.step() corrupts the
        # allocator and fails streams with spurious errors. Wait the
        # scheduler out; only touch the engine once its thread is dead.
        self._running = False
        scheduler_dead = True
        if self._collector is not None:
            self._queue.put(None)   # wake an idle park
            self._collector.join(timeout=600)
            scheduler_dead = not self._collector.is_alive()
            self._collector = None
        self._fail_pending()        # drains + fails anything queued
        # in-flight sequences: evict and fail their streams so no
        # consumer hangs on an iterator that will never close
        for slot, (req, fut, _) in list(self._slot2req.items()):
            if scheduler_dead:
                try:
                    self.engine.evict(slot)
                except ValueError:
                    pass
            req.stream._fail(RuntimeError(
                "GenerationServer stopped before this request finished"))
        self._slot2req.clear()
        for req, fut, _ in self._pending:
            req.stream._fail(RuntimeError(
                "GenerationServer stopped before this request was "
                "admitted"))
        self._pending.clear()
        # control requests (prefix registrations) still queued: fail
        # their futures so no caller blocks on a dead scheduler
        self._fail_control()

    def _fail_control(self):
        while True:
            try:
                _, _, fut = self._control.get_nowait()
            except queue.Empty:
                break
            if not fut.done():
                fut.set_exception(RuntimeError(
                    "GenerationServer stopped before this control "
                    "request was applied"))

    def _fail_pending(self):
        """Queue items here are (request, future, t) — fail the STREAM
        (which resolves the future and closes the iterator), not just
        the future."""
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            self._queue_item_taken(item)
            if item is None:
                continue
            req = item[0]
            if hasattr(req, "stream"):
                req.stream._fail(RuntimeError(
                    "GenerationServer stopped before this request was "
                    "executed"))
            elif not item[1].done():
                item[1].set_exception(RuntimeError(
                    "GenerationServer stopped before this request was "
                    "executed"))
