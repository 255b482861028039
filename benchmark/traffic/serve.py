"""The serving kind: one general open-loop generator of requests, read
from the cell's data file, and the driver of `GenerationServer`.

A cell's file gives `server` (the constructor's arguments), the fixed
`rate_per_s` of Poisson arrivals, log-normal `prompt_len` and
`output_len` (median, sigma, min, max), the share of sampled requests
with their temperature and `top_p`, and `limits`.  The schedule (each
request's sizes, whether it samples, and the gaps between arrivals) is
drawn from the cell's own `shape_seed`, the same for every run; `--seed`
draws the token ids, the sampling words and the weights.  So every seed
offers the same work at the same times: with some sixty requests in a
window, which of them are still open at its close moved the tokens per
second by a quarter from seed to seed (my chip runs, PR 26).

Load is offered from this thread alone, each request sent when it is due
whether or not earlier ones have finished, and timed from when it was
due.  One poller thread reads how many tokens each open stream holds,
every half millisecond, and stamps them: time to first token and the gaps
between tokens are taken from those stamps by the benchmark itself.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

import correct
import harness
from harness import say

POLL_S = 0.0005
LATE_WAIT_S = 60.0


# --------------------------------------------------------------- generator
def _lognormal(rng, spec, n):
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def make_requests(cell, cfg, seed, seconds):
    """[(due_s, prompt ids, n_tokens, sampled, rng words)] in order of
    arrival, enough to cover `seconds` and the gap after it."""
    rate = float(cell["rate_per_s"])
    n = int(rate * seconds * 1.25) + 16
    shape = np.random.default_rng(int(cell["shape_seed"]))
    plen = _lognormal(shape, cell["prompt_len"], n)
    olen = _lognormal(shape, cell["output_len"], n)
    gaps = shape.exponential(1.0 / rate, n)
    sampled = (np.arange(n) % round(1 / cell["sampled_share"]) == 0
               if cell["sampled_share"] > 0 else np.zeros(n, bool))
    rng = np.random.default_rng(int(seed))
    due = np.cumsum(gaps)
    V = cfg["vocab_size"]
    return [(float(due[i]), rng.integers(0, V, plen[i]).astype(np.int32),
             int(olen[i]), bool(sampled[i]),
             np.asarray([int(seed) & 0xFFFFFFFF, i], np.uint32))
            for i in range(n)]


class Poller(threading.Thread):
    """Stamps every token of every open stream with the time at which
    this thread first saw it."""

    def __init__(self):
        super().__init__(daemon=True, name="bench-poller")
        self.lock = threading.Lock()
        self.open, self.stamps = {}, {}
        self.halt = threading.Event()

    def add(self, idx, stream):
        with self.lock:
            self.open[idx] = stream
            self.stamps[idx] = []

    def discard(self, idx):
        with self.lock:
            self.open.pop(idx, None)

    def run(self):
        while not self.halt.is_set():
            now = time.monotonic()
            with self.lock:
                items = list(self.open.items())
            for idx, s in items:
                seen = self.stamps[idx]
                n = len(s.tokens)
                if n > len(seen):
                    seen.extend([now] * (n - len(seen)))
                if n >= s.n_tokens:
                    self.discard(idx)
            time.sleep(POLL_S)


def pct(values, q):
    v = np.sort(np.asarray(values, float))
    return float(v[min(len(v) - 1, int(math.ceil(q * len(v))) - 1)])


# ------------------------------------------------------------------ driver
def offer(server, requests, seconds, cell, tracer, poller, devs=None):
    """Send each request when it is due, for `seconds`.  -> (t0, streams
    by index, lateness of each send, live bytes on the chip at each
    send and at the close)."""
    streams, late, live = {}, [], []
    t0 = time.monotonic()
    for i, (due, prompt, n_tok, sampled, words) in enumerate(requests):
        if due >= seconds:
            break
        wait = t0 + due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        # the traced part lies inside the window, past the ramp from an
        # empty server
        if tracer.on and tracer.t_start is None and due >= cell.get(
                "trace_start_s", 0.0):
            tracer.start()
        if tracer.due():
            tracer.stop(background=True)
        kw = (dict(temperature=cell["temperature"], top_p=cell["top_p"],
                   rng=words) if sampled else {})
        with tracer.span("bench/submit"):
            s = server.generate_async(prompt, n_tok, **kw)
        late.append(time.monotonic() - (t0 + due))
        streams[i] = s
        poller.add(i, s)
        if devs:
            live.append(harness.bytes_in_use(devs))
    rest = t0 + seconds - time.monotonic()
    if rest > 0:
        time.sleep(rest)
    tracer.stop(background=True)
    if devs:
        live.append(harness.bytes_in_use(devs))
    return t0, streams, late, live


def install_weights(net, cell, cfg, seed):
    """Weights from the seed in ONE jitted call, as `fit` would leave
    them (the float32 master copy under the configuration's policy)."""
    import jax

    common = harness.load_module("reference", "common")
    model = harness.load_module("models", cell["config"])
    ref = harness.load_module("reference", cell["config"])
    if "_bench_make" not in net.__dict__:
        def make(words):
            _, state, _ = net._init_trees(0)
            return model.to_program(
                ref.init_params(cfg, common.key_of(words)), cfg), state
        net.__dict__["_bench_make"] = jax.jit(make)
    if net.params:
        common.free(net.params, net.net_state)
    net.params, net.net_state = net._bench_make(common.seed_words(seed))
    net.updater_state = {}
    net._initialized = True


def drive(server, cell, cfg, seed, seconds, tracer, devs=None):
    """One window of traffic against a started server: offer, await
    every answer that was due, reduce to the numbers."""
    requests = make_requests(cell, cfg, seed, seconds)
    poller = Poller()
    poller.start()
    t0, streams, late, live = offer(server, requests, seconds, cell, tracer,
                                    poller, devs)
    t1 = t0 + seconds
    backlog = sum(1 for s in streams.values() if len(s.tokens) < s.n_tokens)
    # every request that was due gets its answer awaited: late is late,
    # only one that never comes (or comes wrong) is failed
    failed, outs = 0, {}
    limit = time.monotonic() + LATE_WAIT_S
    for i, s in streams.items():
        try:
            outs[i] = np.asarray(
                s.result(timeout=max(0.1, limit - time.monotonic())))
        except Exception as e:  # noqa: BLE001 - a refused or lost request is counted, not raised
            failed += 1
            poller.discard(i)
            say(f"request {i} failed: {type(e).__name__}: {e}")
    drained_s = time.monotonic() - t1
    time.sleep(4 * POLL_S)
    poller.halt.set()
    poller.join(timeout=5)

    ttft, gaps, tokens_in_window, wrong = [], [], 0, 0
    processed = []      # (prompt, output) positions done inside the window
    V = cfg["vocab_size"]
    for i, s in streams.items():
        st = poller.stamps[i]
        out = outs.get(i)
        if out is None or len(st) == 0:
            ttft.append(LATE_WAIT_S)
            continue
        if len(out) != requests[i][2] or out.min() < 0 or out.max() >= V:
            wrong += 1
        ttft.append(st[0] - (t0 + requests[i][0]))
        gaps.extend(np.diff(st))
        n_in = sum(1 for t in st if t <= t1)
        tokens_in_window += n_in
        if n_in:
            processed.append((len(requests[i][1]), n_in))
    e2e = {"serve_tokens_per_s": tokens_in_window / seconds,
           "serve_ttft_p95_ms": 1e3 * pct(ttft, 0.95),
           "serve_itl_p95_ms": 1e3 * pct(gaps, 0.95) if gaps else None}
    say(f"{len(streams)} requests sent at {cell['rate_per_s']}/s, "
        f"{len(outs)} answered, {failed} failed, {backlog} unfinished at "
        f"the close, drained {drained_s:.2f}s after it; generator lateness "
        f"p50 {1e3 * pct(late, 0.5):.3f} ms p95 {1e3 * pct(late, 0.95):.3f} "
        f"ms max {1e3 * max(late):.3f} ms; {tokens_in_window} tokens in the "
        f"window; ttft p50 {1e3 * pct(ttft, 0.5):.1f} p95 "
        f"{e2e['serve_ttft_p95_ms']:.1f} ms; itl p50 "
        f"{1e3 * pct(gaps, 0.5) if gaps else float('nan'):.2f} p95 "
        f"{e2e['serve_itl_p95_ms'] or float('nan'):.2f} ms over {len(gaps)} gaps")
    return {"t0": t0, "requests": requests, "streams": streams, "outs": outs,
            "failed": failed, "wrong": wrong, "e2e": e2e, "backlog": backlog,
            "drained_s": drained_s, "processed": processed, "live": live}


def pick_sample(cell, seed, requests, outs):
    """Greedy requests the reference reads: the longest, and others
    drawn from the seed."""
    greedy = [i for i in outs if not requests[i][3]]
    rng = np.random.default_rng(int(seed) + 1)
    longest = max(greedy, key=lambda i: len(requests[i][1]) + len(outs[i]))
    others = [i for i in greedy if i != longest]
    pick = [longest] + [int(i) for i in
                        rng.permutation(others)[:cell["check_requests"] - 1]]
    return [(requests[i][1], outs[i]) for i in pick]


def run(bench, cell, cfg, args, devs, counters, tracer, hooks=None):
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import MetricsRegistry
    from deeplearning4j_tpu.serving import GenerationServer

    common = harness.load_module("reference", "common")
    model = harness.load_module("models", cell["config"])
    ref = harness.load_module("reference", cell["config"])
    work = harness.load_module("work", cell["config"])
    registry = None
    if args.trace:          # the program's spans and counters: traced run only
        registry = monitor.enable(registry=MetricsRegistry(),
                                  jit_compile=False, device_memory=False)
    net = model.build(cfg)
    install_weights(net, cell, cfg, args.seed)
    server = GenerationServer(net, **cell["server"])
    if hooks and "server" in hooks:
        hooks["server"](server)
    say(f"built {cell['config']}: {net.num_params():,} parameters, "
        f"{net.dtype.name}; server {cell['server']}")
    with harness.PeakWatch(devs, counters) as peak:
        server.warmup(int(cell["warmup_prompt_len"]))
    say(f"warm-up grid done: {counters.compiles()} compiles so far, "
        f"{counters.compile_seconds():.1f}s compiling; live bytes read "
        f"{peak.most[0]:,} at the most, {peak.most[2]:.1f}s into it with "
        f"{peak.most[1]} programs compiled or loaded, and "
        f"{harness.bytes_in_use(devs):,} once it was done")
    server.start()

    compiles0 = counters.compiles()
    cache_setup = (counters.cache_requests, counters.cache_hits)
    snap0 = registry.snapshot() if registry else None
    d = drive(server, cell, cfg, args.seed, args.seconds, tracer, devs)
    snap1 = registry.snapshot() if registry else None
    setup_s = d["t0"] - harness.T0
    compiles_in_window = counters.compiles() - compiles0
    server.drain()
    server.stop()
    if registry:
        monitor.disable()
    device = harness.device_line(devs, d["live"])

    # ---- free the program's state, then the reference reads a sample
    common.free(net.params, net.net_state, server.engine.pool.kv)
    requests, outs = d["requests"], d["outs"]
    sample = pick_sample(cell, args.seed, requests, outs)
    t_ref = time.monotonic()
    gap = ref.served_gap(cfg, args.seed, sample, mode="f32")
    say(f"reference read {len(sample)} requests "
        f"({sum(len(o) for _, o in sample)} served tokens) in "
        f"{time.monotonic() - t_ref:.1f}s: widest gap {gap:.5f}")
    _, compared = correct.judge({"served_logit_gap": (gap, None)},
                                 cell["limits"])
    for name, count in (("wrong_length_or_id", d["wrong"]),
                        ("never_answered", d["failed"]),
                        ("compiles_in_window", compiles_in_window)):
        compared[name] = {"value": count, "limit": 0, "ok": count == 0}
    ok = all(c["ok"] for c in compared.values())
    ctx = {"cell": cell, "cfg": cfg, "work": work, "chips": len(devs),
           "peaks": harness.peaks_of(devs, args), "trace": tracer.reduce(),
           "window_s": args.seconds, "units": 1,
           "serve": {"prompt_tokens": [p for p, _ in d["processed"]],
                     "output_tokens": [o for _, o in d["processed"]]},
           "traced_units": 0, "compiles_in_window": compiles_in_window,
           "cache_requests": cache_setup[0], "cache_hits": cache_setup[1],
           "registry": (snap0, snap1)}
    e2e = dict(d["e2e"], setup_s=setup_s)
    return {"correct": bool(ok), "attempted": len(d["streams"]),
            "failed": d["failed"],
            "end_to_end": {k: v for k, v in e2e.items() if v is not None},
            "device": device, "ctx": ctx, "compared": compared}
