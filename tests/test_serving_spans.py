"""The serving loop's spans, emitted once onto two clocks.

Contracts (ISSUE 27, tracing):

- every `serve/decode` and `serve/admit` span of the scheduler thread
  has its children, in order, not overlapping, covering it, all inside
  one `serve/loop` with the same `it`; since ISSUE 30 the decode loop
  runs one step ahead, so a `serve/decode` is the launch of a step
  (`grow`, `dispatch`), the readback of the one before (`wait`,
  `post`), or both in that order;
- the timer families observed at the same boundaries partition the
  loop, and their counts are the engine's dispatches and waves;
- under `jax.profiler.start_trace` the same spans lie on one line of
  the profile's host plane as `dl4tpu/<name>`, as many and as long as
  the tracer's ring holds them;
- monitoring off: no `TraceAnnotation` is made and the ring stays
  empty; on or off the tokens are bit-identical and the device is
  synchronised and read back the same number of times;
- a request lane's phases carry the `it` of the loop that served them.
"""

import glob
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import MetricsRegistry, Tracer
from deeplearning4j_tpu.monitor.tracer import PROFILE_PREFIX
from deeplearning4j_tpu.serving import GenerationServer
from deeplearning4j_tpu.zoo.transformer import TransformerLM, generate

V, D, HEADS, LAYERS, MAXLEN = 23, 16, 4, 2, 32
BL = 4
N_TOK = 6

DECODE_KIDS = ["grow", "dispatch", "wait", "post"]
# the decode loop runs one step ahead: a span may launch without a step
# to read (the loop had drained), or read without launching (before a
# wave or a cancellation, and the last step; with `grow` where the
# engine looked for slots to launch and found none)
PLAIN_KIDS = (DECODE_KIDS, ["grow", "dispatch"], ["wait", "post"],
              ["grow", "wait", "post"])
SPEC_KIDS = ["propose", "grow", "dispatch", "wait", "post"]
FAMILIES = ("serving_sched_host_seconds", "serving_admit_wave_seconds",
            "serving_decode_host_seconds", "serving_decode_wait_seconds")


@pytest.fixture(scope="module")
def net():
    return TransformerLM(vocab_size=V, d_model=D, n_layers=LAYERS,
                         n_heads=HEADS, max_len=MAXLEN, seed=3).init()


@pytest.fixture(scope="module")
def prompts():
    return np.random.default_rng(5).integers(0, V, (6, 3))


@pytest.fixture(scope="module")
def ref_tokens(net, prompts):
    return generate(net, prompts, N_TOK, temperature=0)


@pytest.fixture
def mon():
    reg, tr = MetricsRegistry(), Tracer()
    monitor.enable(registry=reg, tracer=tr)
    yield reg, tr
    _monitor_off()


def _monitor_off():
    monitor.disable()
    monitor._STATE.registry = monitor.GLOBAL_REGISTRY
    monitor._STATE.tracer = monitor.GLOBAL_TRACER


def _serve(net, prompts, *, one_by_one=False, count=None, floor_s=None,
           **server_kw):
    """Six requests through a two-slot server -> (streams, tokens).
    `floor_s`: seconds that each launch of a decode step and each
    readback of one takes at the least, inside the engine's own
    `serve/decode/dispatch` and `serve/decode/wait` spans.
    `count`: a dict that receives how many decode steps the scheduler
    read back (`step`), how many of those reads closed a period of the
    loop (`period`: the call launched the next step too, or the call
    before launched this one and read none; the others read a step
    whose launch an earlier period holds, and are given no seconds),
    and how many `admit_many` calls of the engine admitted something."""
    server_kw.setdefault("n_slots", 2)
    srv = GenerationServer(net, n_blocks=16, block_len=BL, **server_kw)
    eng = srv.engine
    if floor_s is not None:
        # `_kv_read` runs inside the dispatch span, `_take_moe` inside
        # the wait span, once a step each
        for name in ("_kv_read", "_take_moe"):
            def floored(*a, _real=getattr(eng, name), **kw):
                time.sleep(floor_s)
                return _real(*a, **kw)
            setattr(eng, name, floored)
    if count is not None:
        _on_decode_reads(eng, lambda: count.update(
            step=count.get("step", 0) + 1,
            period=count.get("period", 0)
            + bool(eng.launched or srv._launch_s > 0)))

        def counting(*a, _real=eng.admit_many, **kw):
            out = _real(*a, **kw)
            count["admit_many"] = count.get("admit_many", 0) + bool(out)
            return out
        eng.admit_many = counting
    srv.start()
    try:
        if one_by_one:
            streams = []
            for p in prompts:
                streams.append(srv.generate_async(p, N_TOK))
                streams[-1].result(timeout=300)
        else:
            streams = [srv.generate_async(p, N_TOK) for p in prompts]
        toks = np.stack([s.result(timeout=300) for s in streams])
    finally:
        srv.stop()
    return streams, toks


def _on_decode_reads(eng, seen):
    """Call `seen()` after each of the scheduler's calls into the
    engine that came back with a decode step's tokens: `step_ahead`
    (launch the next, read the one before) and `drain` (read alone)."""
    for name in ("step_ahead", "drain"):
        def reading(*a, _real=getattr(eng, name), **kw):
            out = _real(*a, **kw)
            if out[0]:
                seen()
            return out
        setattr(eng, name, reading)


def _spans(tracer, prefix="serve/"):
    """The ring's `serve/*` spans as (name, start, end, args), by start."""
    evs = [(e["name"], e["ts"], e["ts"] + e["dur"], e["args"])
           for e in tracer.events()
           if e["ph"] == "X" and e["name"].startswith(prefix)]
    assert len({e["tid"] for e in tracer.events()
                if e["name"].startswith(prefix)}) == 1, \
        "the serving loop's spans all come from the scheduler thread"
    return sorted(evs, key=lambda e: (e[1], -e[2]))


def _check_children(spans, parent, expected):
    """Every `parent` span: its direct children are `expected(names)`,
    lie inside it one after another, and cover it; it lies inside the
    `serve/loop` of its `it`."""
    loops = {a["it"]: (s, e) for n, s, e, a in spans if n == "serve/loop"}
    parents = [sp for sp in spans if sp[0] == parent]
    assert parents
    covered = total = 0.0
    for _, s, e, a in parents:
        kids = [k for k in spans
                if k[0].startswith(parent + "/") and k[3]["it"] == a["it"]
                and s <= k[1] and k[2] <= e]
        expected([k[0][len(parent) + 1:] for k in kids])
        for left, right in zip(kids, kids[1:]):
            assert left[2] <= right[1], (left, right)
        ls, le = loops[a["it"]]
        assert ls <= s and e <= le
        covered += sum(k[2] - k[1] for k in kids)
        total += e - s
        # Here a decode step is 1-2 ms, and leaving one child and
        # entering the next takes 5-20 us beside XLA's own CPU threads:
        # 91-96% of a span was covered at d16/L2 and at d64/L4 alike
        # (measured while writing this test). So 80% is held of every
        # span and 90% over all of them; the microseconds are the same
        # where a step takes 75 ms (on the chip the span round a decode
        # dispatch and the clock round it differ by 0.01 ms: PERF.md, PR 27).
        # Since the loop runs a step ahead (ISSUE 30) a decode span of
        # this tiny model is a launch that returns at once and a
        # readback of tokens that are ready, 20-500 us in all between
        # the same microseconds: the decode cases give the launch and
        # the readback a floor (`_serve(floor_s=)`), as a device that
        # takes milliseconds a step gives them (3.5 ms on the chip).
        assert sum(k[2] - k[1] for k in kids) >= 0.8 * (e - s), (a, kids)
    assert covered >= 0.9 * total
    return parents


class TestSpanTree:
    def test_decode_children(self, mon, net, prompts, ref_tokens):
        _, tracer = mon
        _, toks = _serve(net, prompts, floor_s=4e-3)
        np.testing.assert_array_equal(toks, ref_tokens)

        seen = []

        def expected(names):
            assert names in PLAIN_KIDS
            seen.append(names)
        parents = _check_children(_spans(tracer), "serve/decode", expected)
        # some spans launch one step and read the one before (how many:
        # the waves of six requests through two slots decide, each
        # drains the loop)
        assert DECODE_KIDS in seen
        assert all(a["active"] >= 1 for n, (_, _, _, a) in zip(seen, parents)
                   if "dispatch" in n)

    def test_admit_children(self, mon, net, prompts):
        _, tracer = mon
        _serve(net, prompts)

        def expected(names):
            assert names[0] == "plan" and names[-1] == "fanout"
            assert names[1:-1] == ["dispatch", "wait", "post"]
        parents = _check_children(_spans(tracer), "serve/admit", expected)
        assert all(a["admitted"] >= 1 and a["bucket"] >= 3
                   for _, _, _, a in parents)

    def test_admit_children_with_the_radix_cache(self, mon, net):
        """A shared-prefix wave goes the fork-and-extend way and the
        tree's insert is bookkeeping of its own: still plan, then
        groups of dispatch, wait and post, then the fan-out."""
        _, tracer = mon
        base = np.arange(2 * BL) % V
        prompts = [np.concatenate([base, [i + 1, i + 2]]) for i in range(4)]
        _serve(net, prompts, prefix_cache="radix")

        def expected(names):
            assert names[0] == "plan" and names[-1] == "fanout"
            body = names[1:-1]
            while body[:3] == ["dispatch", "wait", "post"]:
                body = body[3:]
            assert body in ([], ["post"]), names
        _check_children(_spans(tracer), "serve/admit", expected)

    def test_spec_step_children(self, mon, net):
        _, tracer = mon
        prompt = np.asarray([1, 2, 3, 1, 2, 3], np.int64)
        _serve(net, [prompt], n_slots=1, speculative=4, floor_s=4e-3)
        seen = []

        def expected(names):
            # the scheduler's acceptance policy may turn drafting off
            # for a dispatch: that one is a plain decode step
            assert names == SPEC_KIDS or names in PLAIN_KIDS
            seen.append(names)
        _check_children(_spans(tracer), "serve/decode", expected)
        assert SPEC_KIDS in seen

    def test_every_loop_child_carries_its_it(self, mon, net, prompts):
        _, tracer = mon
        _serve(net, prompts)
        spans = _spans(tracer)
        loops = [(s, e, a["it"]) for n, s, e, a in spans
                 if n == "serve/loop"]
        its = [it for _, _, it in loops]
        assert its == sorted(set(its)), "one loop span an iteration"
        for name, s, e, a in spans:
            if name in ("serve/loop", "serve/sched/park"):
                continue
            inside = [it for ls, le, it in loops if ls <= s and e <= le]
            assert inside == [a["it"]], (name, a)
        names = {n for n, _, _, _ in spans}
        assert {"serve/sched/intake", "serve/sched/fanout",
                "serve/sched/gauges", "serve/sched/park"} <= names


class TestTimers:
    def test_timers_partition_the_loop(self, mon, net, prompts):
        reg, tracer = mon
        _serve(net, prompts)
        spans = _spans(tracer)
        parked = {a["it"] for n, _, _, a in spans if n == "serve/sched/park"}
        loops = sum(e - s for n, s, e, a in spans
                    if n == "serve/loop" and a["it"] not in parked)
        snap = reg.snapshot()
        parts = sum(v["sum"] for f in FAMILIES for v in snap[f]["values"])
        assert parts == pytest.approx(loops / 1e6, rel=0.05)
        n_loops = sum(1 for n, _, _, a in spans
                      if n == "serve/loop" and a["it"] not in parked)
        assert snap["serving_sched_host_seconds"]["values"][0]["count"] \
            == n_loops

    def test_counts_are_the_engines_dispatches_and_waves(self, mon, net,
                                                         prompts):
        reg, tracer = mon
        count = {}
        _serve(net, prompts, count=count)
        snap = reg.snapshot()

        def n_obs(family):
            return sum(v["count"] for v in snap[family]["values"])
        assert count["admit_many"] > 0
        # six requests through two slots: some reads launch nothing
        # (before a wave, the last step) and are given no seconds
        assert 0 < count["period"] < count["step"]
        for family in ("serving_decode_host_seconds",
                       "serving_decode_wait_seconds",
                       "serving_step_seconds"):
            assert n_obs(family) == count["period"], family
        assert n_obs("serving_decode_batch_slots") == count["step"]
        for family in ("serving_admit_wave_seconds",
                       "serving_admit_wait_seconds"):
            assert n_obs(family) == count["admit_many"], family
        waves = snap["serving_admit_waves_total"]["values"][0]["value"]
        assert waves == count["admit_many"]
        names = tracer.span_names()
        assert names["serve/decode/wait"] == count["step"]
        assert names["serve/admit/wait"] == count["admit_many"]
        slots = snap["serving_decode_batch_slots"]["values"][0]
        assert 1 <= slots["sum"] / slots["count"] <= 2


class TestProfilerClock:
    def test_spans_lie_on_one_line_of_the_profiles_host_plane(
            self, mon, net, prompts, tmp_path):
        from jax.profiler import ProfileData

        _, tracer = mon
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _serve(net, prompts)
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        lines = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for i, line in enumerate(plane.lines):
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith(PROFILE_PREFIX + "serve/")]
                    if evs:
                        lines[(plane.name, i)] = evs
        assert len(lines) == 1, "the scheduler thread is one line"
        evs, = lines.values()
        waits = sorted(e for e in evs
                       if e[0] == PROFILE_PREFIX + "serve/decode/wait")
        loops = [e for e in evs if e[0] == PROFILE_PREFIX + "serve/loop"]
        ring = sorted((s, e) for n, s, e, _ in _spans(tracer)
                      if n == "serve/decode/wait")
        assert len(waits) == len(ring) > 0
        for (_, s, e, stats), (rs, re) in zip(waits, ring):
            assert abs((e - s) / 1e6 - (re - rs) / 1e3) < 1.0   # ms
            inside = [lp for lp in loops if lp[1] <= s and e <= lp[2]]
            assert len(inside) == 1
            assert inside[0][3]["it"] == stats["it"]


class TestOffAndOverhead:
    @pytest.fixture
    def annotations(self, monkeypatch):
        made = {"n": 0}
        real = jax.profiler.TraceAnnotation

        class Counting(real):
            def __init__(self, *a, **kw):
                made["n"] += 1
                super().__init__(*a, **kw)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        return made

    def test_monitoring_off_makes_no_annotation_and_no_span(
            self, annotations, net, prompts, ref_tokens):
        assert not monitor.is_enabled()
        monitor.tracer().clear()
        _, toks = _serve(net, prompts)
        np.testing.assert_array_equal(toks, ref_tokens)
        assert annotations["n"] == 0
        assert monitor.tracer().events() == []
        assert monitor.span("serve/loop", it=1) is monitor.NOOP_SPAN
        # and the same class is what monitoring, once on, does make
        tracer = Tracer()
        monitor.enable(registry=MetricsRegistry(), tracer=tracer)
        try:
            _serve(net, prompts[:1])
        finally:
            _monitor_off()
        assert annotations["n"] == len(
            [e for e in tracer.events() if e["name"].startswith(
                ("serve/", "fit/"))]) > 0

    def test_same_tokens_syncs_and_readbacks_on_and_off(
            self, monkeypatch, net, prompts, ref_tokens):
        calls = {"sync": 0, "readback": 0}
        real_sync, real_asarray = jax.block_until_ready, np.asarray

        def sync(*a, **kw):
            calls["sync"] += 1
            return real_sync(*a, **kw)

        def asarray(a, *args, **kw):
            calls["readback"] += isinstance(a, jax.Array)
            return real_asarray(a, *args, **kw)

        monkeypatch.setattr(jax, "block_until_ready", sync)
        monkeypatch.setattr(np, "asarray", asarray)
        # one request in flight at a time: the number of waves and of
        # decode dispatches is then the schedule's, not the threads'
        _, toks_off = _serve(net, prompts, one_by_one=True)
        off = dict(calls)
        monitor.enable(registry=MetricsRegistry(), tracer=Tracer())
        try:
            _, toks_on = _serve(net, prompts, one_by_one=True)
        finally:
            _monitor_off()
        assert off["readback"] > 0
        assert {k: calls[k] - off[k] for k in calls} == off
        np.testing.assert_array_equal(toks_on, toks_off)
        np.testing.assert_array_equal(toks_on, ref_tokens)


class TestRequestLanes:
    def test_lane_phases_name_the_loop_that_served_them(self, mon, net,
                                                        prompts):
        _, tracer = mon
        streams, _ = _serve(net, prompts)
        loops = {a["it"]: (s, e) for n, s, e, a in _spans(tracer)
                 if n == "serve/loop"}
        origin = tracer._origin_ns / 1e3        # the ring counts from here
        seen = 0
        for st in streams:
            for ph in st.trace.phases:
                if ph["name"] not in ("prefill", "decode"):
                    continue
                s, e = loops[ph["args"]["it"]]
                assert s <= ph["t0"] * 1e6 - origin
                assert ph["t1"] * 1e6 - origin <= e
                seen += 1
        assert seen >= len(streams) * 2
        lanes = [e for e in tracer.events()
                 if e["name"] in ("req/prefill", "req/decode")]
        assert lanes and all(e["args"]["it"] in loops for e in lanes)


class TestDecodeReadsInPlace:
    """ISSUE 28: where the kernels are on and the pool can be tiled the
    single-token decode attends over the pool in place
    (`dl4tpu_paged_decode`, interpret mode here), and
    `serving_decode_kv_read_pct` says how much of the slots' whole
    block tables each dispatch read."""

    WIDE = dict(vocab_size=V, d_model=128, n_layers=LAYERS, n_heads=2,
                max_len=MAXLEN, seed=3)            # Dh 64: tileable
    BL = 8
    PROMPT_LENS = (3, 9, 14)

    def _staggered(self, monkeypatch, kernels):
        """Three prompts admitted one iteration apart, six steps (the
        last request's last):
        (tokens by request, kv_read_pct by step, what the formula
        gives by step, the pools)."""
        from deeplearning4j_tpu.serving import PagedDecodeEngine
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", kernels)
        net = TransformerLM(**self.WIDE).init()
        eng = PagedDecodeEngine(net, n_slots=4, n_blocks=20,
                                block_len=self.BL)
        whole = eng.n_slots * eng.max_blocks
        out, slot2req, read, formula = {}, {}, [], []
        for it in range(6):
            if it < len(self.PROMPT_LENS):
                ids = np.random.default_rng(it).integers(
                    0, V, self.PROMPT_LENS[it])
                (slot, first, _), = eng.admit_many(
                    [dict(prompt_ids=ids, n_tokens=5)])
                slot2req[slot] = it
                out[it] = [int(first)]
            decoding = np.flatnonzero(eng.remaining > 0)
            formula.append(100.0 * sum(
                -(-(int(eng.pos[s]) + 1) // self.BL)
                for s in decoding) / whole)
            emitted, _ = eng.step()
            read.append(eng.kv_read_pct)
            for slot, toks in emitted.items():
                out[slot2req[slot]].extend(toks)
        return out, read, formula, eng.pool.kv

    def test_same_greedy_tokens_and_the_read_share(self, monkeypatch):
        toks0, read0, formula, kv0 = self._staggered(monkeypatch, "0")
        toks1, read1, formula1, kv1 = self._staggered(monkeypatch, "1")
        assert toks1 == toks0
        assert all(len(t) == 5 for t in toks0.values())
        assert formula1 == formula and 0 < min(formula) < max(formula) < 50
        assert read0 == [100.0] * len(read0)       # the gather path
        assert read1 == formula                    # blocks held, no more
        # the streams' K and V agree too (layer 2's depend on layer
        # 1's attention), which identical tokens of a random-init
        # model alone would not show; block 0 is the garbage block,
        # where the idle slot's lanes write
        for (k0, v0), (k1, v1) in zip(kv0, kv1):
            np.testing.assert_allclose(np.asarray(k1)[1:],
                                       np.asarray(k0)[1:], atol=1e-4)
            np.testing.assert_allclose(np.asarray(v1)[1:],
                                       np.asarray(v0)[1:], atol=1e-4)

    @pytest.mark.parametrize("kernels", ["0", "1"])
    def test_the_family_is_observed_at_each_decode_dispatch(
            self, mon, monkeypatch, kernels):
        reg, _ = mon
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", kernels)
        net = TransformerLM(**self.WIDE).init()
        prompts = np.random.default_rng(5).integers(0, V, (4, 6))
        seen = []
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=self.BL)
        eng = srv.engine
        _on_decode_reads(eng, lambda: seen.append(eng.kv_read_pct))
        srv.start()
        try:
            for s in [srv.generate_async(p, N_TOK) for p in prompts]:
                s.result(timeout=300)
        finally:
            srv.stop()
        fam = reg.snapshot()["serving_decode_kv_read_pct"]["values"][0]
        assert fam["count"] == len(seen) > 0
        assert fam["sum"] == pytest.approx(sum(seen))
        if kernels == "0":
            assert seen == [100.0] * len(seen)
        else:
            # two slots of at most 12 positions: 1-2 blocks of 4 each
            assert all(100 / 8 <= r <= 100 * 4 / 8 for r in seen)
