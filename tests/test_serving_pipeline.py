"""The decode loop runs one step ahead (ISSUE 30).

`PagedDecodeEngine.step_ahead` launches step n+1 before it reads step n:
the step's carry (last token, position, tokens left, emit index) stays
on the device, the host advances its mirrors by the program's own rule
at the launch, and a slot that finishes in the step just launched is
released then.  `step()` is the same code with nothing left in flight.

Contracts held here, on the CPU at rehearsal size:

(a) the pipelined server's streams are, token for token, those of a loop
    drained after every step, for the GPT-2 block and the latent block,
    greedy and sampled; greedy streams are also `generate()`'s (a
    sampled serving stream is its own contract: token t is drawn from
    `fold_in(request_key, t)`, which whole-batch `generate()` cannot
    offer, see tests/test_serving.py::TestSampledDeterminism);
(b) every token is delivered once, in order, and a slot is named in
    `finished` together with its last token: admissions between two
    launches, a slot finishing in the step in flight, one-token
    requests, answers that end on a block boundary, fused chunks;
(c) whatever needs the tokens on the host, or rewrites a slot, reads
    the step in flight first: `evict`, cancellation, preemption,
    `export_handoff`, drain-and-stop (the hot swap's barrier), a live
    slot whose mirrors the host changed with its token still on the
    device;
(d) blocks freed at a launch and granted again while the step that
    wrote them is in flight hold what the drained loop leaves there;
(e) `serving_decode_overlap_pct` says how often the loop ran ahead, and
    a read that launches nothing feeds no seconds to the step's
    families nor to the rate the shedding policy reads;
(f) direct callers of `step()` lose nothing, and a steady step uploads
    nothing.
"""

import time

import numpy as np
import pytest
from test_latent_moe import _load, build, rehearsal_cfg

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import MetricsRegistry
from deeplearning4j_tpu.serving import GenerationServer, PagedDecodeEngine
from deeplearning4j_tpu.serving import engine as engine_mod
from deeplearning4j_tpu.zoo.transformer import TransformerLM, generate

V, D, HEADS, LAYERS, MAXLEN, BL = 23, 16, 4, 2, 32, 4
GPT_KW = dict(n_slots=3, n_blocks=24, block_len=BL)
LATENT_KW = dict(n_slots=3, n_blocks=40, block_len=8, max_positions=64)


@pytest.fixture(scope="module")
def gpt():
    return TransformerLM(vocab_size=V, d_model=D, n_layers=LAYERS,
                         n_heads=HEADS, max_len=MAXLEN, seed=3).init()


@pytest.fixture(scope="module")
def latent():
    """`sarvam-105b` at its rehearsal size, float32, weights from the
    reference's initialiser: tests/test_latent_moe.py's own build."""
    net, _ = build(_load("models", "sarvam-105b"),
                   _load("reference", "sarvam-105b"), rehearsal_cfg())
    return net


@pytest.fixture(scope="module")
def nets(gpt, latent):
    return {"gpt2": (gpt, GPT_KW, V), "latent": (latent, LATENT_KW, 256)}


def _requests(vocab, lens, n_tokens, *, sampled=False, seed=5):
    """Requests of the given prompt lengths and answer lengths; with
    `sampled` every other one samples (temperature 0.8, top_p 0.95,
    its own key), the rest are greedy, as the serving cells mix them."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i, (p, n) in enumerate(zip(lens, n_tokens)):
        r = dict(prompt_ids=rng.integers(0, vocab, p), n_tokens=int(n))
        if sampled and i % 2 == 0:
            r.update(temperature=0.8, top_p=0.95,
                     rng=np.asarray([11, i], np.uint32))
        reqs.append(r)
    return reqs


def _greedy_refs(net, reqs):
    return [np.asarray(generate(net, r["prompt_ids"][None], r["n_tokens"],
                                temperature=0)[0]) for r in reqs]


def drive(eng, reqs, *, ahead):
    """The scheduler's loop without its threads: requests admitted in
    order as slots and blocks allow, decoded to the end -> tokens by
    request.  `ahead`: step n+1 is launched before step n is read, and
    the step in flight is read before an admission (as
    `server._schedule_once` does); else every step is read by the call
    that launched it.  Holds on the way that a slot is named in
    `finished` by the call that returns its last token, and that a
    preempted request goes back to the head of the queue with every
    token it has emitted."""
    out, owner = {}, {}
    pending = list(range(len(reqs)))

    def take(emitted, finished):
        for slot, toks in emitted.items():
            assert toks, "a slot that emitted is named with its tokens"
            out[owner[slot]].extend(toks)
        for slot in finished:
            assert slot in emitted
            r = owner.pop(slot)
            assert len(out[r]) == reqs[r]["n_tokens"]

    guard = 0
    while pending or eng.active.any() or eng.in_flight:
        guard += 1
        assert guard < 500, "the loop does not end"
        while pending:
            r = pending[0]
            base = reqs[r]
            done = out.get(r, [])
            prompt = np.concatenate(
                [base["prompt_ids"], np.asarray(done, np.int64)])
            left = base["n_tokens"] - len(done)
            if not eng.can_admit(len(prompt), left):
                break
            if eng.in_flight:
                take(*eng.drain())
            pending.pop(0)
            (slot, first, fin), = eng.admit_many([dict(
                base, prompt_ids=prompt, n_tokens=left,
                request_id=r, emit_start=len(done))])
            out.setdefault(r, []).append(first)
            if not fin:
                owner[slot] = r
        take(*(eng.step_ahead() if ahead else eng.step()))
        for note in eng.drain_preempted():
            r = owner.pop(note["slot"])
            assert note["emitted"] == len(out[r])
            pending.insert(0, r)
    assert not owner
    return [np.asarray(out[r]) for r in range(len(reqs))]


def serve(net, kw, reqs, **server_kw):
    srv = GenerationServer(net, **dict(kw, **server_kw)).start()
    try:
        streams = [srv.generate_async(
            r["prompt_ids"], r["n_tokens"],
            **{k: r[k] for k in ("temperature", "top_p", "rng") if k in r})
            for r in reqs]
        got = [np.asarray(s.result(timeout=300)) for s in streams]
    finally:
        srv.stop()
    assert not srv.engine.in_flight
    return got, srv


@pytest.fixture
def registry():
    saved = monitor._STATE.registry, monitor._STATE.tracer
    reg = monitor.enable(registry=MetricsRegistry(), jit_compile=False,
                         device_memory=False)
    yield reg
    monitor.disable()
    monitor._STATE.registry, monitor._STATE.tracer = saved


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ------------------------------------------------------------------- (a)
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("block", ["gpt2", "latent"])
def test_pipelined_streams_are_the_drained_loops(nets, block, sampled):
    net, kw, vocab = nets[block]
    reqs = _requests(vocab, (3, 9, 5, 12, 4, 7, 6), (9, 5, 12, 2, 8, 1, 10),
                     sampled=sampled)
    drained = drive(PagedDecodeEngine(net, **kw), reqs, ahead=False)
    _same(drive(PagedDecodeEngine(net, **kw), reqs, ahead=True), drained)
    served, _ = serve(net, kw, reqs)
    _same(served, drained)
    for r, got, want in zip(reqs, drained, _greedy_refs(net, reqs)):
        if "temperature" not in r:
            np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------------- (b)
SCHEDULES = {
    # six answers through two slots: a wave falls between two launches
    # whenever a slot frees
    "admissions_between_launches": dict(
        lens=(3,) * 6, n_tokens=(6,) * 6, n_slots=2),
    # answers of unequal length: slots finish while others go on
    "a_slot_finishes_in_the_step_in_flight": dict(
        lens=(3, 4, 5), n_tokens=(2, 5, 9), n_slots=3),
    # an answer of one token ends at its admission, of two in the first
    # decode step
    "requests_of_one_and_two_tokens": dict(
        lens=(3, 5, 4, 6), n_tokens=(1, 2, 1, 7), n_slots=2),
    # prompt + answer fill whole blocks exactly (block_len 4)
    "answers_end_on_a_block_boundary": dict(
        lens=(3, 4, 6, 8), n_tokens=(5, 4, 6, 8), n_slots=2),
    # four micro-steps a dispatch: a slot may finish mid-chunk
    "fused_chunks_of_four": dict(
        lens=(3, 4, 5, 6), n_tokens=(6, 9, 3, 8), n_slots=2,
        steps_per_dispatch=4),
    # a pool too small for both answers: the loop preempts and requeues
    "preemption_under_a_pool_too_small": dict(
        lens=(3, 3, 3, 3), n_tokens=(6, 6, 6, 6), n_slots=4, n_blocks=5),
}


@pytest.mark.parametrize("name", list(SCHEDULES))
def test_every_token_once_in_order(gpt, name):
    sch = dict(SCHEDULES[name])
    reqs = _requests(V, sch.pop("lens"), sch.pop("n_tokens"))
    kw = dict(GPT_KW, **sch)
    want = _greedy_refs(gpt, reqs)
    eng = PagedDecodeEngine(gpt, **kw)
    _same(drive(eng, reqs, ahead=True), want)
    if name.startswith("preemption"):
        assert eng.evict_requeue_total >= 1, "the pool never ran short"
    served, _ = serve(gpt, kw, reqs)
    _same(served, want)


def test_a_finishing_slot_is_released_at_the_launch(gpt):
    """The host knows at the launch which slots end in the step: their
    blocks go back to the pool then, and `finished` names them in the
    call that returns the step's tokens."""
    reqs = _requests(V, (3, 3), (2, 4))
    eng = PagedDecodeEngine(gpt, **GPT_KW)
    (sa, fa, _), (sb, fb, _) = eng.admit_many(reqs)
    free = eng.free_blocks
    assert eng.step_ahead() == ({}, [])      # launched, nothing to read
    assert eng.in_flight
    assert eng.slots[sa] is None and not eng.active[sa]
    assert eng.free_blocks > free
    emitted, finished = eng.step_ahead()     # launches 2, reads 1
    assert finished == [sa] and len(emitted[sa]) == 1
    want = _greedy_refs(gpt, reqs)
    assert [fa] + emitted[sa] == list(want[0])
    out_b = [fb] + emitted[sb]
    while eng.in_flight:
        emitted, finished = eng.step_ahead()
        out_b += emitted.get(sb, [])
    assert finished == [sb] and out_b == list(want[1])


# ------------------------------------------------------------------- (c)
def test_evict_reads_the_step_in_flight_first(gpt):
    reqs = _requests(V, (3, 4), (8, 8))
    eng = PagedDecodeEngine(gpt, **GPT_KW)
    (sa, fa, _), (sb, fb, _) = eng.admit_many(reqs)
    got = {sa: [fa], sb: [fb]}
    for _ in range(3):
        for slot, toks in eng.step_ahead()[0].items():
            got[slot] += toks
    assert eng._flight is not None, "a step is in flight"
    counted = eng.slots[sa].emitted
    eng.evict(sa)
    assert eng._flight is None and eng.in_flight    # read, and held
    emitted, finished = eng.drain()
    assert finished == [] and set(emitted) == {sa, sb}
    got[sa] += emitted[sa]
    got[sb] += emitted[sb]
    want = _greedy_refs(gpt, reqs)
    # the evicted request: every token the host had counted, in order
    assert len(got[sa]) == counted and got[sa] == list(want[0][:counted])
    while eng.active.any() or eng.in_flight:
        got[sb] += eng.step_ahead()[0].get(sb, [])
    assert got[sb] == list(want[1])


def test_a_live_slot_cut_short_behind_a_step_in_flight(gpt):
    """The host shortens a live slot's answer by hand, as a stop rule
    would, while a step is in flight: its mirrors now differ from the
    carry, but the slot's last token is the device's. The launch reads
    the step in flight first, and the stream goes on unbroken."""
    reqs = _requests(V, (3,), (12,))
    eng = PagedDecodeEngine(gpt, **GPT_KW)
    (slot, first, _), = eng.admit_many(reqs)
    got = [first]
    for _ in range(3):
        got += eng.step_ahead()[0].get(slot, [])
    assert eng._flight is not None and len(got) == 3
    eng.remaining[slot] = 2
    done = []
    while eng.active.any() or eng.in_flight:
        emitted, finished = eng.step_ahead()
        got += emitted.get(slot, [])
        done += finished
    assert done == [slot] and len(got) == 6
    assert got == list(_greedy_refs(gpt, reqs)[0][:6])


def test_a_cancelled_stream_keeps_what_was_in_flight(gpt):
    reqs = _requests(V, (3, 4), (28, 12))
    want = _greedy_refs(gpt, reqs)
    srv = GenerationServer(gpt, **GPT_KW).start()
    try:
        doomed = srv.generate_async(reqs[0]["prompt_ids"], 28)
        other = srv.generate_async(reqs[1]["prompt_ids"], 12)
        for n, _ in enumerate(doomed):
            if n == 4:
                doomed.cancel()
                break
        part = np.asarray(doomed.result(timeout=60))
        whole = np.asarray(other.result(timeout=60))
        # the server goes on serving, from the slot the cancel freed too
        again = np.asarray(srv.generate_async(
            reqs[1]["prompt_ids"], 12).result(timeout=60))
    finally:
        srv.stop()
    assert 5 <= len(part) <= 28
    np.testing.assert_array_equal(part, want[0][:len(part)])
    np.testing.assert_array_equal(whole, want[1])
    np.testing.assert_array_equal(again, want[1])
    assert not srv.engine.in_flight


def test_preemption_requeues_every_token_emitted(gpt):
    """tests/test_serving.py::test_pool_pressure_preempts_lowest_progress
    run one step ahead: the victim's tokens of the step in flight come
    back from the call that preempted it, and the notice counts them."""
    reqs = _requests(V, (3, 3), (13, 6))
    want = _greedy_refs(gpt, reqs)
    eng = PagedDecodeEngine(gpt, n_slots=2, n_blocks=5, block_len=BL)
    (sa, fa, _), = eng.admit_many([dict(reqs[0], request_id="A")])
    out_a, out_b = [fa], []
    for _ in range(4):                       # A builds a progress lead
        out_a += eng.step_ahead()[0].get(sa, [])
    out_a += eng.drain()[0].get(sa, [])
    (sb, fb, _), = eng.admit_many([dict(reqs[1], request_id="B")])
    out_b.append(fb)
    notes = []
    while not notes:
        emitted, _ = eng.step_ahead()
        out_a += emitted.get(sa, [])
        out_b += emitted.get(sb, [])
        notes = eng.drain_preempted()
    assert [n["request_id"] for n in notes] == ["B"]
    assert notes[0]["emitted"] == len(out_b) and 1 <= len(out_b) < 6
    while eng.active.any() or eng.in_flight:
        out_a += eng.step_ahead()[0].get(sa, [])
    assert out_a == list(want[0])
    cont = np.concatenate([reqs[1]["prompt_ids"], np.asarray(out_b)])
    (sb2, f2, _), = eng.admit_many([dict(
        prompt_ids=cont, n_tokens=6 - len(out_b), request_id="B",
        emit_start=len(out_b))])
    out_b.append(f2)
    while eng.active.any() or eng.in_flight:
        out_b += eng.step_ahead()[0].get(sb2, [])
    assert out_b == list(want[1])


def test_export_handoff_reads_the_step_in_flight_first(gpt):
    reqs = _requests(V, (5,), (10,))
    want = _greedy_refs(gpt, reqs)[0]
    eng = PagedDecodeEngine(gpt, **GPT_KW)
    (slot, first, _), = eng.admit_many(reqs)
    out = [first]
    for _ in range(3):
        out += eng.step_ahead()[0].get(slot, [])
    assert eng._flight is not None
    header, kv = eng.export_handoff(slot)
    assert eng._flight is None
    out += eng.drain()[0][slot]
    assert header["last_token"] == out[-1] == want[len(out) - 1]
    assert header["pos"] == 5 + len(out) - 1
    assert header["remaining"] == 10 - len(out)
    eng.evict(slot)
    other = PagedDecodeEngine(gpt, **GPT_KW)
    adopted = other.adopt_handoff(header, kv)
    while other.active.any() or other.in_flight:
        out += other.step_ahead()[0].get(adopted, [])
    assert out == list(want)


def test_drain_then_stop_loses_no_token(gpt):
    """The hot swap's barrier: `drain()` closes admissions and waits
    for every open stream, `stop()` then finds nothing in flight."""
    reqs = _requests(V, (3, 4, 5, 6), (9, 7, 12, 5))
    want = _greedy_refs(gpt, reqs)
    srv = GenerationServer(gpt, **dict(GPT_KW, n_slots=2)).start()
    streams = [srv.generate_async(r["prompt_ids"], r["n_tokens"])
               for r in reqs]
    assert srv.drain(timeout=120)
    srv.stop()
    assert not srv.engine.in_flight and not srv.engine.active.any()
    _same([np.asarray(s.result(timeout=1)) for s in streams], want)


def test_stop_hands_on_the_step_in_flight(gpt):
    """`stop()` mid-stream fails what is still open, after the tokens
    of the step in flight have gone out: what a stream holds is a
    prefix of its answer, and the engine is left with nothing unread."""
    reqs = _requests(V, (3,), (28,))
    want = _greedy_refs(gpt, reqs)[0]
    srv = GenerationServer(gpt, **GPT_KW).start()
    stream = srv.generate_async(reqs[0]["prompt_ids"], 28)
    deadline = time.monotonic() + 60
    while len(stream.tokens) < 3 and time.monotonic() < deadline:
        time.sleep(0.001)
    srv.stop()
    assert not srv.engine.in_flight
    held = list(stream.tokens)
    assert held == list(want[:len(held)]) and len(held) >= 3


# ------------------------------------------------------------------- (d)
def test_blocks_granted_again_under_the_step_that_wrote_them(gpt):
    """A's blocks are freed at the launch of its last step and granted
    to B's admission while that step is in flight: the device runs its
    programs in order, so the pool ends as the drained loop leaves it
    (the garbage block, which nothing reads, apart)."""
    reqs = _requests(V, (7, 6), (2, 9))
    want = _greedy_refs(gpt, reqs)
    kw = dict(n_slots=1, n_blocks=6, block_len=BL)

    def run(ahead):
        eng = PagedDecodeEngine(gpt, **kw)
        (sa, fa, _), = eng.admit_many(reqs[:1])
        a_blocks = set(eng.slots[sa].blocks)
        out_a, out_b = [fa], []
        if ahead:
            assert eng.step_ahead() == ({}, [])
            assert eng._flight is not None and eng.slots[sa] is None
        else:
            out_a += eng.step()[0][sa]
        (sb, fb, _), = eng.admit_many(reqs[1:])
        assert set(eng.slots[sb].blocks) & a_blocks, "no block came back"
        if ahead:
            # A's last token is still unread, and comes back under the
            # slot B now holds: a caller that maps slots to requests
            # drains before it admits (server._schedule_once)
            assert eng._flight is not None
            emitted, finished = eng.drain()
            out_a += emitted[sa]
            assert finished == [sa]
        out_b.append(fb)
        while eng.active.any() or eng.in_flight:
            step = eng.step_ahead if ahead else eng.step
            out_b += step()[0].get(sb, [])
        return out_a, out_b, [np.asarray(a)[1:] for arrays in eng.pool.kv
                              for a in arrays]

    a0, b0, pool0 = run(False)
    a1, b1, pool1 = run(True)
    assert a0 == a1 == list(want[0]) and b0 == b1 == list(want[1])
    _same(pool1, pool0)


# ------------------------------------------------------------------- (e)
def _overlap(reg):
    fam = reg.snapshot()["serving_decode_overlap_pct"]["values"][0]
    return fam["sum"], fam["count"]


def test_overlap_reads_zero_for_a_lone_one_step_request(gpt, registry):
    reqs = _requests(V, (3,), (2,))
    served, _ = serve(gpt, GPT_KW, reqs)
    _same(served, _greedy_refs(gpt, reqs))
    assert _overlap(registry) == (0.0, 1)


def test_overlap_reads_over_ninety_for_a_steady_batch(gpt, registry):
    reqs = _requests(V, (3, 3), (28, 28))
    served, srv = serve(gpt, GPT_KW, reqs)
    _same(served, _greedy_refs(gpt, reqs))
    total, count = _overlap(registry)
    # 27 decode steps an answer; the loop drains for each wave (one, or
    # two where the second request missed the first's) and no more
    assert 27 <= count <= 54
    assert total / count > 90.0
    snap = registry.snapshot()

    def of(family, key):
        return snap[family]["values"][0][key]
    assert of("serving_decode_batch_slots", "count") == count
    # a step read by a call that launched none (before each wave but
    # the first, and the last of all) is given no seconds: its launch
    # lies in the period before
    periods = of("serving_step_seconds", "count")
    assert count - 3 <= periods <= count - 1
    assert of("serving_decode_wait_seconds", "count") == periods
    assert of("serving_decode_host_seconds", "count") == periods
    # the host's part and the wait add up to the step
    assert of("serving_decode_host_seconds", "sum") + of(
        "serving_decode_wait_seconds", "sum") == pytest.approx(
        of("serving_step_seconds", "sum"), rel=0.05)


def test_the_shedding_rate_is_tokens_over_wall_time_across_waves(
        gpt, monkeypatch):
    """Every wave but the first is preceded by a read of the step in
    flight that takes microseconds (its tokens are ready): such a read
    feeds nothing into the rate `_should_shed` projects the queue by.
    Two slots under the sandbox's step floor, eight answers of 24
    steps each, so that the waves (two floors each, one for the
    prefill and one for the launch that reads nothing) are a sixth of
    the wall time: the rate, which is tokens over the seconds of
    decode steps, stays within 2x of the decode tokens over the wall
    time. One such read at its 100 us would put it 20x over."""
    monkeypatch.setenv("DL4J_SANDBOX_MODEL", "1")
    kw = dict(GPT_KW, n_slots=2, dispatch_floor_s=0.01)
    srv = GenerationServer(gpt, **kw)
    rates = []

    def decode(*a, _real=srv._decode, **k):
        _real(*a, **k)
        rates.append(srv._ewma_tok_s)
    srv._decode = decode
    srv.start()
    try:
        srv.generate_async(np.arange(3), 9).result(timeout=300)  # compiles
        reqs = _requests(V, (3,) * 8, (25,) * 8)
        del rates[:]
        t0 = time.perf_counter()
        streams = [srv.generate_async(r["prompt_ids"], r["n_tokens"])
                   for r in reqs]
        got = [np.asarray(s.result(timeout=300)) for s in streams]
        wall = time.perf_counter() - t0
    finally:
        srv.stop()
    _same(got, _greedy_refs(gpt, reqs))
    per_s = 8 * (25 - 1) / wall       # a wave emits each first token
    steady = rates[8:]                # past the lone compile request's
    assert len(steady) > 20
    assert 0.5 * per_s <= min(steady) and max(steady) <= 2.0 * per_s, \
        (per_s, min(steady), max(steady))


# ------------------------------------------------------------------- (f)
@pytest.mark.parametrize("ahead", [False, True], ids=["step", "step_ahead"])
def test_direct_callers_lose_nothing(gpt, ahead):
    reqs = _requests(V, (3, 5, 4), (7, 2, 11))
    eng = PagedDecodeEngine(gpt, **GPT_KW)
    admitted = eng.admit_many(reqs)
    out = {slot: [first] for slot, first, _ in admitted}
    done = []
    while eng.active.any():
        emitted, finished = eng.step_ahead() if ahead else eng.step()
        for slot, toks in emitted.items():
            out[slot] += toks
        done += finished
    if ahead:
        emitted, finished = eng.drain()      # the last step's tokens
        assert emitted and finished
        for slot, toks in emitted.items():
            out[slot] += toks
        done += finished
    assert not eng.in_flight and eng.drain() == ({}, [])
    assert sorted(done) == sorted(out)
    _same([np.asarray(out[slot]) for slot, _, _ in admitted],
          _greedy_refs(gpt, reqs))


def test_a_steady_step_uploads_nothing(gpt, monkeypatch):
    """What the host did not change since the last launch is not
    uploaded again: a step between two grants launches on device arrays
    alone, a grant uploads the block tables and nothing else."""
    uploads = []
    real = engine_mod.jnp.asarray

    def counting(x, *a, **kw):
        uploads.append(np.shape(x))
        return real(x, *a, **kw)

    reqs = _requests(V, (4,), (13,))          # the prompt fills a block
    eng = PagedDecodeEngine(gpt, **GPT_KW)
    (slot, first, _), = eng.admit_many(reqs)
    out = [first]
    monkeypatch.setattr(engine_mod.jnp, "asarray", counting)
    per_step = []
    while eng.active.any() or eng.in_flight:
        uploads.clear()
        out += eng.step_ahead()[0].get(slot, [])
        per_step.append(list(uploads))
    assert out == list(_greedy_refs(gpt, reqs)[0])
    tables = (GPT_KW["n_slots"], MAXLEN // BL)
    # step 1 (pos 4) opens a block and carries the admission's changes;
    # after it a grant falls on every fourth step, and the last call
    # launches nothing
    assert tables in per_step[0] and len(per_step[0]) >= 2
    for n, ups in enumerate(per_step[1:12], start=1):
        assert ups == ([tables] if n % BL == 0 else []), (n, ups)
    assert per_step[12] == []
