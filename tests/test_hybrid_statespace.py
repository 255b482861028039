"""The hybrid state-space model (`zoo.HybridStateSpaceLM`,
`nn/layers/statespace.py`, `kernels/selective_scan.py`) against its plain
reference (`benchmark/reference/AI21-Jamba2-3B.py`, which imports nothing
of the program), at the configuration's rehearsal size on the CPU (6
layers, attention at 1 and 4), and through the serving engine's third
kind of cache: a state of fixed size a slot beside the pages.

Tolerances, and why each: float32 against float32 is 1e-4 on logits of
order 1 (the two sides sum in other orders: a convolution as four shifted
products against a sum of taps, blocks of queries against one softmax);
the reference computed in bfloat16 reads 1e-3 and more on the same
numbers.  Log-probabilities through the slot state take the same 1e-4.
Under the `bf16_params` policy the program holds and multiplies in
bfloat16 and is held to 0.15 on logits whose spread is 1-2.
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "AI21-Jamba2-3B"
F32_TOL = 1e-4
PAD = 64                        # the rehearsal's serve_positions


def _load(kind, name=NAME):
    path = os.path.join(ROOT, "benchmark", kind, f"{name}.py")
    mod_name = f"t_bench_{kind}_{name}".replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference")


@pytest.fixture(scope="module")
def model():
    return _load("models")


def full_cfg():
    with open(os.path.join(ROOT, "benchmark", "configs", f"{NAME}.json")) as f:
        return json.load(f)


def rehearsal_cfg(**over):
    cfg = full_cfg()
    cfg.update(cfg["rehearsal"])
    cfg.update(param_dtype="float32", dtype_policy="float32")
    cfg.update(over)
    return cfg


def build(model, ref, cfg, seed=7):
    net = model.build(cfg)
    params = ref.init_params(cfg, jax.random.PRNGKey(seed))
    net.params = model.to_program(params, cfg)
    net.net_state, net.updater_state, net._initialized = {}, {}, True
    return net, params


@pytest.fixture(scope="module")
def built(ref, model):
    """(cfg, net, reference weights) in float32, shared by the tests that
    change neither."""
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    return cfg, net, params


def ids(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg["vocab_size"], n)


def log_softmax(x):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(x, jnp.float32), -1))


def gap(a, b):
    return float(np.abs(np.asarray(a, np.float32)
                        - np.asarray(b, np.float32)).max())


def ref_logprobs(ref, params, cfg, seq):
    """The reference's log-probabilities at every position of `seq`, by
    one program whatever its length (padded after the end: no effect
    before it)."""
    padded = np.zeros(PAD, np.int32)
    padded[:len(seq)] = seq
    return log_softmax(ref.logits_row(params, jnp.asarray(padded),
                                      cfg))[:len(seq)]


def ref_greedy(ref, params, cfg, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(ref_logprobs(ref, params, cfg, seq)[-1])))
    return seq[len(prompt):]


def _engine(net, **kw):
    from deeplearning4j_tpu.serving.engine import PagedDecodeEngine
    kw = dict(dict(n_slots=4, n_blocks=40, block_len=8, max_positions=64),
              **kw)
    return PagedDecodeEngine(net, **kw)


def _walk_step(net, eng, kv, tables, token, pos, live):
    """One token a slot through the engine's plan, probabilities out: the
    decode program's body without its sampling."""
    h = jnp.asarray(token)[:, None]
    kv = list(kv)
    for entry in eng._plan:
        layer, lp = net.layers[entry[1]], net.params.get(str(entry[1]), {})
        if entry[0] == "block":
            h, kv[entry[2]] = layer.paged_step(lp, h, kv[entry[2]], tables,
                                               pos, live)
        elif entry[0] == "state":
            h, kv[entry[2]] = layer.state_step(lp, h, kv[entry[2]], live)
        else:
            h, _ = layer.forward(lp, {}, h, train=False, rng=None)
    return tuple(kv), h[:, -1]


def served_logprobs(net, eng, slot, tokens):
    """Teacher-forced from what the engine holds for `slot` NOW: feed
    `tokens` one at a time from the slot's position through the plan (the
    other slots not live) -> log-probabilities after each.  On a copy of
    the pools: the engine goes on undisturbed."""
    S = eng.n_slots
    live = jnp.asarray(np.arange(S) == slot)
    tables = jnp.asarray(eng.block_tables.copy())
    need = -(-(int(eng.pos[slot]) + len(tokens)) // eng.block_len)
    assert need <= len(eng.slots[slot].blocks), "grant the blocks first"
    kv = jax.tree_util.tree_map(jnp.copy, eng.pool.kv)
    step = net.__dict__.setdefault("_test_walk", {}).get(id(eng))
    if step is None:
        step = net.__dict__["_test_walk"][id(eng)] = jax.jit(
            lambda kv, tables, tok, pos, live: _walk_step(
                net, eng, kv, tables, tok, pos, live))
    out = []
    for j, t in enumerate(tokens):
        tok = np.zeros(S, np.int32)
        tok[slot] = t
        pos = eng.pos.copy()
        pos[slot] += j
        kv, p = step(kv, tables, jnp.asarray(tok), jnp.asarray(pos), live)
        out.append(np.log(np.asarray(p[slot])))
    return np.stack(out)


# ------------------------------------------------------ model == reference
@pytest.mark.parametrize("layer", [0, 1], ids=["mamba", "attention"])
def test_block_is_the_reference_in_float32(ref, built, layer):
    cfg, net, params = built
    kind = ref.layer_kinds(cfg)[layer]
    assert kind == ("attention" if layer == 1 else "mamba")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 30, cfg["hidden_size"]))
    block = net.layers[layer + 1]
    assert block.mixer == kind
    assert (block.paged_cache, block.slot_state) == (layer == 1, layer == 0)
    got, _ = block.forward(net.params[str(layer + 1)], {}, x)
    want = ref.block(x[0], params["layers"][layer], cfg, kind)
    assert gap(got[0], want) < F32_TOL
    # the reference in bfloat16 is outside that tolerance: it is tight
    low = ref.block(x[0], params["layers"][layer], cfg, kind, "bf16")
    assert gap(low, want) > 5 * F32_TOL


def test_whole_model_is_the_reference_in_float32(ref, built):
    cfg, net, params = built
    assert ref.layer_kinds(cfg) == ["mamba", "attention", "mamba"] * 2
    seq = ids(cfg, 40, seed=1)
    got = np.log(np.asarray(net.output(seq[None]))[0])
    assert gap(got, ref_logprobs(ref, params, cfg, seq)) < F32_TOL


def test_whole_model_under_the_bf16_policy(ref, model):
    """The configuration's own policy: weights held and multiplied in
    bfloat16, the recurrence, its constants and the norms' gains float32."""
    cfg = rehearsal_cfg(param_dtype="bfloat16", dtype_policy="bf16_params")
    net, params = build(model, ref, cfg)
    held = {k: v.dtype for k, v in net.params["1"].items()}
    assert all(held[k] == jnp.float32 for k in (
        "A_log", "D", "dt_bias", "mixer_norm", "mlp_norm", "dt_norm"))
    assert held["in_proj"] == held["conv_w"] == jnp.bfloat16
    seq = ids(cfg, 40, seed=2)
    got = np.log(np.asarray(net.output(seq[None]), np.float32)[0])
    want = ref_logprobs(ref, params, cfg, seq)
    assert 1e-3 < gap(got, want) < 0.15


def test_streamed_in_pieces_is_the_full_forward(built):
    """`rnn_time_step` carries a Mamba layer's state and an attention
    layer's cache: a sequence in three pieces is the sequence whole."""
    cfg, net, _ = built
    seq = ids(cfg, 24, seed=3)[None]
    whole = np.asarray(net.output(seq))
    net.rnn_clear_previous_state()
    pieces = [np.asarray(net.rnn_time_step(seq[:, a:b]))
              for a, b in ((0, 9), (9, 10), (10, 24))]
    net.rnn_clear_previous_state()
    assert gap(np.concatenate(pieces, 1), whole) < 1e-5


# ------------------------------------------------ prefill through a bucket
@pytest.mark.parametrize("last_idx", range(8))
def test_padded_prompt_leaves_the_unpadded_state(ref, built, last_idx):
    """A prompt right-padded to its bucket of 8: whatever its last real
    token, the wave's probabilities are the reference's at that token and
    every Mamba layer's state (and its convolution's tail) is the one the
    unpadded prompt leaves: nothing past `last_idx` moved it."""
    cfg, net, params = built
    eng = net.__dict__.setdefault("_test_engine", None) or _engine(net)
    net.__dict__["_test_engine"] = eng
    seq = ids(cfg, 8, seed=4)
    n = last_idx + 1
    probs, carries, _ = eng._run_prefill(seq[None].astype(np.int32),
                                         np.asarray([last_idx], np.int32))
    want = ref_logprobs(ref, params, cfg, seq[:n])[-1]
    assert gap(np.log(np.asarray(probs[0])), want) < F32_TOL
    # layer 1 (a Mamba layer) alone, unpadded, on the same input
    block, lp = net.layers[1], net.params["1"]
    x = net.params["0"]["W"][jnp.asarray(seq)][None]
    _, (h, tail) = block.forward_prefill(lp, x[:, :n], None)
    got_h, got_tail = carries[eng.pool.n_paged]
    assert gap(got_h, h) < 1e-6 and gap(got_tail, tail) < 1e-6
    if n < 8:
        # and the bucket's end would have been another state
        _, (h_end, _) = block.forward_prefill(lp, x, None)
        assert gap(h_end, h) > 1e-3


def test_state_gb_is_reckoned_from_the_arrays_the_program_is_handed(ref,
                                                                    model):
    """`state_gb` is structural: the size of the state arrays a decode
    dispatch's program takes and returns, read off the arrays themselves,
    whatever the slots that decode. Half the slots' rows read half; and a
    wave's prefill is ONE program for the whole plan."""
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    readings = {}
    for n_slots in (2, 4):
        eng = _engine(net, n_slots=n_slots)
        assert eng.pool.state_bytes() == sum(
            a.nbytes for arrays in eng.pool.kv[eng.pool.n_paged:]
            for a in arrays)
        prompt = ids(cfg, 9, seed=8)
        slot, first, _ = eng.admit(prompt, 6)
        out = {slot: [first]}
        _run_all(eng, out)
        assert out[slot] == ref_greedy(ref, params, cfg, prompt, 6)
        readings[n_slots] = eng.state_gb       # one slot decoded in each
    assert readings[4] == pytest.approx(2 * readings[2]) and readings[2] > 0
    keys = [k[0] for k in net.__dict__["_serving_jit_cache"]]
    assert keys.count("prefill_paged") == 1
    assert not [k for k in keys if k.startswith("prefill_") and
                k != "prefill_paged"]


def test_prefill_then_decode_through_the_slot_state(ref, built):
    """A prompt through the paged prefill, its pages and its states given
    to slot 2 of pools that hold 1e30 everywhere else, then token by
    token through `state_step` / `paged_step`: the log-probabilities at
    every position are the reference's full forward's, and no other
    slot's row is read or written."""
    cfg, net, params = built
    eng = _engine(net)
    seq = ids(cfg, 46, seed=5)
    P = 19
    prompts = np.zeros((1, 32), np.int32)
    prompts[0, :P] = seq[:P]
    probs, carries, _ = eng._run_prefill(prompts, np.asarray([P - 1], np.int32))
    want = ref_logprobs(ref, params, cfg, seq)
    assert gap(np.log(np.asarray(probs[0])), want[P - 1]) < F32_TOL
    blocks = [3, 9, 4, 7, 11, 2]
    rows = np.zeros((1, 4), np.int32)
    rows[0, :3] = blocks[:3]
    poisoned = jax.tree_util.tree_map(lambda a: jnp.full_like(a, 1e30),
                                      eng.pool.kv)
    fin = eng._build_admit_finish(1, True)
    kv, _ = fin(poisoned, (jnp.asarray(rows), jnp.asarray([2], jnp.int32)),
                carries, probs, jnp.zeros((1, 2), jnp.uint32),
                jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.float32),
                jnp.ones(1, jnp.float32))
    tables = np.zeros((4, eng.max_blocks), np.int32)
    tables[2, :6] = blocks
    live = jnp.asarray(np.arange(4) == 2)
    step = jax.jit(lambda kv, tok, pos: _walk_step(
        net, eng, kv, jnp.asarray(tables), tok, pos, live))
    for t in range(P, 46):
        tok = np.zeros(4, np.int32)
        tok[2] = seq[t]
        kv, p = step(kv, jnp.asarray(tok), jnp.full((4,), t, jnp.int32))
        assert gap(np.log(np.asarray(p[2])), want[t]) < F32_TOL, t
    for h, tail in kv[eng.pool.n_paged:]:
        assert bool(jnp.all(h[jnp.asarray([0, 1, 3])] == 1e30))
        assert bool(jnp.all(tail[:, jnp.asarray([0, 1, 3])] == 1e30))
        assert bool(jnp.all(jnp.abs(h[2]) < 1e3))


# --------------------------------------------------- the engine's scenarios
def _run_all(eng, got):
    while eng.active.any() or eng.in_flight:
        emitted, _ = eng.step()
        for s, toks in emitted.items():
            got[s].extend(toks)


def test_two_lengths_in_one_wave_and_one_beside_decoding_slots(ref, built):
    """Two requests of unequal length in one padded wave, a third
    admitted while they decode: each stream is the reference's greedy
    stream, and the third's log-probabilities, teacher-forced from what
    the engine holds after its admission, are the reference's."""
    cfg, net, params = built
    eng = _engine(net)
    prompts = [ids(cfg, n, seed=10 + n) for n in (5, 11, 7)]
    want = [ref_greedy(ref, params, cfg, p, 12) for p in prompts]
    out = eng.admit_many([dict(prompt_ids=p, n_tokens=12)
                          for p in prompts[:2]])
    assert eng.admit_bucket == 16 and len(out) == 2
    assert eng.scan_pad_pct == pytest.approx(100.0 * (32 - 16) / 32)
    got = {s: [first] for s, first, _ in out}
    for _ in range(3):
        emitted, _ = eng.step()
        for s, toks in emitted.items():
            got[s].extend(toks)
    (s3, first3, _), = eng.admit_many([dict(prompt_ids=prompts[2],
                                            n_tokens=12)])
    got[s3] = [first3]
    assert eng.active_slots == 3
    forced = ids(cfg, 1, seed=99)
    lp = served_logprobs(net, eng, s3, forced)
    seq = np.concatenate([prompts[2], forced])
    assert gap(lp[0], ref_logprobs(ref, params, cfg, seq)[-1]) < F32_TOL
    _run_all(eng, got)
    for (s, _, _), w in zip(out + [(s3, 0, 0)], want):
        assert got[s] == w
    # the state arrays the decode program is handed, in and out each step:
    # 4 slots' rows of 4 state layers, reckoned from the arrays themselves
    assert eng.pool.state_bytes() == 4 * 4 * (16 * 128 * 4 + 3 * 128 * 4)
    assert eng.state_gb == pytest.approx(2 * eng.pool.state_bytes() / 1e9)


@pytest.mark.parametrize("fault", [None, "state_not_installed",
                                   "state_returned_unchanged"])
def test_released_slot_serves_its_next_request_from_zeros(ref, built,
                                                          monkeypatch, fault):
    """A slot released and given to a second request serves it as the
    reference does: the admission overwrites the slot's state whole.  With
    a fault planted (the admission leaves the first request's state in
    place; the decode step hands the state back as it found it) the same
    comparison fails."""
    cfg, net, params = built
    eng = _engine(net, n_slots=2, allocation="upfront")
    first = ids(cfg, 9, seed=20)
    slot, _, _ = eng.admit(first, 6)
    got = {slot: []}
    _run_all(eng, got)
    assert eng.slots[slot] is None
    if fault == "state_not_installed":
        build_fin = eng._build_admit_finish
        n_paged = eng.pool.n_paged

        def faulty(k, greedy):
            fin = build_fin(k, greedy)

            def run(kv, *a):
                new, firsts = fin(kv, *a)
                return new[:n_paged] + tuple(kv[n_paged:]), firsts
            return run
        monkeypatch.setattr(eng, "_build_admit_finish", faulty)
        eng._admit_finish.clear()
        # the faulty program must not donate what it hands back
        monkeypatch.setattr(
            "deeplearning4j_tpu.serving.engine.donate_argnums",
            lambda *a: ())
        net.__dict__["_serving_jit_cache"] = {
            k: v for k, v in net.__dict__["_serving_jit_cache"].items()
            if k[0] != "admit"}
    second = ids(cfg, 6, seed=21)
    slot2, _, _ = eng.admit(second, 8)
    assert slot2 == slot
    forced = ids(cfg, 5, seed=22)
    if fault == "state_returned_unchanged":
        block = type(net.layers[1])
        real = block.state_step
        monkeypatch.setattr(
            block, "state_step",
            lambda self, p, x, arrays, live=None: (
                real(self, p, x, arrays, live)[0], arrays))
        net.__dict__.get("_test_walk", {}).pop(id(eng), None)
    lp = served_logprobs(net, eng, slot2, forced)
    want = ref_logprobs(ref, params, cfg,
                        np.concatenate([second, forced]))[len(second):]
    if fault is None:
        assert gap(lp, want) < F32_TOL
    else:
        assert gap(lp, want) > 1e-2
    net.__dict__.get("_test_walk", {}).pop(id(eng), None)
    if fault == "state_not_installed":
        net.__dict__["_serving_jit_cache"] = {
            k: v for k, v in net.__dict__["_serving_jit_cache"].items()
            if k[0] != "admit"}


def test_preempted_and_resumed_request_streams_the_undisturbed_ids(
        ref, built):
    """Preemption rebuilds by prefilling again (no snapshot): the
    continuation (prompt + emitted, emit offset kept) goes on with the
    ids of a request left alone, which are the reference's."""
    cfg, net, params = built
    prompt = ids(cfg, 10, seed=30)
    want = ref_greedy(ref, params, cfg, prompt, 16)
    eng = _engine(net)
    slot, first, _ = eng.admit(prompt, 16, request_id="r")
    got = [first]
    for _ in range(5):
        emitted, _ = eng.step_ahead()
        got.extend(emitted.get(slot, []))
    eng._preempt(slot)
    emitted, _ = eng.drain()
    got.extend(emitted.get(slot, []))
    (note,) = eng.drain_preempted()
    assert note["request_id"] == "r" and note["emitted"] == len(got)
    assert eng.slots[slot] is None
    again, first, _ = eng.admit(
        np.concatenate([prompt, got]), 16 - len(got), request_id="r")
    rest = {again: [first]}
    _run_all(eng, rest)
    assert got + rest[again] == want


def test_greedy_through_the_server_is_generate(ref, built):
    """conf -> MultiLayerNetwork -> GenerationServer with no option of
    its own: six requests over four slots (slots reused, waves padded)
    stream `generate()`'s tokens, which are the reference's; the two
    families are observed."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import MetricsRegistry
    from deeplearning4j_tpu.serving import GenerationServer
    from deeplearning4j_tpu.zoo.transformer import generate
    cfg, net, params = built
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n) for n in (5, 19, 27, 3, 14, 30)]
    want = [generate(net, p[None], 12, temperature=0)[0] for p in prompts]
    assert list(want[0]) == ref_greedy(ref, params, cfg, prompts[0], 12)
    saved = monitor._STATE.registry, monitor._STATE.tracer
    reg = monitor.enable(registry=MetricsRegistry(), jit_compile=False,
                         device_memory=False)
    try:
        srv = GenerationServer(net, n_slots=4, n_blocks=40, block_len=8,
                               max_positions=64, max_prefill_tokens=64,
                               min_prefill_bucket=8)
        srv.warmup(32)
        srv.start()
        streams = [srv.generate_async(p, 12) for p in prompts]
        got = [np.asarray(s.result(timeout=300)) for s in streams]
        srv.drain()
        srv.stop()
        snap = reg.snapshot()
    finally:
        monitor.disable()
        monitor._STATE.registry, monitor._STATE.tracer = saved
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    assert srv.engine.pool.free_blocks == 39
    state = snap["serving_decode_state_gb"]["values"][0]
    assert state["count"] > 0 and state["sum"] / state["count"] == \
        pytest.approx(2 * srv.engine.pool.state_bytes() / 1e9)
    pad = snap["serving_scan_pad_pct"]["values"][0]
    assert pad["count"] > 0 and 0 <= pad["sum"] / pad["count"] < 100


# ------------------------------------------------------------- the kernel
def test_selective_scan_kernel_is_the_lax_scan():
    """`dl4tpu_selective_scan` (interpret mode) against the plain scan
    over time: rows of unequal length (one ends inside the first block of
    positions, one at a block's edge, one fills the bucket), a non-zero
    initial state, two channel tiles."""
    from deeplearning4j_tpu.kernels.selective_scan import (
        KERNEL_NAME, selective_scan, selective_scan_reference,
        unsupported_reason)
    K, T, C, N = 3, 256, 256, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (K, T, C))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (K, T, C)) - 3)
    a = -jnp.exp(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None]
                 * jnp.ones((1, C)))
    b = jax.random.normal(ks[2], (K, T, N))
    c = jax.random.normal(ks[3], (K, T, N))
    d = jax.random.normal(ks[4], (C,))
    h0 = jax.random.normal(ks[5], (K, N, C))
    lengths = jnp.asarray([T, 128, 5], jnp.int32)
    y, h = selective_scan(x, delta, a, b, c, d, h0, lengths, channels=128,
                          interpret=True)
    yr, hr = selective_scan_reference(x, delta, a, b, c, d, h0, lengths)
    real = (np.arange(T)[None, :] < np.asarray(lengths)[:, None])[..., None]
    assert gap(np.where(real, y, 0), np.where(real, yr, 0)) < 1e-5
    assert gap(h, hr) < 1e-5
    # a block of positions wholly past a row's length is not computed
    assert bool(jnp.all(y[2, 128:] == 0)) and bool(jnp.all(y[1, 128:] == 0))
    # the state past the length is the state AT the length
    _, h5 = selective_scan_reference(x[2:, :5], delta[2:, :5], a, b[2:, :5],
                                     c[2:, :5], d, h0[2:],
                                     jnp.asarray([5], jnp.int32))
    assert gap(h[2:], h5) < 1e-5
    assert KERNEL_NAME == "dl4tpu_selective_scan"
    assert unsupported_reason((1, 100, 256), 16) is not None
    assert unsupported_reason((1, 2048, 5120), 16) is None


def test_the_layer_takes_the_kernel_where_it_can(monkeypatch):
    """With the kernels' switch on, a prefill whose bucket the kernel can
    tile runs it (interpret mode here) and leaves the plain scan's state."""
    from deeplearning4j_tpu.nn.layers.statespace import HybridStateSpaceBlock
    block = HybridStateSpaceBlock(n_in=64, ffn_hidden=32, dt_rank=8)
    p = block.init_params(jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))
    lengths = jnp.asarray([128, 77], jnp.int32)
    want_y, want = block.forward_prefill(p, x, lengths)
    monkeypatch.setenv("DL4J_PALLAS_KERNELS", "1")
    text = str(jax.make_jaxpr(
        lambda x: block.forward_prefill(p, x, lengths))(x))
    assert "dl4tpu_selective_scan" in text
    got_y, got = block.forward_prefill(p, x, lengths)
    assert gap(got[0], want[0]) < 1e-5 and gap(got[1], want[1]) < 1e-6
    assert gap(got_y[1, :77], want_y[1, :77]) < 1e-5


# ---------------------------------------------------- refusals and the rest
def test_what_cannot_serve_a_state_refuses_loudly(built):
    cfg, net, _ = built
    with pytest.raises(NotImplementedError, match="radix prefix cache"):
        _engine(net, prefix_cache="radix")
    with pytest.raises(NotImplementedError, match="speculative decoding"):
        _engine(net, speculative=4)
    eng = _engine(net)
    assert eng.state_layers == 4 and eng.pool.n_paged == 2
    assert eng._plan == [("plain", 0), ("state", 1, 2), ("block", 2, 0),
                         ("state", 3, 3), ("state", 4, 4), ("block", 5, 1),
                         ("state", 6, 5), ("plain", 7), ("plain", 8)]
    assert [a.shape for a in eng.pool.kv[2]] == [(4, 16, 128), (3, 4, 128)]
    assert eng.pool.kv[2][0].dtype == jnp.float32
    with pytest.raises(NotImplementedError, match="registered prefix"):
        eng.register_prefix(ids(cfg, 8))
    slot, _, _ = eng.admit(ids(cfg, 6), 4)
    with pytest.raises(NotImplementedError, match="handoff wire"):
        eng.export_handoff(slot)
    with pytest.raises(NotImplementedError, match="handoff wire"):
        eng.adopt_handoff({}, np.zeros((2, 2, 1, 8, 1, 16), np.float32))


def test_a_recurrent_layer_that_declares_nothing_is_still_refused():
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (EmbeddingLayer, LSTM,
                                              RnnOutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.layers.transformer import (
        TransformerEncoderBlock)
    b = (NeuralNetConfiguration.builder().seed(1).list()
         .layer(EmbeddingLayer(n_in=32, n_out=16, has_bias=False))
         .layer(TransformerEncoderBlock(n_heads=2, causal=True, cache_len=16))
         .layer(LSTM(n_out=16))
         .layer(RnnOutputLayer(n_out=32, activation="softmax", loss="mcxent")))
    b.set_input_type(InputType.recurrent(32))
    net = MultiLayerNetwork(b.build()).init()
    with pytest.raises(ValueError, match="declares no way to serve it"):
        _engine(net, block_len=4, max_positions=None)


def test_a_net_with_no_state_layer_keeps_its_programs():
    """The pools, the plan and the admission program's key of a net whose
    layers all keep pages are what they were."""
    from deeplearning4j_tpu.serving.engine import PagedDecodeEngine
    from deeplearning4j_tpu.zoo.transformer import TransformerLM
    net = TransformerLM(64, d_model=16, n_layers=2, n_heads=2, max_len=32,
                        seed=3).init()
    eng = PagedDecodeEngine(net, n_slots=4, n_blocks=24, block_len=4)
    assert eng.state_layers == 0 and eng.pool.state_indices == []
    assert eng.pool.n_paged == len(eng.pool.kv) == 2
    assert eng.pool.state_bytes() == 0
    assert eng._plan == [("plain", 0), ("pos", 1), ("block", 2, 0),
                         ("block", 3, 1), ("plain", 4)]
    eng.admit(np.arange(5), 3)
    while eng.active.any():
        eng.step()
    assert eng.state_gb == 0.0 and eng.scan_pad_pct is None
    keys = set(net.__dict__["_serving_jit_cache"])
    assert keys == {("admit", 1, True, 4, None),
                    ("decode", True, 1, tuple(eng._plan), None,
                     (False, False))}


def test_zoo_builder_ties_the_head_and_orders_its_layers():
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo import HybridStateSpaceLM
    zoo = HybridStateSpaceLM(64, d_model=32, n_layers=28, attn_period=14,
                             attn_offset=7)
    kinds = zoo.layer_types
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds.count("mamba") == 26
    net = HybridStateSpaceLM(64, d_model=32, n_layers=3, attn_period=3,
                             attn_offset=1, mlp_hidden=48, head_dim=8,
                             cache_len=32).init()
    assert isinstance(net, MultiLayerNetwork)
    assert net.params["0"]["W"] is net.params[str(len(net.layers) - 1)]["W"]
    assert [l.mixer for l in net.layers[1:4]] == ["mamba", "attention",
                                                  "mamba"]
    assert not net.conf.input_preprocessors
    out = net.rnn_time_step(np.zeros((1, 3), np.int32))
    assert out.shape == (1, 3, 64)


def test_serving_names_neither_the_model_nor_the_layer():
    import re
    serving = os.path.join(ROOT, "deeplearning4j_tpu", "serving")
    for name in os.listdir(serving):
        if name.endswith(".py"):
            text = open(os.path.join(serving, name)).read()
            assert not re.search(r"jamba|mamba|HybridStateSpace(LM)?\b(?!Block)",
                                 text, re.I), name


def test_full_size_work_is_the_published_model():
    """The arithmetic of ISSUE 36: parameters by part, whole."""
    work = _load("work")
    cfg = full_cfg()
    assert work.mamba_params(cfg) == 41_241_792
    assert work.attention_params(cfg) == 13_762_560
    assert work.mlp_params(cfg) == 62_914_560
    assert work.held_params(cfg) == 3_029_337_472          # 6.06 GB in bf16
    assert 2 * work.token_matmul_params(cfg) == pytest.approx(5.717e9,
                                                              rel=1e-3)
    one = work.selective_scan(cfg, 1)
    assert one == {"flops": 7.0 * 16 * 5120, "bytes": 4.0 * (3 * 5120 + 32)}
    cell = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", "jamba2_3b_serve_chat.json")))
    s = cell["server"]
    assert s["n_blocks"] == s["n_slots"] * (
        s["max_positions"] // s["block_len"]) + 1 == 2561
    assert cfg["serve_positions"] == s["max_positions"] == 2560
    # a slot's state: 26 layers of h (float32) and the tail (bfloat16)
    assert 26 * (5120 * 16 * 4 + 3 * 5120 * 2) == 9_318_400


def test_configuration_keeps_every_published_width():
    cfg = full_cfg()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside this checkout")
    rows = [json.loads(line) for line in open(path) if line.strip()]
    row = next(r for r in rows if r["name"] == NAME)
    assert {k for k, v in row["config"].items() if cfg.get(k) != v} == set()
    assert cfg["reduced"] == [] and cfg["source"] == row["source_url"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == row["source_url"]
