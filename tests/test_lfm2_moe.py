"""The LFM2 family's hybrid block (`nn/layers/statespace.py`: the gated
short convolution, QK-normed rotate-half attention, routed experts all
held) through `zoo.HybridStateSpaceLM` against its plain reference
(`benchmark/reference/LFM2-8B-A1B.py`, which imports nothing of the
program), at the configuration's rehearsal size on the CPU (6 layers: two
dense convolution layers, attention at 2, three routed convolution
layers), and through the serving engine: the convolution's two rows a
slot beside the attention layer's pages.

Tolerances, and why each: float32 against float32 is 1e-4 on logits of
order 1 (the two sides sum in other orders: three shifted products
against a sum of taps, blocks of queries against one softmax, grouped
expert rows against a mask); the reference computed in bfloat16 reads
1e-3 and more on the same numbers.  A state or a routing weight gone
wrong moves the logits by 1e-2 and more: the planted faults are held to
that.
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
NAME = "LFM2-8B-A1B"
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
    """One token a slot through the engine's plan, probabilities out."""
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
    """Teacher-forced from what the engine holds for `slot` now, on a
    copy of the pools: log-probabilities after each of `tokens`."""
    S = eng.n_slots
    live = jnp.asarray(np.arange(S) == slot)
    tables = jnp.asarray(eng.block_tables.copy())
    need = -(-(int(eng.pos[slot]) + len(tokens)) // eng.block_len)
    assert need <= len(eng.slots[slot].blocks), "grant the blocks first"
    kv = jax.tree_util.tree_map(jnp.copy, eng.pool.kv)
    step = jax.jit(lambda kv, tables, tok, pos, live: _walk_step(
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


def _run_all(eng, got):
    while eng.active.any() or eng.in_flight:
        emitted, _ = eng.step()
        for s, toks in emitted.items():
            got[s].extend(toks)


# ------------------------------------------------------ model == reference
@pytest.mark.parametrize("layer", [0, 2, 3],
                         ids=["dense_conv", "routed_attention",
                              "routed_conv"])
def test_block_is_the_reference_in_float32(ref, built, layer):
    cfg, net, params = built
    kind, routed = ref.layer_kinds(cfg)[layer]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 30, cfg["hidden_size"]))
    block = net.layers[layer + 1]
    assert (block.mixer, bool(block.n_routed)) == (
        {"conv": "conv", "full_attention": "attention"}[kind], routed)
    got, _ = block.forward(net.params[str(layer + 1)], {}, x)
    want = ref.block(x[0], params["layers"][layer], cfg, kind, routed)
    assert gap(got[0], want) < F32_TOL
    low = ref.block(x[0], params["layers"][layer], cfg, kind, routed, "bf16")
    assert gap(low, want) > 5 * F32_TOL


def test_whole_model_is_the_reference_in_float32(ref, built):
    cfg, net, params = built
    assert [k for k, _ in ref.layer_kinds(cfg)] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv"]
    assert [r for _, r in ref.layer_kinds(cfg)] == [False, False] + [True] * 4
    seq = ids(cfg, 40, seed=1)
    got = np.log(np.asarray(net.output(seq[None]))[0])
    assert gap(got, ref_logprobs(ref, params, cfg, seq)) < F32_TOL


def test_whole_model_under_the_bf16_policy(ref, model):
    """The configuration's own policy: weights held and multiplied in
    bfloat16, the selection bias and the gains float32."""
    cfg = rehearsal_cfg(param_dtype="bfloat16", dtype_policy="bf16_params")
    net, params = build(model, ref, cfg)
    held = {k: v.dtype for k, v in net.params["4"].items()}
    assert all(held[k] == jnp.float32 for k in (
        "expert_bias", "mixer_norm", "mlp_norm"))
    assert held["in_proj"] == held["conv_w"] == held["e_gate"] \
        == jnp.bfloat16
    seq = ids(cfg, 40, seed=2)
    got = np.log(np.asarray(net.output(seq[None]), np.float32)[0])
    want = ref_logprobs(ref, params, cfg, seq)
    assert 1e-3 < gap(got, want) < 0.3


def test_streamed_in_pieces_is_the_full_forward(built):
    """`rnn_time_step` carries the convolution's two rows and the
    attention layer's cache: a sequence in three pieces is the sequence
    whole."""
    cfg, net, _ = built
    seq = ids(cfg, 24, seed=3)[None]
    whole = np.asarray(net.output(seq))
    net.rnn_clear_previous_state()
    pieces = [np.asarray(net.rnn_time_step(seq[:, a:b]))
              for a, b in ((0, 9), (9, 10), (10, 11), (11, 24))]
    net.rnn_clear_previous_state()
    assert gap(np.concatenate(pieces, 1), whole) < 1e-5


# ----------------------------------------------------- the convolution alone
def test_conv_prefill_decode_and_carry_are_the_reference(ref, built):
    """The mixer of a routed convolution layer: a prefill of 13 rows
    leaves the last two rows of `B * x`; 7 decode steps from that state
    give the reference's whole-sequence output row by row; the state is
    the last two rows whatever way they came."""
    cfg, net, params = built
    block, lp, w = net.layers[4], net.params["4"], params["layers"][3]
    D = cfg["hidden_size"]
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 20, D))
    want = ref.short_conv(u[0], w, cfg, "f32")
    tail0 = jnp.zeros((2, 1, D))
    out, tail = block._conv(lp, u[:, :13], tail0)
    assert gap(out[0], want[:13]) < F32_TOL
    outs = []
    for t in range(13, 20):
        o, tail = block._conv(lp, u[:, t:t + 1], tail)
        outs.append(o[0, 0])
    assert gap(jnp.stack(outs), want[13:]) < F32_TOL
    bcx = u[0] @ lp["in_proj"]
    v = bcx[:, :D] * bcx[:, 2 * D:]
    assert gap(tail[:, 0], v[18:20]) < 1e-6
    # the whole sequence in one call leaves the same rows
    _, whole = block._conv(lp, u, tail0)
    assert gap(whole, tail) < 1e-6
    # the taps reach back two rows: a state of zeros is another output
    o_cold, _ = block._conv(lp, u[:, 13:14], tail0)
    assert gap(o_cold[0, 0], want[13]) > 1e-2


@pytest.mark.parametrize("last_idx", range(8))
def test_padded_prompt_leaves_the_unpadded_state(ref, built, last_idx):
    """A prompt right-padded to its bucket of 8: whatever its last real
    token, the wave's probabilities are the reference's at that token and
    every convolution layer's two rows are the ones the unpadded prompt
    leaves: nothing past `last_idx` moved them."""
    cfg, net, params = built
    eng = net.__dict__.setdefault("_test_engine", None) or _engine(net)
    net.__dict__["_test_engine"] = eng
    seq = ids(cfg, 8, seed=4)
    n = last_idx + 1
    probs, carries, _ = eng._run_prefill(seq[None].astype(np.int32),
                                         np.asarray([last_idx], np.int32))
    want = ref_logprobs(ref, params, cfg, seq[:n])[-1]
    assert gap(np.log(np.asarray(probs[0])), want) < F32_TOL
    block, lp = net.layers[1], net.params["1"]
    x = net.params["0"]["W"][jnp.asarray(seq)][None]
    _, (tail,) = block.forward_prefill(lp, x[:, :n], None)
    (got_tail,) = carries[eng.pool.n_paged]
    assert gap(got_tail, tail) < 1e-6
    if n < 8:
        _, (tail_end,) = block.forward_prefill(lp, x, None)
        assert gap(tail_end, tail) > 1e-3


# --------------------------------------------------------------- routing
def test_the_bias_chooses_and_the_score_weighs():
    """Hand-picked scores where the bias reorders the experts: the chosen
    are the largest `s + b`, the gates `s / (sum s + eps)` of the chosen."""
    from deeplearning4j_tpu.nn.layers import moe
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0]])
    score = np.asarray(jax.nn.sigmoid(logits))[0]
    bias = jnp.asarray([-1.0, 0.0, 0.5, 0.9, 0.0])
    # s + b: 0.881-1, 0.731, 0.5+0.5, 0.269+0.9, 0.119: experts 3 and 2
    # lead, then 1; expert 0 has the largest score and is not chosen
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0, -2.0]] + [[0.0] * 5] * 4)
    h = jnp.ones((1, 5))                 # h @ router: the logits above
    chosen, gates = moe.sigmoid_topk_route(h, router, bias, 3, 1.0,
                                           eps=1e-6)
    assert list(np.asarray(chosen[0])) == [3, 2, 1]
    picked = score[[3, 2, 1]]
    np.testing.assert_allclose(np.asarray(gates[0]),
                               picked / (picked.sum() + 1e-6), rtol=1e-6)
    # the scaling multiplies; no eps leaves the older programs' sum
    _, scaled = moe.sigmoid_topk_route(h, router, bias, 3, 2.5)
    np.testing.assert_allclose(np.asarray(scaled[0]),
                               2.5 * picked / picked.sum(), rtol=1e-6)


def test_eps_is_no_part_of_the_older_routers_programs():
    from deeplearning4j_tpu.nn.layers import moe
    h = jnp.ones((4, 8))
    r = jnp.ones((8, 6))
    b = jnp.zeros(6)
    plain = str(jax.make_jaxpr(
        lambda h: moe.sigmoid_topk_route(h, r, b, 2, 1.0))(h))
    zero = str(jax.make_jaxpr(
        lambda h: moe.sigmoid_topk_route(h, r, b, 2, 1.0, eps=0.0))(h))
    with_eps = str(jax.make_jaxpr(
        lambda h: moe.sigmoid_topk_route(h, r, b, 2, 1.0, eps=1e-6))(h))
    assert plain == zero and with_eps != plain


def test_every_expert_is_held_and_its_load_counted(built):
    """All the router's experts are the layer's own (`first` 0), and the
    counters read the share of experts a dispatch touched."""
    from deeplearning4j_tpu.nn.layers import moe
    cfg, net, _ = built
    block = net.layers[4]
    assert block.n_routed == cfg["num_experts"] == 8
    assert net.params["4"]["e_gate"].shape == (8, 64, 32)
    stats = {}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 1, 64))
    block._ffn(net.params["4"], x, stats=stats)
    rows, _, touched = (float(stats[k]) for k in (
        "moe_rows", "moe_load_max_over_mean", "moe_touched_pct"))
    assert rows == 4 and touched == pytest.approx(50.0)     # 4 of 8
    sizes = jnp.asarray([0, 3, 0, 1], jnp.int32)
    assert float(moe.expert_load_stats(sizes)[2]) == pytest.approx(50.0)


# ------------------------------------------------------- through serving
def test_prefill_then_decode_through_the_slot_state(ref, built):
    """A prompt through the paged prefill, its pages and rows given to
    slot 2 of pools that hold 1e30 everywhere else, then token by token
    through `state_step` / `paged_step`: the log-probabilities at every
    position are the reference's full forward's, and no other slot's
    row is read or written."""
    cfg, net, params = built
    eng = _engine(net)
    seq = ids(cfg, 46, seed=5)
    P = 19
    prompts = np.zeros((1, 32), np.int32)
    prompts[0, :P] = seq[:P]
    probs, carries, _ = eng._run_prefill(prompts,
                                         np.asarray([P - 1], np.int32))
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
    assert len(kv) == eng.pool.n_paged + 5        # 1 page layer, 5 conv
    for (tail,) in kv[eng.pool.n_paged:]:
        assert tail.shape == (2, 4, 64)
        assert bool(jnp.all(tail[:, jnp.asarray([0, 1, 3])] == 1e30))
        assert bool(jnp.all(jnp.abs(tail[:, 2]) < 1e3))


@pytest.mark.parametrize("fault", [None, "state_not_advanced",
                                   "bias_weighs"])
def test_served_logits_are_the_reference_and_faults_show(ref, model,
                                                         monkeypatch, fault):
    """Three requests of unequal length, two in one padded wave and one
    admitted while they decode; then the third's log-probabilities,
    teacher-forced from what the engine holds, are the reference's.  With
    a fault planted (the decode step hands the convolution's rows back
    as it found them; the router weighs by `s + b`) the comparison
    fails.  A net of its own: the programs a fault is traced into are
    cached on the net."""
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    if fault == "state_not_advanced":
        block = type(net.layers[1])
        real = block.state_step
        monkeypatch.setattr(
            block, "state_step",
            lambda self, p, x, arrays, live=None, **kw: (
                real(self, p, x, arrays, live, **kw)[0], arrays))
    elif fault == "bias_weighs":
        from deeplearning4j_tpu.nn.layers import moe
        real_route = moe.sigmoid_topk_route

        def weighs(h, router, bias, top_k, scaling, eps=0.0):
            chosen, _ = real_route(h, router, bias, top_k, scaling, eps)
            s = jax.nn.sigmoid(jnp.matmul(
                h, router, preferred_element_type=jnp.float32)) + bias
            picked = jnp.take_along_axis(s, chosen, -1)
            return chosen, picked / (picked.sum(-1, keepdims=True) + eps)
        monkeypatch.setattr(moe, "sigmoid_topk_route", weighs)
    # every block of a request granted at its admission: the teacher-
    # forced tokens below write past the pages a slot holds so far
    eng = _engine(net, allocation="upfront")
    prompts = [ids(cfg, n, seed=10 + n) for n in (5, 11, 7)]
    out = eng.admit_many([dict(prompt_ids=p, n_tokens=12)
                          for p in prompts[:2]])
    assert eng.admit_bucket == 16 and len(out) == 2
    got = {s: [first] for s, first, _ in out}
    for _ in range(3):
        emitted, _ = eng.step()
        for s, toks in emitted.items():
            got[s].extend(toks)
    (s3, first3, _), = eng.admit_many([dict(prompt_ids=prompts[2],
                                            n_tokens=12)])
    got[s3] = [first3]
    forced = ids(cfg, 6, seed=99)
    lp = served_logprobs(net, eng, s3, forced)
    seq = np.concatenate([prompts[2], forced])
    want = ref_logprobs(ref, params, cfg, seq)
    if fault is None:
        assert first3 == int(np.argmax(want[len(prompts[2]) - 1]))
        assert gap(lp, want[len(prompts[2]):]) < F32_TOL
        _run_all(eng, got)
        for (s, _, _), p in zip(out, prompts[:2]):
            assert got[s] == ref_greedy(ref, params, cfg, p, 12)
    else:
        assert gap(lp, want[len(prompts[2]):]) > 1e-2


def test_greedy_through_the_server_is_the_reference(ref, built):
    """conf -> MultiLayerNetwork -> GenerationServer with no option of
    its own: six requests over four slots stream tokens that are the
    reference's best at every position (by its logits); the expert and
    state families are observed."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import MetricsRegistry
    from deeplearning4j_tpu.serving import GenerationServer
    cfg, net, params = built
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n) for n in (5, 19, 27, 3, 14, 30)]
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
    for p, g in zip(prompts, got):
        lp = ref_logprobs(ref, params, cfg, np.concatenate([p, g]))
        rows = lp[len(p) - 1:-1]
        # each served token is the reference's best at its position
        assert np.max(rows.max(-1) - rows[np.arange(len(g)), g]) < F32_TOL
    assert srv.engine.pool.free_blocks == 39
    touched = snap["serving_moe_touched_pct"]["values"][0]
    rows = snap["serving_moe_rows"]["values"][0]
    assert touched["count"] == rows["count"] > 0
    assert 0 < touched["sum"] / touched["count"] <= 100
    # every dispatch reports how many of its rows the grouped kernel
    # multiplied: none on the CPU, where the layer takes `ragged_dot`
    kernel = snap["serving_moe_kernel_pct"]["values"][0]
    assert kernel["count"] == rows["count"] and kernel["sum"] == 0
    state = snap["serving_decode_state_gb"]["values"][0]
    assert state["count"] > 0 and state["sum"] / state["count"] == \
        pytest.approx(2 * srv.engine.pool.state_bytes() / 1e9)


def test_attention_reads_the_pool_in_place_with_narrow_heads(monkeypatch):
    """Eight key heads of 16 lanes (the published 8 of 64, narrowed): the
    decode step takes `dl4tpu_paged_decode`'s block-diagonal form
    (interpret mode here) and writes and reads what the gathering path
    does."""
    from deeplearning4j_tpu.nn.layers.statespace import HybridStateSpaceBlock
    block = HybridStateSpaceBlock(
        n_in=128, mixer="attention", n_heads=16, n_kv_heads=8, head_dim=16,
        qk_norm=True, rope_theta=1e6, ffn_hidden=32, n_routed=4,
        experts_per_token=2, eps=1e-5)
    p = block.init_params(jax.random.PRNGKey(0))
    S, bl, nb = 3, 8, 16
    pools = tuple(0.3 * jax.random.normal(jax.random.PRNGKey(i),
                                          (nb, bl, 128)) for i in (1, 2))
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]],
                        jnp.int32)
    pos = jnp.asarray([17, 3, 30], jnp.int32)
    live = jnp.asarray([True, False, True])
    x = jax.random.normal(jax.random.PRNGKey(3), (S, 1, 128))
    want, want_pools = block.paged_step(p, x, pools, table, pos, live)
    monkeypatch.setenv("DL4J_PALLAS_KERNELS", "1")
    assert block.paged_in_place(pools)
    text = str(jax.make_jaxpr(lambda x: block.paged_step(
        p, x, pools, table, pos, live))(x))
    assert "dl4tpu_paged_decode" in text
    got, got_pools = block.paged_step(p, x, pools, table, pos, live)
    assert gap(got[jnp.asarray([0, 2])], want[jnp.asarray([0, 2])]) < 1e-4
    for a, b in zip(got_pools, want_pools):
        assert gap(a, b) == 0.0


# ---------------------------------------------------- the published model
def test_zoo_builder_orders_kinds_and_ffns(model):
    cfg = full_cfg()
    cfg = dict(cfg, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=32,
               moe_intermediate_size=16, vocab_size=128)
    net = model.build(cfg)
    blocks = net.layers[1:15]
    assert [b.mixer for b in blocks] == (
        ["conv", "conv", "attention"] + ["conv"] * 3 + ["attention"]
        + ["conv"] * 3 + ["attention"] + ["conv"] * 3)
    assert [b.n_routed for b in blocks] == [0, 0] + [32] * 12
    assert [b.ffn_hidden for b in blocks[:3]] == [32, 32, 16]
    assert all(b.qk_norm and b.rope_theta == 1e6 and b.d_conv == 3
               for b in blocks)
    assert [b.slot_state_axes for b in blocks[:3]] == [(1,), (1,), (1,)]


def test_full_size_work_is_the_published_model():
    """The arithmetic of the configuration's deployment: parameters by
    part, the cut, what a slot and a position hold."""
    work = _load("work")
    cfg = full_cfg()
    assert work.conv_params(cfg) == 16_783_360
    assert work.attention_params(cfg) == 10_485_760
    assert work.dense_ffn_params(cfg) == 44_040_192
    assert work.routed_ffn_params(cfg) == 352_387_104
    assert work.held_params(cfg) == 4_667_077_376           # 9.33 GB
    whole = dict(cfg, num_hidden_layers=24,
                 layer_types=cfg["published"]["layer_types"])
    assert work.held_params(whole) == pytest.approx(8.3399e9, rel=1e-4)
    cell = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", "lfm2_8b_serve_chat128.json")))
    s = cell["server"]
    assert s["n_blocks"] == s["n_slots"] * (
        s["max_positions"] // s["block_len"]) + 1 == 5633
    assert cfg["serve_positions"] == s["max_positions"] == 2816
    assert work.page_bytes_per_position(cfg) == 6144
    assert work.state_bytes(cfg, 1) == 11 * 2 * 2048 * 2     # 90 KB a slot
    one = work.serve_flops(cfg, cell, {"prompt_tokens": [100],
                                       "output_tokens": [1]})
    assert one == pytest.approx(work.forward_flops(cfg, 100, last_only=True))


def test_configuration_keeps_every_published_width():
    """Only depth is cut: the two keys `reduced` names differ from the
    published ones (kept under `published`), as the first 14 layers of
    the published order, and every width is the catalog row's."""
    cfg = full_cfg()
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    pub = cfg["published"]
    assert (cfg["num_hidden_layers"], pub["num_hidden_layers"]) == (14, 24)
    assert cfg["layer_types"] == pub["layer_types"][:14]
    assert [i for i, k in enumerate(pub["layer_types"])
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"],
            cfg["conv_L_cache"]) == (2048, 7168, 1792, 32, 4, 32, 8,
                                     65536, 3)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
