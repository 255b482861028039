"""The latent-attention mixture-of-experts model (`zoo.LatentMoELM`,
`nn/layers/latent.py`, `nn/layers/moe.py`) against its plain reference
(`benchmark/reference/sarvam-105b.py`, which imports nothing of the
program), at the configuration's rehearsal size on the CPU, and through
the serving engine's paged protocol.

Tolerances, and why each: float32 against float32 is 2e-5 on values of
order 1 (the two sides sum in other orders: a grouped product over
sorted rows against a scan over experts, blocks of queries against all
keys); the reference computed in bfloat16 reads 1e-4 and more on the
same numbers, so bfloat16 in float32's place fails these.  Under the
`bf16_params` policy the program holds and multiplies in bfloat16 and
is held to 0.15 on logits whose spread is 1-2.
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
F32_TOL = 2e-5


def _load(kind, name):
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
    return _load("reference", "sarvam-105b")


@pytest.fixture(scope="module")
def model():
    return _load("models", "sarvam-105b")


def rehearsal_cfg(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sarvam-105b.json")) as f:
        cfg = json.load(f)
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


def ids(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg["vocab_size"], n)


def log_softmax(x):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(x, jnp.float32), -1))


# ------------------------------------------------------ model == reference
@pytest.mark.parametrize("layer", [0, 1], ids=["dense", "experts"])
def test_block_is_the_reference_in_float32(ref, model, layer):
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg["hidden_size"]))
    block = net.layers[layer + 1]
    got, _ = block.forward(net.params[str(layer + 1)], {}, x)
    want = ref.block(x[0], params["layers"][layer], cfg)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < F32_TOL
    # the reference in bfloat16 is outside that tolerance: it is tight
    low = ref.block(x[0], params["layers"][layer], cfg, "bf16")
    assert np.abs(np.asarray(low) - np.asarray(want)).max() > 5 * F32_TOL


def test_whole_model_is_the_reference_in_float32(ref, model):
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    x = ids(cfg, 40)
    got = np.log(np.asarray(net.output(x[None]))[0])
    want = log_softmax(ref.logits_row(params, jnp.asarray(x), cfg))
    assert np.abs(got - want).max() < 5 * F32_TOL     # log of a softmax


def test_whole_model_under_the_bf16_policy(ref, model):
    cfg = rehearsal_cfg(param_dtype="bfloat16", dtype_policy="bf16_params")
    net, params = build(model, ref, cfg)
    assert net.dtype.name == "bf16_params"
    leaves = jax.tree_util.tree_leaves(net.params)
    assert sum(l.dtype == jnp.bfloat16 for l in leaves) >= len(leaves) - 2
    x = ids(cfg, 40)
    probs = net.output(x[None])
    assert probs.dtype == jnp.float32            # logits stay float32
    got = np.log(np.asarray(probs)[0])
    want = log_softmax(ref.logits_row(params, jnp.asarray(x), cfg))
    gap = np.abs(got - want).max()
    assert 1e-4 < gap < 0.15, gap


def test_yarn_table_and_scale_are_the_references(ref):
    from deeplearning4j_tpu.nn.layers import latent
    for cfg in (rehearsal_cfg(), json.load(open(os.path.join(
            ROOT, "benchmark", "configs", "sarvam-105b.json")))):
        f = latent.yarn_inv_freq(cfg["qk_rope_head_dim"],
                                 float(cfg["rope_theta"]), cfg["rope_scaling"])
        assert np.allclose(f, ref.yarn_inv_freq(cfg), rtol=1e-12)
        assert f[0] == 1.0 and np.isclose(
            f[-1] * cfg["rope_scaling"]["factor"],
            cfg["rope_theta"] ** (-(cfg["qk_rope_head_dim"] - 2)
                                  / cfg["qk_rope_head_dim"]))
    full = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       "sarvam-105b.json")))
    assert ref.softmax_scale(full) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)


def test_absorbed_attention_is_expanded_attention(ref, model):
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    block, p = net.layers[2], net.params["2"]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 20, cfg["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(20), (2, 20))
    expanded, rows = block._attend_expanded(p, h, pos)
    absorbed = block._attend_absorbed(p, h, pos, rows)
    assert np.abs(np.asarray(expanded) - np.asarray(absorbed)).max() < F32_TOL
    assert rows.shape[-1] == cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


@pytest.mark.parametrize("qb,kb", [(16, 24), (8, 64), (70, 16)])
def test_keys_in_chunks_are_one_softmax(qb, kb):
    """The expanded form takes queries a block and keys a chunk at a
    time (over 4,096 keys at once XLA's attention is 30 times slower on
    the v5e); whatever the two sizes, it is one causal softmax."""
    from deeplearning4j_tpu.nn.layers.latent import LatentAttentionBlock
    kw = dict(n_in=64, n_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, ffn="dense", ffn_hidden=32)
    whole = LatentAttentionBlock(**kw, query_block=128, key_block=128)
    cut = LatentAttentionBlock(**kw, query_block=qb, key_block=kb)
    p = whole.init_params(jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 70, 64))
    pos = jnp.broadcast_to(jnp.arange(70), (2, 70))
    a, rows_a = whole._attend_expanded(p, h, pos)
    b, rows_b = cut._attend_expanded(p, h, pos)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6
    assert np.array_equal(np.asarray(rows_a), np.asarray(rows_b))


# ------------------------------------------------------------ expert layer
def test_four_shares_add_up_to_the_uncut_layer(ref, model):
    """Each chip's routed part, with the shared expert counted once, is
    the whole layer of the reference given all 16 experts."""
    from deeplearning4j_tpu.nn.layers.latent import swiglu
    whole = rehearsal_cfg(num_experts=16, held_experts_first=0)
    params = ref.init_params(whole, jax.random.PRNGKey(5))
    w = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, whole["hidden_size"]))
    routed = 0.0
    for first in (0, 4, 8, 12):
        cfg = rehearsal_cfg(num_experts=4, held_experts_first=first)
        block = model.build(cfg).layers[2]
        share = dict(w, **{k: w[k][first:first + 4]
                           for k in ("e_gate", "e_up", "e_down")})
        h = ref.rms_norm(x, w["ffn_norm"], cfg["rms_norm_eps"])
        shared = swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
        routed = routed + (block._feed_forward(share, x) - x - shared)
        # and one share is the reference's same share
        one = ref.feed_forward(x[0], share, cfg, "f32", held=(first, 4))
        assert np.abs(np.asarray(block._feed_forward(share, x)[0])
                      - np.asarray(one)).max() < F32_TOL
    want = ref.feed_forward(x[0], w, whole, "f32")
    got = (x + shared + routed)[0]
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL


def test_all_experts_absent_leaves_the_shared_expert_alone(ref, model):
    from deeplearning4j_tpu.nn.layers.latent import swiglu
    cfg = rehearsal_cfg()            # holds experts 4-7 of 16
    net, _ = build(model, ref, cfg)
    block, p = net.layers[2], dict(net.params["2"])
    bias = np.zeros(cfg["router_num_experts"], np.float32)
    bias[[0, 1]] = 50.0              # every token chooses absent 0 and 1
    p["router_bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, cfg["hidden_size"]))
    stats = {}
    got = block._feed_forward(p, x, stats=stats)
    h = ref.rms_norm(x, p["ffn_norm"], cfg["rms_norm_eps"])
    want = x + swiglu(h, p["s_gate"], p["s_up"], p["s_down"])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6
    assert float(stats["moe_rows"]) == 0.0
    assert float(stats["moe_load_max_over_mean"]) == 0.0


def test_no_token_dropped_when_routing_piles_onto_one_expert(ref, model):
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    block, p = net.layers[2], dict(net.params["2"])
    bias = np.zeros(cfg["router_num_experts"], np.float32)
    bias[[5, 6]] = 50.0              # all 48 tokens onto held 5 and 6
    p["router_bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg["hidden_size"]))
    stats = {}
    got = block._feed_forward(p, x, stats=stats)
    w = dict(params["layers"][1], router_bias=p["router_bias"])
    for b in range(2):
        want = ref.feed_forward(x[b], w, cfg, "f32")
        assert np.abs(np.asarray(got[b]) - np.asarray(want)).max() < F32_TOL
    assert float(stats["moe_rows"]) == 2 * 48       # none dropped
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(2.0)


def test_invalid_tokens_are_routed_nowhere():
    from deeplearning4j_tpu.nn.layers import moe
    chosen = jnp.asarray([[4, 9], [5, 4], [7, 6]], jnp.int32)
    valid = jnp.asarray([True, False, True])
    order, sizes, held = moe.held_expert_groups(chosen, valid, 4, 4)
    assert sizes.tolist() == [1, 0, 1, 1]
    assert held.tolist() == [[True, False], [False, False], [True, True]]
    assert order[:3].tolist() == [0, 5, 4]          # experts 4, 6, 7


# ----------------------------------------------------------- paged serving
def _engine(net, **kw):
    from deeplearning4j_tpu.serving.engine import PagedDecodeEngine
    kw = dict(dict(n_slots=4, n_blocks=40, block_len=8, max_positions=64), **kw)
    return PagedDecodeEngine(net, **kw)


def _walk_step(net, eng, kv, tables, token, pos):
    """One token through the engine's plan, probabilities out: the
    decode program's body without its sampling."""
    h = jnp.asarray(token)[:, None]
    kv = list(kv)
    for entry in eng._plan:
        layer, lp = net.layers[entry[1]], net.params.get(str(entry[1]), {})
        if entry[0] == "block":
            h, kv[entry[2]] = layer.paged_step(lp, h, kv[entry[2]], tables,
                                               pos)
        else:
            h, _ = layer.forward(lp, {}, h, train=False, rng=None)
    return tuple(kv), h[:, -1]


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_prefill_then_paged_decode_reads_the_references_logits(
        ref, model, monkeypatch, kernel):
    """A prompt through the paged prefill, its rows cut into pages, then
    token by token through `paged_step` over the latent pool: the
    log-probabilities at every position are the reference's full
    forward's.  Logits, not tokens.  With the kernel (interpret mode)
    at widths it can tile: latent 128, block_len 8."""
    over = {}
    if kernel:
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", "1")
        over = dict(kv_lora_rank=128, num_attention_heads=8, head_dim=136)
    cfg = rehearsal_cfg(**over)
    net, params = build(model, ref, cfg)
    eng = _engine(net)
    assert all(eng._in_place) is kernel and eng._paged_prefill
    seq = ids(cfg, 30, seed=3)
    P = 19
    prompts = np.zeros((1, 32), np.int32)
    prompts[0, :P] = seq[:P]
    probs, rows, _ = eng._run_prefill(prompts,
                                      np.asarray([P - 1], np.int32))
    want = log_softmax(ref.logits_row(params, jnp.asarray(seq), cfg))
    tol = 2e-4 if kernel else 5 * F32_TOL       # online softmax: another order
    assert np.abs(np.log(np.asarray(probs[0])) - want[P - 1]).max() < tol
    blocks = [3, 9, 4, 7]
    table = np.zeros((1, eng.max_blocks), np.int32)
    table[0, :4] = blocks
    fin = eng._build_admit_finish(1, True)
    page_rows = np.zeros((1, rows[0][0].shape[1] // 8), np.int32)
    page_rows[0, :3] = blocks[:3]               # 19 positions: 3 pages
    kv, _ = fin(eng.pool.kv, jnp.asarray(page_rows), rows, probs,
                jnp.zeros((1, 2), jnp.uint32), jnp.zeros(1, jnp.int32),
                jnp.zeros(1, jnp.float32), jnp.ones(1, jnp.float32))
    for t in range(P, 30):
        kv, p = _walk_step(net, eng, kv, jnp.asarray(table),
                           seq[t:t + 1], jnp.asarray([t], jnp.int32))
        assert np.abs(np.log(np.asarray(p[0])) - want[t]).max() < tol, t


def test_score_program_reads_the_references_logits(ref, model):
    """The K-position path (`paged_step_multi`) over the latent pool."""
    from deeplearning4j_tpu.zoo.transformer import paged_score_forward
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    eng = _engine(net)
    seq = ids(cfg, 16, seed=9)
    table = np.zeros((4, eng.max_blocks), np.int32)
    table[1, :2] = [5, 2]
    toks = np.zeros((4, 16), np.int32)
    toks[1] = seq
    kv, probs = paged_score_forward(
        net, eng._plan, net.params, {}, eng.pool.kv, jnp.asarray(table),
        jnp.asarray(toks), jnp.zeros(4, jnp.int32),
        jnp.asarray([0, 16, 0, 0], jnp.int32))
    want = log_softmax(ref.logits_row(params, jnp.asarray(seq), cfg))
    assert np.abs(np.log(np.asarray(probs[1])) - want).max() < 5 * F32_TOL


def test_kernel_reads_only_what_a_slot_holds():
    from deeplearning4j_tpu.kernels.mla_paged_attention import (
        mla_paged_decode_attention, unsupported_reason)
    rng = np.random.default_rng(1)
    S, H, R, dr, bl, nb, mb = 4, 8, 128, 64, 8, 40, 8
    pool = jnp.asarray(rng.normal(size=(nb, bl, 256)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(S, H, R + dr)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, nb))[:S * mb]
                        .reshape(S, mb), jnp.int32)
    lens = jnp.asarray([0, 13, 64, 1], jnp.int32)
    got = mla_paged_decode_attention(q, pool, table, lens, latent=R,
                                     scale=0.1, interpret=True)
    view = pool[table].reshape(S, -1, 256)
    s = jnp.einsum("shc,slc->shl", q, view[..., :R + dr]) * 0.1
    keep = jnp.arange(view.shape[1])[None, None, :] < lens[:, None, None]
    p = jnp.where(keep, jax.nn.softmax(jnp.where(keep, s, -1e30), -1), 0)
    want = jnp.einsum("shl,slr->shr", p, view[..., :R])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.abs(np.asarray(got[0])).max() == 0.0     # length 0: zeros
    # poison every page the slots do not hold: nothing changes
    held = {int(b) for s in range(S)
            for b in np.asarray(table[s, :-(-int(lens[s]) // bl)])}
    poison = pool.at[jnp.asarray([b for b in range(nb) if b not in held])
                     ].set(jnp.nan)
    again = mla_paged_decode_attention(q, poison, table, lens, latent=R,
                                       scale=0.1, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(again))
    assert unsupported_reason((40, 8, 40), jnp.float32, 8, 32) is not None
    assert unsupported_reason((4352, 64, 640), jnp.bfloat16, 64, 512) is None


def test_greedy_through_the_server_is_generate(ref, model):
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import MetricsRegistry
    from deeplearning4j_tpu.serving import GenerationServer
    from deeplearning4j_tpu.zoo.transformer import generate
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n) for n in (5, 9, 17, 3, 12, 20)]
    want = [generate(net, p[None], 8, temperature=0)[0] for p in prompts]
    saved = monitor._STATE.registry, monitor._STATE.tracer
    reg = monitor.enable(registry=MetricsRegistry(), jit_compile=False,
                         device_memory=False)
    try:
        srv = GenerationServer(net, n_slots=4, n_blocks=40, block_len=8,
                               max_positions=64, max_prefill_tokens=32,
                               min_prefill_bucket=4)
        srv.warmup(16)
        srv.start()
        streams = [srv.generate_async(p, 8) for p in prompts]
        got = [np.asarray(s.result(timeout=300)) for s in streams]
        srv.drain()
        srv.stop()
        snap = reg.snapshot()
    finally:
        monitor.disable()
        monitor._STATE.registry, monitor._STATE.tracer = saved
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    rows = snap["serving_moe_rows"]["values"][0]
    load = snap["serving_moe_load_max_over_mean"]["values"][0]
    assert rows["count"] == load["count"] > 0 and rows["sum"] > 0
    assert snap["serving_latent_positions_read"]["values"][0]["value"] > 0


def test_admission_is_bounded_in_tokens(ref, model):
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    eng = _engine(net, max_prefill_tokens=32, min_prefill_bucket=4)
    reqs = [dict(prompt_ids=ids(cfg, n, seed=n), n_tokens=2)
            for n in (9, 12, 3, 16)]
    out = eng.admit_many(reqs)
    # 9, 12 pad to 16: two of them fill 2 x 16 = 32; a third row would
    # make the program 4 x 16
    assert len(out) == 2 and eng.admit_bucket == 16
    assert eng.admit_tokens == 21
    out = eng.admit_many(reqs[2:])
    assert len(out) == 2 and eng.admit_bucket == 16    # 3 and 16 -> 2 x 16
    assert eng._bucket(1) == 4 and eng._bucket(33) == 64
    with pytest.raises(ValueError, match="max_prefill_tokens"):
        eng.check_budget(40, 2)          # pads to 64 > 32: never admitted


def test_warmup_grid_skips_what_the_token_bound_forbids(ref, model):
    from deeplearning4j_tpu.serving import GenerationServer
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    srv = GenerationServer(net, n_slots=4, n_blocks=40, block_len=8,
                           max_positions=64, max_prefill_tokens=32,
                           min_prefill_bucket=8)
    seen = set()
    eng = srv.engine

    def fake(reqs):
        seen.add((len(reqs), eng._bucket(len(reqs[0]["prompt_ids"]))))
        return [(0, 0, True)] * len(reqs)

    eng.admit_many = fake
    srv.warmup(32)
    assert seen == {(1, 8), (1, 16), (1, 32), (2, 8), (2, 16), (4, 8)}


def test_a_rotary_net_needs_the_servers_budget(ref, model):
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    from deeplearning4j_tpu.serving.engine import PagedDecodeEngine
    with pytest.raises(ValueError, match="max_positions"):
        PagedDecodeEngine(net, n_slots=2, n_blocks=8, block_len=8)
    eng = _engine(net, max_positions=48)
    assert eng.max_total_tokens == 48 and eng.max_blocks == 6
    assert [a.shape for a in eng.pool.kv[0]] == [(40, 8, 128)]
    assert len(eng.pool.kv) == cfg["num_hidden_layers"]


def test_a_latent_pool_refuses_the_handoff_wire(ref, model):
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    eng = _engine(net)
    slot, _, _ = eng.admit(ids(cfg, 6), 4)
    with pytest.raises(NotImplementedError, match="no head axis"):
        eng.export_handoff(slot)
    with pytest.raises(NotImplementedError, match="no head axis"):
        eng.adopt_handoff({}, np.zeros((3, 2, 1, 8, 4, 10), np.float32))


# ------------------------------------------------ the GPT-2 path, unchanged
def _tiny_gpt():
    from deeplearning4j_tpu.zoo.transformer import TransformerLM
    return TransformerLM(64, d_model=16, n_layers=2, n_heads=2,
                         max_len=32, seed=3).init()


def test_gpt2_path_keeps_its_program_keys():
    from deeplearning4j_tpu.serving import GenerationServer
    net = _tiny_gpt()
    srv = GenerationServer(net, n_slots=4, n_blocks=24, block_len=4)
    eng = srv.engine
    assert not eng._paged_prefill and eng.max_total_tokens == 32
    assert eng.max_prefill_tokens is None and eng.min_prefill_bucket == 1
    srv.warmup(8)
    plan = (("plain", 0), ("pos", 1), ("block", 2, 0), ("block", 3, 1),
            ("plain", 4))
    keys = set(net.__dict__["_serving_jit_cache"])
    want = {("decode", g, 1, plan, None, (False, False))
            for g in (True, False)}
    want |= {("admit", k, g, 4, None) for k in (1, 2, 4)
             for g in (True, False)}
    assert keys == want
    # the (K, V) pytree of the pool and the prefill's own jit are as before
    assert [len(a) for a in eng.pool.kv] == [2, 2]
    assert eng.pool.kv[0][0].shape == (24, 4, 16)
    assert "prefill_bucketed" in net.__dict__["_transformer_gen_jit"]


def test_gpt2_warmup_grid_is_the_whole_cross_product():
    """`gpt2m_serve_chat`'s server: 32 slots, `warmup(512)` with no token
    bound and no bucket floor walks 6 widths x 10 buckets, each greedy
    and sampled; with the 12 admit-finish, 2 decode and the cell's other
    programs that is its 84."""
    from deeplearning4j_tpu.serving import GenerationServer
    from deeplearning4j_tpu.zoo.transformer import TransformerLM
    net = TransformerLM(64, d_model=16, n_layers=1, n_heads=2,
                        max_len=1024, seed=3).init()
    srv = GenerationServer(net, n_slots=32, n_blocks=2048, block_len=16)
    eng, calls = srv.engine, []

    def fake(reqs):
        calls.append((len(reqs), len(reqs[0]["prompt_ids"]),
                      any(r.get("temperature") for r in reqs)))
        return [(0, 0, True)] * len(reqs)

    eng.admit_many = fake
    srv.warmup(512)
    assert len(calls) == 120 and len(set(calls)) == 120
    assert {c[0] for c in calls} == {1, 2, 4, 8, 16, 32}
    assert {c[1] for c in calls} == {2 ** i for i in range(10)}


# ------------------------------------------------------------ dtype policy
def test_bf16_params_policy_holds_what_it_says():
    from deeplearning4j_tpu.nd import dtype
    p = dtype.policy_from_name("bf16_params")
    assert p.name == "bf16_params" and not p.is_mixed
    assert jnp.dtype(p.param_dtype) == jnp.bfloat16
    assert jnp.dtype(p.output_dtype) == jnp.float32
    tree = {"w": jnp.ones((2, 2), jnp.bfloat16), "b": jnp.ones(2, jnp.float32)}
    assert p.cast_params(tree) is tree            # nothing to cast
    # "bf16" keeps meaning float32 masters, and says so
    assert dtype.policy_from_name("bf16").name == "mixed_bf16"
    assert dtype.bf16_policy().name == "mixed_bf16"
    assert "bf16_params" in dtype.bf16_policy.__doc__


def test_reference_draws_big_leaves_in_slabs(ref):
    """A leaf of more than 2^25 values is drawn a slab at a time; the
    values are a function of the key and shape alone."""
    big = ref._draw(jax.random.PRNGKey(1), (8, 1 << 22, 2), 0.02, jnp.bfloat16)
    assert big.shape == (8, 1 << 22, 2) and big.dtype == jnp.bfloat16
    assert abs(float(jnp.std(big.astype(jnp.float32))) - 0.02) < 1e-3
    again = ref._draw(jax.random.PRNGKey(1), (8, 1 << 22, 2), 0.02,
                      jnp.bfloat16)
    assert bool(jnp.all(big == again))
