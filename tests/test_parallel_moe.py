"""The parallel-block mixture-of-experts model (`zoo.ParallelMoELM`,
`nn/layers/parallel.py`, `nn/layers/moe.py`) against its plain reference
(`benchmark/reference/command-a-plus-05-2026.py`, which imports nothing
of the program), at the configuration's rehearsal size on the CPU with a
window (12) shorter than the sequences, and through the serving engine's
two kinds of pool.

Tolerances, and why each: float32 against float32 is 2e-5 on values of
order 1 (the two sides sum in other orders: a grouped product over
sorted rows against a scan over experts, blocks of queries of all key
heads against one key head at a time, one product 2 x F wide against
two experts kept apart); the reference computed in bfloat16 reads 1e-3
and more on the same numbers, so bfloat16 in float32's place fails
these.  Log-probabilities take 5 times that (the log of a softmax); the
kernel's online softmax, which sums in another order again, 2e-4.  Under
the `bf16_params` policy the program holds and multiplies in bfloat16
and is held to 0.15 on logits whose spread is 1-2.
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
NAME = "command-a-plus-05-2026"
F32_TOL = 2e-5


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


def ids(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg["vocab_size"], n)


def log_softmax(x):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(x, jnp.float32), -1))


def gap(a, b):
    return float(np.abs(np.asarray(a, np.float32)
                        - np.asarray(b, np.float32)).max())


# ------------------------------------------------------ model == reference
@pytest.mark.parametrize("layer", [0, 3], ids=["window", "full"])
def test_block_is_the_reference_in_float32(ref, model, layer):
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    kind = ref.layer_kinds(cfg)[layer]
    assert kind == ("sliding_attention" if layer < 3 else "full_attention")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 30, cfg["hidden_size"]))
    block = net.layers[layer + 1]
    assert block.window == (cfg["sliding_window"] if layer < 3 else None)
    got, _ = block.forward(net.params[str(layer + 1)], {}, x)
    want = ref.block(x[0], params["layers"][layer], cfg, kind)
    assert gap(got[0], want) < F32_TOL
    # the reference in bfloat16 is outside that tolerance: it is tight
    low = ref.block(x[0], params["layers"][layer], cfg, kind, "bf16")
    assert gap(low, want) > 5 * F32_TOL


def test_whole_model_is_the_reference_in_float32(ref, model):
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    x = ids(cfg, 40)
    got = np.log(np.asarray(net.output(x[None]))[0])
    want = log_softmax(ref.logits_row(params, jnp.asarray(x), cfg))
    assert gap(got, want) < 5 * F32_TOL               # log of a softmax


def test_whole_model_under_the_bf16_policy(ref, model):
    cfg = rehearsal_cfg(param_dtype="bfloat16", dtype_policy="bf16_params")
    net, params = build(model, ref, cfg)
    assert net.dtype.name == "bf16_params"
    leaves = jax.tree_util.tree_leaves(net.params)
    assert all(l.dtype == jnp.bfloat16 for l in leaves)
    x = ids(cfg, 40)
    probs = net.output(x[None])
    assert probs.dtype == jnp.float32            # logits stay float32
    want = log_softmax(ref.logits_row(params, jnp.asarray(x), cfg))
    assert 1e-4 < gap(np.log(np.asarray(probs)[0]), want) < 0.15


def test_rotation_on_window_layers_only(ref, model):
    """Keys of a window layer are the reference's rotated keys, pairs in
    their own two columns; a full layer's keys are the plain projection,
    whatever the position."""
    from deeplearning4j_tpu.nn.layers.parallel import rotate_interleaved
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 9, cfg["hidden_size"]))
    pos = jnp.arange(9)[None] + 5
    for layer, rotary in ((1, True), (4, False)):
        block, p = net.layers[layer], net.params[str(layer)]
        assert block.rotary is rotary
        q, k, _ = block._qkv(p, h, pos)
        plain = jnp.matmul(h, p["wk"])
        if not rotary:
            assert np.array_equal(np.asarray(k), np.asarray(plain))
            continue
        want = ref.rotate(plain[0].reshape(9, cfg["num_key_value_heads"], -1),
                          pos[0], cfg)
        assert gap(k[0], want.reshape(9, -1)) < 1e-6
        assert gap(k, plain) > 0.1
    x = jax.random.normal(jax.random.PRNGKey(3), (7, 2, 8))
    y = np.asarray(rotate_interleaved(x, jnp.arange(7), 50000.0))
    f1 = 50000.0 ** (-2 / 8)
    assert np.allclose(y[3, 1, 2], x[3, 1, 2] * np.cos(3 * f1)
                       - x[3, 1, 3] * np.sin(3 * f1), atol=1e-6)
    assert np.allclose(y[0], x[0])                    # position 0: no turn


def test_grouped_heads_are_the_repeated_heads(ref, model):
    """Query head i reads key head i // G: the program's grouped product
    is plain multi-head attention over the key heads repeated G times."""
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    block, p = net.layers[4], net.params["4"]         # full: no window
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 17, cfg["hidden_size"]))
    pos = jnp.arange(17)[None]
    q, k, v = block._qkv(p, h, pos)
    from deeplearning4j_tpu.nn.layers.parallel import attend_blocks
    got = attend_blocks(q, k, v, p["wo"], **block._attn())
    kr = jnp.repeat(k.reshape(17, Hkv, Dh), H // Hkv, axis=1)
    vr = jnp.repeat(v.reshape(17, Hkv, Dh), H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q[0], kr) * Dh ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((17, 17), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vr)
    assert gap(got[0], o.reshape(17, -1) @ p["wo"]) < F32_TOL


@pytest.mark.parametrize("qb,kb", [(16, 24), (8, 64), (70, 5)])
@pytest.mark.parametrize("window", [12, None], ids=["window", "full"])
def test_keys_in_chunks_are_one_softmax(qb, kb, window):
    """Queries a block and keys a chunk at a time, over the band alone
    in a window layer: whatever the two sizes, one softmax."""
    from deeplearning4j_tpu.nn.layers.parallel import (
        ParallelAttentionMoEBlock)
    kw = dict(n_in=32, n_heads=4, n_kv_heads=2, head_dim=8, window=window,
              rotary=window is not None, ffn_hidden=16, n_routed=8,
              experts_per_token=2, held_count=2, n_shared=2)
    whole = ParallelAttentionMoEBlock(**kw, query_block=128, key_block=128)
    cut = ParallelAttentionMoEBlock(**kw, query_block=qb, key_block=kb)
    p = whole.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 70, 32))
    a, rows_a = whole.forward_prefill(p, x, None)
    b, rows_b = cut.forward_prefill(p, x, None)
    assert gap(a, b) < 1e-6
    assert all(np.array_equal(np.asarray(r), np.asarray(s))
               for r, s in zip(rows_a, rows_b))


# ------------------------------------------------------------ expert layer
def test_eight_shares_add_up_to_the_uncut_layer(ref, model):
    """Each chip's routed part, with the averaged shared experts counted
    once, is the whole layer of the reference given all 16 experts."""
    whole = rehearsal_cfg(num_experts=16, held_experts_first=0)
    params = ref.init_params(whole, jax.random.PRNGKey(5))
    w = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, whole["hidden_size"]))
    h = ref.layer_norm(x, w["norm"], whole["layer_norm_eps"])
    total = 0.0
    for first in range(0, 16, 2):
        cfg = rehearsal_cfg(num_experts=2, held_experts_first=first)
        block = model.build(cfg).layers[1]
        share = dict(w, **{k: w[k][first:first + 2]
                           for k in ("e_gate", "e_up", "e_down")})
        p = model.to_program({"embed": params["embed"], "layers": [share],
                              "final_norm": params["final_norm"]},
                             dict(cfg, num_hidden_layers=1))["1"]
        mine = block._experts(p, h)[0]
        # one share is the reference's same share
        one = ref.routed(h[0], share, cfg, "f32", held=(first, 2))
        avg = ref.shared(h[0], w, cfg, "f32")
        assert gap(mine, one + avg) < F32_TOL
        total = total + (mine - avg)
    want = ref.routed(h[0], w, whole, "f32") + avg
    assert gap(total + avg, want) < F32_TOL
    # the average is of four experts kept apart: a quarter each, not a sum
    assert gap(avg * whole["num_shared_experts"], sum(
        ref.swiglu(h[0], w["s_gate"][j], w["s_up"][j], w["s_down"][j], "f32")
        for j in range(whole["num_shared_experts"]))) < F32_TOL


def test_expert_stats_are_filled(ref, model):
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, cfg["hidden_size"]))
    stats = {}
    valid = jnp.arange(16)[None] < 10
    net.layers[1]._experts(net.params["1"], x, valid, stats)
    assert stats["moe_layers"] == 1
    assert 0 <= float(stats["moe_rows"]) <= 10 * cfg["num_experts_per_tok"]


# ----------------------------------------------------------- paged serving
def _engine(net, **kw):
    from deeplearning4j_tpu.serving.engine import PagedDecodeEngine
    kw = dict(dict(n_slots=4, n_blocks=40, window_blocks=14, block_len=8,
                   max_positions=64), **kw)
    return PagedDecodeEngine(net, **kw)


def _walk_step(net, eng, kv, tables, token, pos):
    """One token through the engine's plan, probabilities out: the
    decode program's body without its sampling."""
    from deeplearning4j_tpu.serving.paged import plan_table
    h = jnp.asarray(token)[:, None]
    kv = list(kv)
    for entry in eng._plan:
        layer, lp = net.layers[entry[1]], net.params.get(str(entry[1]), {})
        if entry[0] == "block":
            h, kv[entry[2]] = layer.paged_step(
                lp, h, kv[entry[2]], plan_table(tables, entry), pos)
        else:
            h, _ = layer.forward(lp, {}, h, train=False, rng=None)
    return tuple(kv), h[:, -1]


def test_two_kinds_of_pool_in_one_manager(ref, model):
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    eng = _engine(net)
    # window 12, blocks of 8: a ring of ceil(12 / 8) + 1 = 3 blocks a slot
    assert eng.window_ring == 3 and eng.max_blocks == 8
    assert eng.pool.window == 12
    assert eng.pool.window_layers == (True, True, True, False)
    assert [a[0].shape[0] for a in eng.pool.kv] == [14, 14, 14, 40]
    assert eng.window_tables.shape == (4, 3)
    assert eng._plan[1:5] == [("block", 1, 0, 1), ("block", 2, 1, 1),
                              ("block", 3, 2, 1), ("block", 4, 3, 0)]
    assert eng._paged_prefill


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_prefill_then_paged_decode_across_the_windows_edge(
        ref, model, monkeypatch, kernel):
    """A prompt longer than the window through the paged prefill, its
    rows cut into pages (a window layer's into its ring of 3), then
    token by token through `paged_step` while the ring is written
    round: the log-probabilities at every position are the reference's
    full forward's.  Every page position outside what a slot holds is
    1e30: nothing outside the window, or past the length, is read."""
    if kernel:
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", "1")
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    eng = _engine(net)
    assert all(eng._in_place) is kernel
    seq = ids(cfg, 46, seed=3)
    P = 19                                   # 3 blocks; the window is 12
    prompts = np.zeros((1, 32), np.int32)
    prompts[0, :P] = seq[:P]
    probs, rows, _ = eng._run_prefill(prompts, np.asarray([P - 1], np.int32))
    want = log_softmax(ref.logits_row(params, jnp.asarray(seq), cfg))
    tol = 2e-4 if kernel else 5 * F32_TOL       # online softmax: another order
    assert gap(np.log(np.asarray(probs[0])), want[P - 1]) < tol
    full, ring = [3, 9, 4, 7, 11, 2], [5, 1, 8]
    tables = (np.zeros((1, eng.max_blocks), np.int32),
              np.zeros((1, eng.window_ring), np.int32))
    tables[0][0, :6] = full
    tables[1][0] = ring
    n_rows = rows[0][0].shape[1] // 8
    page_rows = (np.zeros((1, n_rows), np.int32),
                 np.zeros((1, n_rows), np.int32))
    page_rows[0][0, :3] = full[:3]
    page_rows[1][0, :3] = ring               # logical blocks 0-2 -> columns 0-2
    poisoned = tuple(tuple(jnp.full_like(a, 1e30) for a in arrays)
                     for arrays in eng.pool.kv)
    fin = eng._build_admit_finish(1, True)
    kv, _ = fin(poisoned, tuple(jnp.asarray(r) for r in page_rows), rows,
                probs, jnp.zeros((1, 2), jnp.uint32), jnp.zeros(1, jnp.int32),
                jnp.zeros(1, jnp.float32), jnp.ones(1, jnp.float32))
    # the prefill's rows past the prompt (padding) are poison too
    kv = tuple(tuple(a.at[blocks[2], P % 8:].set(1e30) for a in arrays)
               for arrays, blocks in zip(kv, (ring, ring, ring, full)))
    tables = tuple(jnp.asarray(t) for t in tables)
    step = jax.jit(lambda kv, tok, pos: _walk_step(net, eng, kv, tables,
                                                   tok, pos))
    for t in range(P, 46):                   # the ring goes round twice
        kv, p = step(kv, jnp.asarray(seq[t:t + 1]),
                     jnp.asarray([t], jnp.int32))
        assert gap(np.log(np.asarray(p[0])), want[t]) < tol, t
    # the window layers wrote the three blocks of their ring and no other
    for arrays, blocks in zip(kv[:3], (ring,) * 3):
        untouched = [b for b in range(14) if b not in blocks and b != 0]
        assert bool(jnp.all(arrays[0][jnp.asarray(untouched)] == 1e30))


def test_score_program_reads_the_references_logits(ref, model):
    """The K-position path (`paged_step_multi`), K up to block_len + 1,
    over both kinds of pool and across the window's edge."""
    from deeplearning4j_tpu.zoo.transformer import paged_score_forward
    cfg = rehearsal_cfg()
    net, params = build(model, ref, cfg)
    eng = _engine(net)
    seq = ids(cfg, 36, seed=9)
    tables = (np.zeros((4, eng.max_blocks), np.int32),
              np.zeros((4, eng.window_ring), np.int32))
    tables[0][1, :5] = [5, 2, 9, 6, 3]
    tables[1][1] = [4, 7, 2]
    tables = tuple(jnp.asarray(t) for t in tables)
    want = log_softmax(ref.logits_row(params, jnp.asarray(seq), cfg))
    kv = eng.pool.kv
    for start in range(0, 36, 9):
        toks = np.zeros((4, 9), np.int32)
        toks[1] = seq[start:start + 9]
        kv, probs = paged_score_forward(
            net, eng._plan, net.params, {}, kv, tables, jnp.asarray(toks),
            jnp.asarray([0, start, 0, 0], jnp.int32),
            jnp.asarray([0, 9, 0, 0], jnp.int32))
        assert gap(np.log(np.asarray(probs[1])),
                   want[start:start + 9]) < 5 * F32_TOL, start


def test_greedy_through_the_server_is_generate(ref, model):
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import MetricsRegistry
    from deeplearning4j_tpu.serving import GenerationServer
    from deeplearning4j_tpu.zoo.transformer import generate
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n) for n in (5, 19, 27, 3, 14, 30)]
    want = [generate(net, p[None], 20, temperature=0)[0] for p in prompts]
    saved = monitor._STATE.registry, monitor._STATE.tracer
    reg = monitor.enable(registry=MetricsRegistry(), jit_compile=False,
                         device_memory=False)
    try:
        srv = GenerationServer(net, n_slots=4, n_blocks=40, window_blocks=14,
                               block_len=8, max_positions=64,
                               max_prefill_tokens=64, min_prefill_bucket=8)
        srv.warmup(32)
        srv.start()
        streams = [srv.generate_async(p, 20) for p in prompts]
        got = [np.asarray(s.result(timeout=300)) for s in streams]
        srv.drain()
        srv.stop()
        snap = reg.snapshot()
    finally:
        monitor.disable()
        monitor._STATE.registry, monitor._STATE.tracer = saved
    for w, g in zip(want, got):
        assert np.array_equal(w, g)
    eng = srv.engine
    assert eng.pool.free_blocks == 39                  # every block is back
    assert eng.pool.window_allocator.free_blocks == 13
    rows = snap["serving_moe_rows"]["values"][0]
    load = snap["serving_moe_load_max_over_mean"]["values"][0]
    assert rows["count"] == load["count"] > 0 and rows["sum"] > 0
    held = snap["serving_window_kv_held_pct"]["values"][0]
    assert held["count"] > 0 and 0 < held["sum"] / held["count"] < 100
    read = snap["serving_decode_kv_read_pct"]["values"][0]
    assert 0 < read["sum"] / read["count"] <= 100
    pools = {v["labels"].get("pool"): v["value"]
             for v in snap["serving_pool_blocks_free"]["values"]}
    assert pools == {None: 39, "window": 13}


def test_a_window_layer_never_holds_more_than_its_ring(ref, model):
    """Over a long decode the blocks a slot holds in the window layers'
    pool never pass ceil(window / block_len) + 1, the others grow with
    the length, and all come back to their pools."""
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    eng = _engine(net)
    slot, _, _ = eng.admit(ids(cfg, 5), 56)
    seen = []
    while eng.active.any():
        eng.step()
        s = eng.slots[slot]
        if s is not None:
            seen.append((len(s.blocks), len(s.window_blocks)))
            assert eng.window_held_pct is not None
    assert max(w for _, w in seen) == 3 == eng.window_ring
    assert max(b for b, _ in seen) == 8                # 60 positions
    assert seen[0] == (1, 1)                           # both grow from 1
    assert eng.pool.free_blocks == 39
    assert eng.pool.window_allocator.free_blocks == 13
    assert not eng.window_tables.any() and not eng.block_tables.any()
    # the counters: at 60 positions a ring of 3 blocks holds 24 of them
    eng2 = _engine(net)
    slot, _, _ = eng2.admit(ids(cfg, 30), 8)
    assert len(eng2.slots[slot].window_blocks) == 3
    assert len(eng2.slots[slot].blocks) == 4
    assert eng2._window_held() == pytest.approx(100.0 * 24 / 31)
    pct, positions = eng2._kv_read()
    # gather path: every table entry of every layer
    assert pct == 100.0
    assert positions == 4 * 8 * (3 * 3 + 8)


def test_admission_is_all_or_nothing_over_both_pools(ref, model):
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    # 4 usable ring blocks: one 3-block prompt fits, a second does not
    eng = _engine(net, window_blocks=5)
    reqs = [dict(prompt_ids=ids(cfg, 20, seed=n), n_tokens=4)
            for n in (1, 2)]
    assert eng.can_admit(20, 4)
    out = eng.admit_many(reqs)
    assert len(out) == 1
    # the refused request took nothing from either pool
    assert eng.pool.used_blocks == 3
    assert eng.pool.window_allocator.used_blocks == 3
    assert not eng.can_admit(20, 4) and eng.can_admit(8, 4)
    while eng.active.any():
        eng.step()
    assert eng.pool.used_blocks == 0
    assert eng.pool.window_allocator.used_blocks == 0
    assert len(eng.admit_many(reqs[1:])) == 1
    with pytest.raises(ValueError, match="window layers' pool"):
        _engine(net, window_blocks=3).check_budget(20, 4)


def test_growth_under_pressure_preempts_over_the_window_pool(ref, model):
    """Two slots that both need a third ring block while one is free:
    the lower-progress one is requeued, nothing deadlocks or leaks."""
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    eng = _engine(net, window_blocks=6)                # 5 usable
    for n in (1, 2):
        assert eng.admit(ids(cfg, 14, seed=n), 12) is not None
    assert eng.pool.window_allocator.used_blocks == 4
    while eng.active.any():
        eng.step()
    assert [p["slot"] for p in eng.drain_preempted()] == [1]
    assert eng.pool.window_allocator.used_blocks == 0
    assert eng.pool.used_blocks == 0


def test_what_cannot_take_two_pools_refuses_loudly(ref, model):
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    with pytest.raises(NotImplementedError, match="radix prefix cache"):
        _engine(net, prefix_cache="radix")
    eng = _engine(net)
    with pytest.raises(NotImplementedError, match="registered prefix"):
        eng.register_prefix(ids(cfg, 8))
    slot, _, _ = eng.admit(ids(cfg, 6), 4)
    with pytest.raises(NotImplementedError, match="handoff wire"):
        eng.export_handoff(slot)
    with pytest.raises(NotImplementedError, match="handoff wire"):
        eng.adopt_handoff({}, np.zeros((4, 2, 1, 8, 2, 128), np.float32))
    with pytest.raises(ValueError, match="block_len \\+ 1"):
        _engine(net, speculative=10)
    _engine(net, speculative=9)                        # block_len + 1: fine
    from deeplearning4j_tpu.serving.engine import PagedDecodeEngine
    with pytest.raises(ValueError, match="max_positions"):
        PagedDecodeEngine(net, n_slots=2, n_blocks=8, block_len=8)
    from deeplearning4j_tpu.zoo.transformer import TransformerLM
    gpt = TransformerLM(64, d_model=16, n_layers=1, n_heads=2, max_len=32,
                        seed=3).init()
    with pytest.raises(ValueError, match="no window layer"):
        PagedDecodeEngine(gpt, n_slots=2, n_blocks=8, block_len=4,
                          window_blocks=8)


def test_speculation_through_the_ring_is_the_greedy_stream(ref, model):
    """Speculative decoding (k <= block_len + 1) over both kinds of
    pool emits `generate()`'s greedy tokens."""
    from deeplearning4j_tpu.zoo.transformer import generate
    cfg = rehearsal_cfg()
    net, _ = build(model, ref, cfg)
    prompt = np.tile(ids(cfg, 6, seed=4), 3)           # repetitive: drafts
    want = generate(net, prompt[None], 30, temperature=0)[0]
    eng = _engine(net, speculative=4)
    slot, first, _ = eng.admit(prompt, 30)
    got = [first]
    while eng.active.any():
        emitted, _ = eng.step()
        got.extend(emitted.get(slot, []))
    assert np.array_equal(want, np.asarray(got))


def test_zoo_builder_ties_the_head_and_names_its_layers():
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo import ParallelMoELM
    net = ParallelMoELM(64, window=8, cache_len=32).init()
    assert isinstance(net, MultiLayerNetwork)
    assert net.params["0"]["W"] is net.params[str(len(net.layers) - 1)]["W"]
    assert [l.window for l in net.layers[1:5]] == [8, 8, 8, None]
    assert not net.conf.input_preprocessors
    with pytest.raises(ValueError, match="layer_types"):
        ParallelMoELM(64, layer_types=("chunked_attention",))
    out = net.rnn_time_step(np.zeros((1, 3), np.int32))
    assert out.shape == (1, 3, 64)


def test_serving_names_neither_the_model_nor_the_layer():
    import re
    serving = os.path.join(ROOT, "deeplearning4j_tpu", "serving")
    for name in os.listdir(serving):
        if name.endswith(".py"):
            text = open(os.path.join(serving, name)).read()
            assert not re.search(
                r"ParallelAttentionMoEBlock|ParallelMoELM|parallel_moe|"
                r"command.a|cohere", text, re.I), name


def test_full_size_work_is_the_published_models_share():
    """The table of ISSUE 33: parameters by part, this chip's share."""
    work = _load("work")
    cfg = full_cfg()
    assert work.attention_params(cfg) == 142_606_336
    assert work.expert_params(cfg) == 50_331_648
    assert work.layer_params(cfg) == 1_149_763_584
    assert work.layer_params(cfg, 128) == 6_786_908_160
    assert work.held_params(cfg) == 4_733_272_064        # 9.47 GB in bf16
    assert 2 * work.token_matmul_params(cfg) == pytest.approx(3.158e9, rel=1e-3)
    assert work.gqa_paged_decode(cfg, 1) == {"flops": 65536.0, "bytes": 4096.0}
    # an 8,192-token prompt: the full layer's triangle, three bands
    full = 8192 * 8193 / 2
    band = 4096 * 4097 / 2 + 4096 * 4096
    assert work.attention_flops(cfg, 8192) == pytest.approx(
        (full + 3 * band) * 128 * 128 * 4)
    cell = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", "commandaplus_serve_mixedlen.json")))
    s = cell["server"]
    assert s["n_blocks"] == 32 * (s["max_positions"] // s["block_len"]) + 1
    assert s["window_blocks"] == 32 * (
        -(-cfg["sliding_window"] // s["block_len"]) + 1) + 1
    assert cfg["serve_positions"] == s["max_positions"] == 9216


def test_configuration_keeps_every_published_width():
    cfg = full_cfg()
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside this checkout")
    rows = [json.loads(line) for line in open(path) if line.strip()]
    row = next(r for r in rows if r["name"] == NAME)
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["router_num_experts"] == row["config"]["num_experts"]
    assert cfg["source"] == row["source_url"]
