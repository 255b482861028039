"""The `sarvam-105b` configuration's own yardstick files: the plain
reference and its control, `work/` against a count of the reference's
jaxpr at the rehearsal size, and the full-size counts against the
published model's arithmetic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops
import harness

CELL = "sarvam105b_serve_longdoc"
SEEDS = (2147483659, 11, 3000000019)


def test_reference_reads_its_own_greedy_tokens_at_gap_nought():
    """Greedy tokens of the reference itself have gap 0; an altered one
    reads above it; the fp8 control reads above bfloat16 on every seed."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    common = harness.load_module("reference", "common")
    for seed in SEEDS:
        params = ref.init_params(cfg, common.key_of(common.seed_words(seed)))
        rng = np.random.default_rng(seed)
        seq = list(rng.integers(0, cfg["vocab_size"], 12))
        for _ in range(12):
            lg = ref.logits_row(params, jnp.asarray(seq), cfg)
            seq.append(int(jnp.argmax(lg[-1])))
        sample = [(np.asarray(seq[:12]), np.asarray(seq[12:]))]
        assert ref.served_gap(cfg, seed, sample) < 1e-5
        ctx = rng.integers(0, cfg["vocab_size"], 60)
        spread = [(ctx[:12], ctx[12:])]
        fp8 = ref.served_gap(cfg, seed, spread, mode="fp8")
        assert fp8 > 1e-4 and fp8 > 3 * ref.served_gap(cfg, seed, spread,
                                                       mode="bf16")
        wrong = (np.asarray(seq[:12]), (np.asarray(seq[12:]) + 1) % 256)
        assert ref.served_gap(cfg, seed, [wrong]) > 1e-3


def test_work_counts_match_the_references_jaxpr():
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", "sarvam-105b")
    work = harness.load_module("work", "sarvam-105b")
    T = 32
    params = jax.eval_shape(lambda: ref.init_params(cfg, jax.random.PRNGKey(0)))
    jaxpr = jax.make_jaxpr(lambda p, x: ref.logits_row(p, x, cfg))(
        params, jax.ShapeDtypeStruct((T,), jnp.int32))
    counted = flops.count_math_flops(jaxpr.jaxpr)
    # the reference multiplies the whole T x T square and sends every
    # token through every held expert; work/ counts the causal half and,
    # of a token's experts, the expected share that is held here
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    expected_held = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                     / cfg["router_num_experts"])
    masked = 2.0 * T * n_moe * (cfg["num_experts"] - expected_held) \
        * work.expert_params(cfg)
    half = cfg["num_hidden_layers"] * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) * 2.0 * T * T / 2
    assert counted == pytest.approx(
        work.forward_flops(cfg, T) + masked + half, rel=1e-9)
    # a decode position against p cached rows, and the prefill's one head row
    assert work.forward_flops(cfg, T) - work.forward_flops(
        cfg, T, last_only=True) == pytest.approx(
        2.0 * cfg["hidden_size"] * cfg["vocab_size"] * (T - 1))
    one = work.serve_flops(cfg, cell, {"prompt_tokens": [T],
                                       "output_tokens": [1]})
    assert one == pytest.approx(work.forward_flops(cfg, T, last_only=True))


def test_full_size_work_is_the_published_models_share():
    """The table of ISSUE 29: parameters by part, this chip's share."""
    _, cell, cfg = harness.load_cell(CELL)
    work = harness.load_module("work", "sarvam-105b")
    assert work.attention_params(cfg) == 94_633_984
    assert work.dense_layer_params(cfg) == 295_960_576
    assert work.expert_params(cfg) == 25_165_824
    assert work.expert_layer_params(cfg, 128) == 3_341_549_568
    assert work.expert_layer_params(cfg) == 925_630_464
    assert work.held_params(cfg) == 4_535_353_344       # 9.07 GB in bf16
    need = work.mla_decode(cfg, 1)
    assert need == {"flops": 2.0 * 64 * 1088, "bytes": 1152.0}
    assert need["flops"] / need["bytes"] == pytest.approx(120.9, abs=0.1)
    s = cell["server"]
    assert s["n_blocks"] * s["block_len"] == 278_528 == 32 * s["max_positions"]
    assert cfg["serve_positions"] == s["max_positions"] == 8704


def test_configuration_keeps_every_published_width():
    import json
    import os
    _, _, cfg = harness.load_cell(CELL)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            if os.path.exists(
                "/opt/skills/guides/model-configs/architectures.jsonl") \
            else open(os.devnull) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next((r for r in rows if r["name"] == "sarvam-105b"), None)
    if row is None:
        pytest.skip("no catalog beside this checkout")
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["router_num_experts"] == row["config"]["num_experts"]
