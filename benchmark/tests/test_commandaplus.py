"""The `command-a-plus-05-2026` configuration's own yardstick files: the
plain reference and its control, `--rehearse-cpu` of its cell with the
fp8 control in the program's place, `work/` against a count of the
reference's jaxpr at the rehearsal size, and the cell's sizes against
the configuration's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops
import harness

CELL = "commandaplus_serve_mixedlen"
NAME = "command-a-plus-05-2026"
SEEDS = (2147483659, 11, 3000000019)


def test_reference_reads_its_own_greedy_tokens_at_gap_nought():
    """Greedy tokens of the reference itself have gap 0 across the
    window's edge (12 positions at the rehearsal size); an altered one
    reads above it; the fp8 control reads above bfloat16 on every seed."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    common = harness.load_module("reference", "common")
    assert cfg["sliding_window"] < 24
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


def test_fp8_control_in_the_programs_place_is_not_correct(run_cell):
    """`--rehearse-cpu` runs the cell at its rehearsal size; with the
    tokens the fp8 control puts first served in the program's place the
    run comes out `correct: false` by `served_logit_gap`."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    common = harness.load_module("reference", "common")
    seed = SEEDS[0]
    params = ref.init_params(cfg, common.key_of(common.seed_words(seed)))

    def control(server):
        real = server.generate_async

        def submit(prompt, n_tokens, **kw):
            s = real(prompt, n_tokens, **kw)
            if kw.get("temperature"):
                return s
            emit = s._emit_many

            def emit_fp8(toks, now):
                # each token is what the fp8 forward puts first after
                # the request's own history
                out = []
                for _ in toks:
                    seq = np.concatenate([prompt, s.tokens, out]).astype(int)
                    lg = ref.logits_row(params, jnp.asarray(seq), cfg, "fp8")
                    out.append(int(jnp.argmax(lg[-1])))
                emit(out, now)
            s._emit_many = emit_fp8
            return s
        server.generate_async = submit

    res, err = run_cell(CELL, hooks={"server": control}, seed=seed)
    assert res["correct"] is False, err
    assert res["compared"]["served_logit_gap"]["ok"] is False


def test_work_counts_match_the_references_jaxpr():
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", NAME)
    work = harness.load_module("work", NAME)
    T = 32                                    # past the window of 12
    params = jax.eval_shape(lambda: ref.init_params(cfg, jax.random.PRNGKey(0)))
    jaxpr = jax.make_jaxpr(lambda p, x: ref.logits_row(p, x, cfg))(
        params, jax.ShapeDtypeStruct((T,), jnp.int32))
    counted = flops.count_math_flops(jaxpr.jaxpr)
    # the reference sends every token through every held expert and
    # multiplies, of a block of queries, every key from the band's first
    # on; work/ counts the expected held share and the pairs alone
    L, W = cfg["num_hidden_layers"], cfg["sliding_window"]
    expected_held = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                     / cfg["router_num_experts"])
    masked = 2.0 * T * L * (cfg["num_experts"] - expected_held) \
        * work.expert_params(cfg)
    per_pair = cfg["num_attention_heads"] * cfg["head_dim"] * 4.0
    # one query block at this size: sliding and full layers alike
    # multiply the whole T x T square
    assert T <= ref.QUERY_BLOCK
    square = L * T * T * per_pair
    assert counted == pytest.approx(
        work.forward_flops(cfg, T) - work.attention_flops(cfg, T)
        + square + masked, rel=1e-9)
    # the banded count: three window layers, one full
    band = W * (W + 1) / 2 + (T - W) * W
    assert work.attention_flops(cfg, T) == pytest.approx(
        (3 * band + T * (T + 1) / 2) * per_pair)
    # a decode position against its cache, and the prefill's one head row
    assert work.forward_flops(cfg, T) - work.forward_flops(
        cfg, T, last_only=True) == pytest.approx(
        2.0 * cfg["hidden_size"] * cfg["vocab_size"] * (T - 1))
    one = work.serve_flops(cfg, cell, {"prompt_tokens": [T],
                                       "output_tokens": [1]})
    assert one == pytest.approx(work.forward_flops(cfg, T, last_only=True))
    two = work.serve_flops(cfg, cell, {"prompt_tokens": [T],
                                       "output_tokens": [3]})
    # two decoded positions, each against min(pos + 1, window) keys in
    # the window layers and pos + 1 in the full one
    assert two - one == pytest.approx(
        2 * (2.0 * work.token_matmul_params(cfg)
             + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
        + (3 * 2 * W + (T + 1) + (T + 2)) * per_pair)


def test_cell_sizes_are_the_configurations():
    _, cell, cfg = harness.load_cell(CELL)
    work = harness.load_module("work", NAME)
    s = cell["server"]
    assert work.held_params(cfg) == 4_733_272_064       # 9.47 GB in bf16
    assert s["n_blocks"] == s["n_slots"] * (
        s["max_positions"] // s["block_len"]) + 1
    assert s["window_blocks"] == s["n_slots"] * (
        -(-cfg["sliding_window"] // s["block_len"]) + 1) + 1
    assert cfg["serve_positions"] == s["max_positions"]
    assert cell["prompt_len"]["max"] + cell["output_len"]["max"] \
        <= s["max_positions"]
    assert cell["prompt_len"]["median"] == cfg["sliding_window"]
    need = work.gqa_paged_decode(cfg, 1)
    assert need == {"flops": 65536.0, "bytes": 4096.0}  # 16 FLOP a byte
