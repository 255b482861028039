"""The `AI21-Jamba2-3B` configuration's own yardstick files: the plain
reference and its control, `--rehearse-cpu` of its cell with the fp8
control and with an altered token in the program's place, `work/` against
a count of the reference's jaxpr at the rehearsal size, the cell's sizes
against the configuration's, and every per-layer metric's reader on a
registry that lacks the families this configuration's program adds."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flops
import harness

CELL = "jamba2_3b_serve_chat"
NAME = "AI21-Jamba2-3B"
SEEDS = (2147483659, 11, 3000000019)


def test_reference_reads_its_own_greedy_tokens_at_gap_nought():
    """Greedy tokens of the reference itself have gap 0; an altered one
    reads above it; the fp8 control reads above bfloat16 on every seed."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    common = harness.load_module("reference", "common")
    pad = cfg["serve_positions"]
    for seed in SEEDS:
        params = ref.init_params(cfg, common.key_of(common.seed_words(seed)))
        rng = np.random.default_rng(seed)
        seq = list(rng.integers(0, cfg["vocab_size"], 12))
        for _ in range(12):
            padded = np.zeros(pad, np.int32)
            padded[:len(seq)] = seq
            lg = ref.logits_row(params, jnp.asarray(padded), cfg)
            seq.append(int(jnp.argmax(lg[len(seq) - 1])))
        sample = [(np.asarray(seq[:12]), np.asarray(seq[12:]))]
        assert ref.served_gap(cfg, seed, sample) < 1e-5
        ctx = rng.integers(0, cfg["vocab_size"], 60)
        spread = [(ctx[:12], ctx[12:])]
        fp8 = ref.served_gap(cfg, seed, spread, mode="fp8")
        assert fp8 > 1e-4 and fp8 > 3 * ref.served_gap(cfg, seed, spread,
                                                       mode="bf16")
        wrong = (np.asarray(seq[:12]), (np.asarray(seq[12:]) + 1) % 256)
        assert ref.served_gap(cfg, seed, [wrong]) > 1e-3


def test_cell_rehearses_correct_and_reports_the_two_new_metrics(run_cell):
    res, err = run_cell(CELL, "--trace", "1")
    assert res["correct"] is True, err
    assert res["failed"] == 0
    assert res["compared"]["compiles_in_window"]["value"] == 0
    m = res["metrics"]
    assert m["decode_state_gb.serve"]["value"] > 0
    assert 0 <= m["scan_pad_pct.serve"]["value"] < 100
    assert "window_kv_held_pct.serve" not in m and "moe_rows_mean.serve" not in m


def _in_the_programs_place(ref, cfg, params, choose):
    """A hook that serves, for every greedy request, the token `choose`
    picks from the reference's logits after the request's own history."""
    pad = cfg["serve_positions"]

    def control(server):
        real = server.generate_async

        def submit(prompt, n_tokens, **kw):
            s = real(prompt, n_tokens, **kw)
            if kw.get("temperature"):
                return s
            emit = s._emit_many

            def emit_other(toks, now):
                out = []
                for _ in toks:
                    seq = np.concatenate([prompt, s.tokens, out]).astype(int)
                    padded = np.zeros(pad, np.int32)
                    padded[:len(seq)] = seq
                    out.append(choose(params, jnp.asarray(padded), len(seq)))
                emit(out, now)
            s._emit_many = emit_other
            return s
        server.generate_async = submit
    return control


def test_fp8_control_in_the_programs_place_is_not_correct(run_cell):
    """`--rehearse-cpu` runs the cell at its rehearsal size; with the
    tokens the fp8 control puts first served in the program's place the
    run comes out `correct: false` by `served_logit_gap`."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    common = harness.load_module("reference", "common")
    seed = SEEDS[0]
    params = ref.init_params(cfg, common.key_of(common.seed_words(seed)))

    def fp8(params, seq, n):
        return int(jnp.argmax(ref.logits_row(params, seq, cfg, "fp8")[n - 1]))

    res, err = run_cell(CELL, seed=seed, hooks={
        "server": _in_the_programs_place(ref, cfg, params, fp8)})
    assert res["correct"] is False, err
    assert res["compared"]["served_logit_gap"]["ok"] is False


def test_an_altered_token_is_not_correct(run_cell):
    """The reference's own second choice served in the place of its
    first: `correct: false`."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    common = harness.load_module("reference", "common")
    seed = SEEDS[0]
    params = ref.init_params(cfg, common.key_of(common.seed_words(seed)))

    def second(params, seq, n):
        return int(jnp.argsort(ref.logits_row(params, seq, cfg)[n - 1])[-2])

    res, err = run_cell(CELL, seed=seed, hooks={
        "server": _in_the_programs_place(ref, cfg, params, second)})
    assert res["correct"] is False, err
    assert res["compared"]["served_logit_gap"]["ok"] is False


def test_work_counts_match_the_references_jaxpr():
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", NAME)
    work = harness.load_module("work", NAME)
    T = 32
    params = jax.eval_shape(lambda: ref.init_params(cfg, jax.random.PRNGKey(0)))
    jaxpr = jax.make_jaxpr(lambda p, x: ref.logits_row(p, x, cfg))(
        params, jax.ShapeDtypeStruct((T,), jnp.int32))
    counted = flops.count_math_flops(jaxpr.jaxpr)
    # the reference multiplies, of a block of queries, every key up to the
    # block's end: one block at this size, the whole T x T square; work/
    # counts the causal pairs alone.  The recurrence is no matrix product
    # and is in neither count
    H = cfg["num_attention_heads"]
    per_pair = H * (cfg["hidden_size"] // H) * 4.0
    assert T <= ref.QUERY_BLOCK
    n_attn = ref.layer_kinds(cfg).count("attention")
    assert n_attn == 2
    assert counted == pytest.approx(
        work.forward_flops(cfg, T) - work.attention_flops(cfg, T)
        + n_attn * T * T * per_pair, rel=1e-9)
    assert work.attention_flops(cfg, T) == pytest.approx(
        n_attn * T * (T + 1) / 2 * per_pair)
    one = work.serve_flops(cfg, cell, {"prompt_tokens": [T],
                                       "output_tokens": [1]})
    assert one == pytest.approx(work.forward_flops(cfg, T, last_only=True))
    two = work.serve_flops(cfg, cell, {"prompt_tokens": [T],
                                       "output_tokens": [3]})
    assert two - one == pytest.approx(
        2 * (2.0 * work.token_matmul_params(cfg)
             + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
        + n_attn * ((T + 1) + (T + 2)) * per_pair)


def test_cell_sizes_are_the_configurations():
    bench, cell, cfg = harness.load_cell(CELL)
    work = harness.load_module("work", NAME)
    s = cell["server"]
    assert work.held_params(cfg) == 3_029_337_472       # 6.06 GB in bf16
    assert s["n_blocks"] == s["n_slots"] * (
        s["max_positions"] // s["block_len"]) + 1
    assert cfg["serve_positions"] == s["max_positions"]
    assert cell["prompt_len"]["max"] + cell["output_len"]["max"] \
        <= s["max_positions"]
    assert cell["warmup_prompt_len"] == cell["prompt_len"]["max"]
    assert cell["rate_per_s"] == pytest.approx(
        cell["rate_share_of_knee"] * cell["knee_per_s"], rel=0.02)
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in new) == ["decode_state_gb.serve",
                                              "scan_pad_pct.serve"]


def test_every_reader_of_every_cell_is_quiet_on_a_parents_registry():
    """The traced runs of the accepted cells are made with this
    benchmark's files over a program that may lack the families this
    configuration's counters add: EVERY per-layer metric of EVERY cell,
    read on a registry that has none of the program's families (and with
    no trace, no peaks, no units), returns None or a number and raises
    nothing."""
    bench = json.load(open(os.path.join(harness.REPO, "BENCHMARK.json")))
    empty = {"cell": {}, "cfg": {}, "work": None, "chips": 1, "peaks": None,
             "trace": None, "window_s": 3.0, "units": 0, "traced_units": 0,
             "etl_ms": None, "compiles_in_window": 0, "cache_requests": 0,
             "cache_hits": 0, "serve": {"prompt_tokens": [],
                                        "output_tokens": []},
             "registry": ({}, {})}
    for w in bench["workloads"]:
        for m in harness.cell_metrics(bench, w["name"], "per_layer"):
            spec = harness.load_json(os.path.join(
                harness.HERE, "layer_metrics", f"{m['name']}.json"))
            reader = harness.load_module("readers", spec["reader"])
            for registry in (({}, {}), None):
                value = reader.read(dict(empty, registry=registry),
                                    **spec.get("args", {}))
                assert value is None or isinstance(value, (int, float)), (
                    w["name"], m["name"], value)
    for name in ("decode_state_gb.serve", "scan_pad_pct.serve"):
        spec = harness.load_json(os.path.join(
            harness.HERE, "layer_metrics", f"{name}.json"))
        reader = harness.load_module("readers", spec["reader"])
        assert reader.read(empty, **spec["args"]) is None
