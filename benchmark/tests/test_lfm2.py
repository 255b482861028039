"""The `LFM2-8B-A1B` configuration's own yardstick files: the plain
reference, its firmness rule and its control, `--rehearse-cpu` of its
cell with the fp8 control and with an altered token in the program's
place, `work/` against a count of the reference's jaxpr at the rehearsal
size, the cell's sizes against the configuration's, and the new expert
metric's reader on a registry that lacks its family."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import correct
import flops
import harness

CELL = "lfm2_8b_serve_chat128"
NAME = "LFM2-8B-A1B"
SEEDS = (2147483659, 11, 3000000019)


def _greedy(ref, cfg, params, seq, n):
    pad = cfg["serve_positions"]
    seq = list(seq)
    for _ in range(n):
        padded = np.zeros(pad, np.int32)
        padded[:len(seq)] = seq
        lg = ref.logits_row(params, jnp.asarray(padded), cfg)
        seq.append(int(jnp.argmax(lg[len(seq) - 1])))
    return seq


def test_reference_reads_its_own_greedy_tokens_at_gap_nought():
    """Greedy tokens of the reference itself have gap 0; an altered one
    reads above it; the fp8 control reads above bfloat16 on every seed."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    common = harness.load_module("reference", "common")
    for seed in SEEDS:
        params = ref.init_params(cfg, common.key_of(common.seed_words(seed)))
        rng = np.random.default_rng(seed)
        seq = _greedy(ref, cfg, params, rng.integers(0, cfg["vocab_size"],
                                                     12), 12)
        sample = [(np.asarray(seq[:12]), np.asarray(seq[12:]))]
        gaps, _ = ref.served_gaps(cfg, seed, sample)
        assert gaps.max() < 1e-5
        ctx = rng.integers(0, cfg["vocab_size"], 60)
        spread = [(ctx[:12], ctx[12:])]
        gaps, firm = ref.served_gaps(cfg, seed, spread, mode="fp8")
        low, _ = ref.served_gaps(cfg, seed, spread, mode="bf16")
        assert firm.shape == (4, 48)        # four routed layers
        assert gaps.max() > 1e-4 and gaps.max() > 3 * low.max()
        wrong = (np.asarray(seq[:12]), (np.asarray(seq[12:]) + 1) % 256)
        gaps, firm = ref.served_gaps(cfg, seed, [wrong])
        assert gaps[np.min(firm, 0) > ref.FIRM].max() > 1e-3


def test_too_few_firm_positions_are_not_correct(monkeypatch):
    """Under `MIN_FIRM` firmly routed positions the gap reads infinite
    and the run is not correct; at `MIN_FIRM` it is their widest gap."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    n = ref.MIN_FIRM
    gaps = np.full(2 * n, 0.01)
    gaps[-1] = 50.0                       # a loose position: never read
    firm = np.zeros((4, 2 * n))
    firm[:, :n - 1] = 1.0
    firm[0, n - 1] = 1.0                  # firm in one layer of four only
    monkeypatch.setattr(ref, "served_gaps", lambda *a, **k: (gaps, firm))
    few = ref.served_gap(cfg, 0, [])
    assert few == float("inf")
    assert not correct.judge({"served_logit_gap": (few, None)},
                             cell["limits"])[0]
    firm[:, n - 1] = 1.0
    enough = ref.served_gap(cfg, 0, [])
    assert enough == pytest.approx(0.01)
    assert correct.judge({"served_logit_gap": (enough, None)},
                         cell["limits"])[0]


def test_firmness_is_the_routers_own_margin():
    """`firmness` is the 4th largest `s + b` less the 5th over the rms of
    the router's logits, read on the float32 router of the layer's FFN
    input; where it is nought the choice is a tie."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    D, E = cfg["hidden_size"], cfg["num_experts"]
    x = jax.random.normal(jax.random.PRNGKey(0), (5, D))
    router = jax.random.normal(jax.random.PRNGKey(1), (D, E)) * 0.05
    w = {"mlp_norm": jnp.ones(D), "router": router,
         "expert_bias": jnp.zeros(E)}
    got = np.asarray(ref.firmness(x, w, cfg))
    z = np.asarray(ref.rms_norm(x, w["mlp_norm"], cfg["norm_eps"])
                   @ np.asarray(router))
    s = np.sort(1 / (1 + np.exp(-z)), -1)[:, ::-1]
    want = (s[:, 3] - s[:, 4]) / np.sqrt((z * z).mean(-1))
    np.testing.assert_allclose(got, want, rtol=1e-4)
    # a bias that ties the 4th and the 5th choice reads nought
    order = np.argsort(-(1 / (1 + np.exp(-z[0]))))
    tie = np.zeros(E, np.float32)
    sig = 1 / (1 + np.exp(-z[0]))
    tie[order[4]] = sig[order[3]] - sig[order[4]]
    w["expert_bias"] = jnp.asarray(tie)
    assert abs(float(ref.firmness(x[:1], w, cfg)[0])) < 1e-5


def test_cell_rehearses_correct_and_reports_the_expert_metrics(run_cell):
    res, err = run_cell(CELL, "--trace", "1")
    assert res["correct"] is True, err
    assert res["failed"] == 0
    assert res["compared"]["compiles_in_window"]["value"] == 0
    m = res["metrics"]
    assert m["conv_state_gb.serve"]["value"] > 0
    assert 0 <= m["conv_pad_pct.serve"]["value"] < 100
    assert 0 < m["moe_touched_pct.serve"]["value"] <= 100
    assert m["moe_rows_mean.serve"]["value"] > 0
    # jamba's own two read the same families, under jamba's names
    assert "decode_state_gb.serve" not in m
    assert "scan_pad_pct.serve" not in m
    assert "window_kv_held_pct.serve" not in m


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
    """With the tokens the fp8 control puts first served in the program's
    place the run comes out `correct: false` by `served_logit_gap`."""
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
    """The reference sends every token through every expert under a mask;
    `work/` counts the 4 a token is sent to.  The difference is exactly
    the other experts' products, and the attention square's upper half."""
    _, cell, cfg = harness.load_cell(CELL, rehearse=True)
    ref = harness.load_module("reference", NAME)
    work = harness.load_module("work", NAME)
    T = 32
    params = jax.eval_shape(lambda: ref.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
    jaxpr = jax.make_jaxpr(lambda p, x: ref.logits_row(p, x, cfg))(
        params, jax.ShapeDtypeStruct((T,), jnp.int32))
    counted = flops.count_math_flops(jaxpr.jaxpr)
    H = cfg["num_attention_heads"]
    per_pair = H * (cfg["hidden_size"] // H) * 4.0
    n_attn = sum(1 for k, _ in ref.layer_kinds(cfg) if k == "full_attention")
    n_routed = sum(1 for _, r in ref.layer_kinds(cfg) if r)
    assert (n_attn, n_routed) == (1, 4)
    idle = (cfg["num_experts"] - cfg["num_experts_per_tok"])
    assert counted == pytest.approx(
        work.forward_flops(cfg, T) - work.attention_flops(cfg, T)
        + n_attn * T * T * per_pair
        + n_routed * idle * 2.0 * T * work.expert_params(cfg), rel=1e-9)
    one = work.serve_flops(cfg, cell, {"prompt_tokens": [T],
                                       "output_tokens": [1]})
    assert one == pytest.approx(work.forward_flops(cfg, T, last_only=True))


def test_cell_sizes_are_the_configurations():
    bench, cell, cfg = harness.load_cell(CELL)
    work = harness.load_module("work", NAME)
    s = cell["server"]
    assert work.held_params(cfg) == 4_667_077_376       # 9.33 GB in bf16
    assert s["n_blocks"] == s["n_slots"] * (
        s["max_positions"] // s["block_len"]) + 1
    assert cfg["serve_positions"] == s["max_positions"]
    assert cell["prompt_len"]["max"] + cell["output_len"]["max"] \
        == s["max_positions"]
    assert cell["warmup_prompt_len"] == cell["prompt_len"]["max"]
    assert cell["rate_per_s"] == pytest.approx(
        cell["rate_share_of_knee"] * cell["knee_per_s"], rel=0.02)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert {"moe_touched_pct.serve", "conv_state_gb.serve",
            "conv_pad_pct.serve"} <= mine
    assert not mine & {"decode_state_gb.serve", "scan_pad_pct.serve",
                       "window_kv_held_pct.serve"}
    touched = next(m for m in bench["per_layer"]
                   if m["name"] == "moe_touched_pct.serve")
    assert touched["workloads"] == ["sarvam105b_serve_longdoc",
                                    "commandaplus_serve_mixedlen", CELL]
    assert touched["better"] == "lower"     # more touched, more bytes a step
    own = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in own) == ["conv_pad_pct.serve",
                                              "conv_state_gb.serve"]


def test_the_touched_share_is_quiet_on_a_parents_registry():
    """The parent's program has no `serving_moe_touched_pct`: the reader
    returns None there and raises nothing."""
    spec = harness.load_json(os.path.join(
        harness.HERE, "layer_metrics", "moe_touched_pct.serve.json"))
    reader = harness.load_module("readers", spec["reader"])
    for registry in (({}, {}), None):
        assert reader.read({"registry": registry}, **spec["args"]) is None
    bench = json.load(open(os.path.join(harness.REPO, "BENCHMARK.json")))
    assert any(w["name"] == CELL and w["chips"] == 1
               for w in bench["workloads"])
