"""The plain references against the system at a tiny size, the control
(the reference in fp8 put in the program's place), and `work/` against a
count of the reference's own jaxpr."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import correct
import flops
import harness

# every training cell that BENCHMARK.json lists
TRAIN = [w["name"] for w in harness.load_json(
    os.path.join(harness.REPO, "BENCHMARK.json"))["workloads"]
    if harness.load_cell(w["name"])[1]["kind"] == "train"]
SEEDS = (2147483659, 11, 3000000019)


def trainer(cell_name, policy):
    _, cell, cfg = harness.load_cell(cell_name, rehearse=True)
    cfg = dict(cfg, dtype_policy=policy)
    train = harness.load_module("traffic", "train")
    return train.Trainer(cell, cfg, harness.Tracer(False)), cell, cfg


@pytest.mark.parametrize("cell_name", TRAIN)
def test_system_in_float32_is_the_reference(cell_name):
    """Same weights, same batches, float32 on both sides: three steps of
    `fit` and of the reference agree to rounding, leaf by leaf.  A
    departure in either (an optimizer's form, a missed L2 term, a leaf
    not mapped) shows here at 1e-1, not 1e-5."""
    tr, _, _ = trainer(cell_name, "float32")
    tr.install(SEEDS[0])
    prog = tr.follow()
    tr.release()
    ref = tr.reference(SEEDS[0])
    for name, (value, leaf) in correct.training_numbers(prog, ref).items():
        assert value < 2e-4, (name, value, leaf)
    assert set(prog["grad_norm"]) == set(ref["grad_norm"])


@pytest.mark.parametrize("cell_name", TRAIN)
def test_control_reads_above_the_stated_precision(cell_name):
    """The reference computed in fp8 against the reference computed in
    bfloat16 (what the configuration states), both measured against
    float32: the control's loss or gradient reading is the larger on
    every seed.  The chip's readings at the cell's own size, which the
    limits are set from, are in PERF.md."""
    tr, _, _ = trainer(cell_name, "float32")
    for seed in SEEDS:
        tr.install(seed)
        tr.release()
        ref = tr.reference(seed)
        stated = correct.training_numbers(tr.reference(seed, mode="bf16"), ref)
        control = correct.training_numbers(tr.reference(seed, mode="fp8"), ref)
        keys = ("loss_step1", "grad_norm_gap")
        assert any(control[k][0] > 2 * stated[k][0] for k in keys), \
            (seed, stated, control)


def test_serving_reference_and_its_control():
    """Greedy tokens of the reference itself have gap 0; the fp8 control
    reads a gap above it on every seed."""
    _, cell, cfg = harness.load_cell("gpt2m_serve_chat", rehearse=True)
    ref = harness.load_module("reference", cell["config"])
    common = harness.load_module("reference", "common")
    for seed in SEEDS:
        params = ref.init_params(cfg, common.key_of(common.seed_words(seed)))
        rng = np.random.default_rng(seed)
        seq = list(rng.integers(0, cfg["vocab_size"], 12))
        for _ in range(20):
            lg = ref.logits_row(params, jnp.asarray(seq), cfg["n_head"])
            seq.append(int(jnp.argmax(lg[-1])))
        sample = [(np.asarray(seq[:12]), np.asarray(seq[12:]))]
        assert ref.served_gap(cfg, seed, sample) < 1e-5
        # the control reads the token fp8 puts first at each position of a
        # served sequence; greedy loops of a 2-layer toy repeat one token,
        # so read it over a sequence of distinct contexts
        ctx = rng.integers(0, cfg["vocab_size"], 60)
        spread = [(ctx[:12], ctx[12:])]
        fp8 = ref.served_gap(cfg, seed, spread, mode="fp8")
        assert fp8 > 1e-4 and fp8 > 5 * ref.served_gap(cfg, seed, spread,
                                                       mode="bf16")
        wrong = (np.asarray(seq[:12]), (np.asarray(seq[12:]) + 1) % 256)
        assert ref.served_gap(cfg, seed, [wrong]) > 1e-3


def test_work_counts_match_the_references_jaxpr():
    _, cell, cfg = harness.load_cell("gpt2m_train_t1024", rehearse=True)
    ref = harness.load_module("reference", "gpt2-medium")
    work = harness.load_module("work", "gpt2-medium")
    T = cell["seq_len"]
    params = jax.eval_shape(lambda: ref.init_params(cfg, jax.random.PRNGKey(0)))
    jaxpr = jax.make_jaxpr(lambda p, x: ref.logits_row(p, x, cfg["n_head"]))(
        params, jax.ShapeDtypeStruct((T,), jnp.int32))
    counted = flops.count_math_flops(jaxpr.jaxpr)
    # the jaxpr multiplies the whole T x T square; work/ counts the causal half
    square = cfg["n_layer"] * 2.0 * T * T * cfg["n_embd"]
    assert counted == pytest.approx(work.forward_flops(cfg, 1, T) + square,
                                    rel=1e-9)
    assert work.train_step_flops(cfg, cell) == pytest.approx(
        3 * cell["batch"] * work.forward_flops(cfg, 1, T))



def test_full_size_work_is_the_published_model():
    _, cell, cfg = harness.load_cell("gpt2m_train_t1024")
    work = harness.load_module("work", "gpt2-medium")
    assert work.matmul_params(cfg) == 353_453_056
    assert work.train_step_flops(cfg, cell) == pytest.approx(9.305e12, rel=1e-3)


def test_seeds_past_32_signed_bits():
    common = harness.load_module("reference", "common")
    a = common.key_of(common.seed_words(2**31 + 5))
    b = common.key_of(common.seed_words(5))
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    with pytest.raises(ValueError):
        common.seed_words(-1)
