"""The system under test for `gpt2-medium`: the net as a user builds it
(`zoo.TransformerLM` -> `MultiLayerNetwork`, `mixed_bf16`, Adam), and the
map between its parameter tree and the reference's names.  Nothing here
computes a forward pass."""

from __future__ import annotations

import jax.numpy as jnp

# program layer index: 0 embedding, 1 positions, 2..L+1 blocks, L+2 head
_BLOCK = {"ln1_g": "ln1_gamma", "ln1_b": "ln1_beta",
          "wq": "attn_Wq", "bq": "attn_bq", "wk": "attn_Wk", "bk": "attn_bk",
          "wv": "attn_Wv", "bv": "attn_bv", "wo": "attn_Wo", "bo": "attn_bo",
          "ln2_g": "ln2_gamma", "ln2_b": "ln2_beta",
          "w1": "ff_W1", "b1": "ff_b1", "w2": "ff_W2", "b2": "ff_b2"}


def build(cfg):
    from deeplearning4j_tpu.common.updaters import Adam
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.transformer import TransformerLM

    d = cfg["n_embd"]
    conf = TransformerLM(
        cfg["vocab_size"], d_model=d, n_layers=cfg["n_layer"],
        n_heads=cfg["n_head"], ff_multiplier=(cfg.get("n_inner") or 4 * d) // d,
        max_len=cfg["n_positions"], seed=0).conf()
    conf.dtype_policy = cfg["dtype_policy"]
    for layer in conf.layers:
        if layer.updater is not None:
            layer.updater = Adam(float(cfg["learning_rate"]))
    return MultiLayerNetwork(conf)


def to_program(ref, cfg):
    """Reference-named weights -> the program's `params` tree."""
    L = cfg["n_layer"]
    tree = {"0": {"W": ref["wte"], "b": ref["wte_b"]},
            str(L + 2): {"W": ref["head_w"], "b": ref["head_b"]}}
    for i in range(L):
        tree[str(i + 2)] = {pk: ref["blocks"][rk][i]
                            for rk, pk in _BLOCK.items()}
    return tree


def to_reference(tree, cfg):
    """The program's tree (params, or one slot of the updater's state
    picked out by the caller) -> the reference's names, blocks stacked."""
    L = cfg["n_layer"]
    blocks = {rk: jnp.stack([tree[str(i + 2)][pk] for i in range(L)])
              for rk, pk in _BLOCK.items()}
    return {"wte": tree["0"]["W"], "wte_b": tree["0"]["b"], "blocks": blocks,
            "head_w": tree[str(L + 2)]["W"], "head_b": tree[str(L + 2)]["b"]}


def first_gradient(upd_state, cfg):
    """The first gradient as the optimizer got it, from its state after
    one step: Adam's m is (1 - beta1) * g then."""
    m = {lk: {pk: s["m"] for pk, s in lv.items()}
         for lk, lv in upd_state.items()}
    ref = to_reference(m, cfg)
    import jax
    return jax.tree_util.tree_map(lambda x: x / (1 - 0.9), ref)
