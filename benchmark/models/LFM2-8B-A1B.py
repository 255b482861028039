"""The system under test for `LFM2-8B-A1B`: the net as a user builds it
(`zoo.HybridStateSpaceLM` with LFM2's arguments -> `MultiLayerNetwork`,
parameters held in bfloat16), and the map between its parameter tree and
the reference's names.  Nothing here computes a forward pass."""

from __future__ import annotations

# program layer index: 0 embedding, 1..L blocks, L+1 final norm, L+2 head.
# The program keeps the short convolution's taps with the channels on the
# lanes (`[K, D]`); the reference keeps the equations' own `[D, K]`
_TRANSPOSED = ("conv_w",)
_KINDS = {"conv": "conv", "full_attention": "attention"}


def build(cfg):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.hybrid_statespace import HybridStateSpaceLM

    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    conf = HybridStateSpaceLM(
        cfg["vocab_size"], d_model=D,
        layer_types=[_KINDS[k] for k in cfg["layer_types"][:L]],
        n_heads=H, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or D // H, qk_norm=True,
        rope_theta=float(cfg["rope_theta"]),
        mlp_hidden=cfg["intermediate_size"], d_conv=cfg["conv_L_cache"],
        n_dense_layers=cfg["num_dense_layers"],
        n_routed=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        route_eps=float(cfg["route_eps"]),
        routed_scaling=float(cfg["routed_scaling_factor"]),
        eps=cfg["norm_eps"], cache_len=cfg["serve_positions"],
        seed=0).conf()
    conf.dtype_policy = cfg["dtype_policy"]
    return MultiLayerNetwork(conf)


def _layer(w):
    return {k: (v.T if k in _TRANSPOSED else v) for k, v in w.items()}


def to_program(ref, cfg):
    """Reference-named weights -> the program's `params` tree: a layer's
    leaves under the reference's own names, the taps transposed; the
    tied table is given to the embedding and to the head alike."""
    L = cfg["num_hidden_layers"]
    tree = {"0": {"W": ref["embed"]},
            str(L + 1): {"gamma": ref["final_norm"]},
            str(L + 2): {"W": ref["embed"]}}
    for i, w in enumerate(ref["layers"]):
        tree[str(i + 1)] = _layer(w)
    return tree


def to_reference(tree, cfg):
    L = cfg["num_hidden_layers"]
    return {"embed": tree["0"]["W"],
            "layers": [_layer(tree[str(i + 1)]) for i in range(L)],
            "final_norm": tree[str(L + 1)]["gamma"]}
