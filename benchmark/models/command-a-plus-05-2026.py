"""The system under test for `command-a-plus-05-2026`: the net as a user
builds it (`zoo.ParallelMoELM` -> `MultiLayerNetwork`, parameters held
in bfloat16), and the map between its parameter tree and the
reference's names.  Nothing here computes a forward pass."""

from __future__ import annotations

# program layer index: 0 embedding, 1..L blocks, L+1 final norm, L+2 head
_SAME = ("norm", "wq", "wk", "wv", "wo", "router", "e_gate", "e_up", "e_down")


def build(cfg):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.parallel_moe import ParallelMoELM

    conf = ParallelMoELM(
        cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], rope_theta=float(cfg["rope_theta"]),
        expert_hidden=cfg["intermediate_size"],
        n_routed=cfg["router_num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        held=(cfg["held_experts_first"], cfg["num_experts"]),
        n_shared=cfg["num_shared_experts"], eps=cfg["layer_norm_eps"],
        logit_scale=float(cfg["logit_scale"]),
        cache_len=cfg["serve_positions"], seed=0).conf()
    conf.dtype_policy = cfg["dtype_policy"]
    return MultiLayerNetwork(conf)


def _side_by_side(w):
    """The reference's shared experts [n, D, F] -> the program's one
    product [D, n*F]: expert j's F columns at j*F."""
    n, D, F = w.shape
    return w.transpose(1, 0, 2).reshape(D, n * F)


def to_program(ref, cfg):
    """Reference-named weights -> the program's `params` tree.  The
    routed experts, attention and the router keep the reference's
    layout; the four shared experts are laid side by side; the tied
    table is given to the embedding and to the head alike."""
    L = cfg["num_hidden_layers"]
    tree = {"0": {"W": ref["embed"]},
            str(L + 1): {"gamma": ref["final_norm"]},
            str(L + 2): {"W": ref["embed"]}}
    for i, w in enumerate(ref["layers"]):
        n, F, D = w["s_down"].shape
        tree[str(i + 1)] = dict(
            {k: w[k] for k in _SAME},
            s_gate=_side_by_side(w["s_gate"]), s_up=_side_by_side(w["s_up"]),
            s_down=w["s_down"].reshape(n * F, D))
    return tree


def to_reference(tree, cfg):
    L, n = cfg["num_hidden_layers"], cfg["num_shared_experts"]

    def apart(w):
        D = w.shape[0]
        return w.reshape(D, n, -1).transpose(1, 0, 2)

    layers = []
    for i in range(L):
        w = tree[str(i + 1)]
        layers.append(dict(
            {k: w[k] for k in _SAME}, s_gate=apart(w["s_gate"]),
            s_up=apart(w["s_up"]),
            s_down=w["s_down"].reshape(n, -1, w["s_down"].shape[-1])))
    return {"embed": tree["0"]["W"], "layers": layers,
            "final_norm": tree[str(L + 1)]["gamma"]}
