"""The system under test for `sarvam-105b`: the net as a user builds it
(`zoo.LatentMoELM` -> `MultiLayerNetwork`, parameters held in bfloat16),
and the map between its parameter tree and the reference's names.
Nothing here computes a forward pass."""

from __future__ import annotations

# program layer index: 0 embedding, 1..L blocks, L+1 final norm, L+2 head
_BLOCK = ("attn_norm", "wq", "q_norm", "wkv_a", "kv_norm", "wkv_b", "wo",
          "ffn_norm")
_DENSE = ("w_gate", "w_up", "w_down")
_EXPERTS = ("router", "router_bias", "e_gate", "e_up", "e_down",
            "s_gate", "s_up", "s_down")


def build(cfg):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.latent_moe import LatentMoELM

    conf = LatentMoELM(
        cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        n_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        dense_hidden=cfg["intermediate_size"],
        expert_hidden=cfg["moe_intermediate_size"],
        n_routed=cfg["router_num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        held=(cfg["held_experts_first"], cfg["num_experts"]),
        routed_scaling=cfg["routed_scaling_factor"],
        router_bias_std=cfg["router_bias_std"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=cfg["rope_scaling"], eps=cfg["rms_norm_eps"],
        cache_len=cfg["serve_positions"], seed=0).conf()
    conf.dtype_policy = cfg["dtype_policy"]
    return MultiLayerNetwork(conf)


def to_program(ref, cfg):
    """Reference-named weights -> the program's `params` tree, leaf for
    leaf (the reference's layout is the program's: no copy is made)."""
    L = cfg["num_hidden_layers"]
    tree = {"0": {"W": ref["embed"]},
            str(L + 1): {"gamma": ref["final_norm"]},
            str(L + 2): {"W": ref["head"]}}
    for i, w in enumerate(ref["layers"]):
        names = _BLOCK + (_DENSE if "w_gate" in w else _EXPERTS)
        tree[str(i + 1)] = {n: w[n] for n in names}
    return tree


def to_reference(tree, cfg):
    L = cfg["num_hidden_layers"]
    return {"embed": tree["0"]["W"],
            "layers": [dict(tree[str(i + 1)]) for i in range(L)],
            "final_norm": tree[str(L + 1)]["gamma"],
            "head": tree[str(L + 2)]["W"]}
