"""The system under test for `AI21-Jamba2-3B`: the net as a user builds
it (`zoo.HybridStateSpaceLM` -> `MultiLayerNetwork`, parameters held in
bfloat16), and the map between its parameter tree and the reference's
names.  Nothing here computes a forward pass."""

from __future__ import annotations

# program layer index: 0 embedding, 1..L blocks, L+1 final norm, L+2 head.
# The program keeps the state columns on sublanes and the channels on the
# lanes (`A_log [N, C]`, the convolution's taps `[K, C]`); the reference
# keeps the equations' own `[C, N]` and `[C, K]`
_TRANSPOSED = ("A_log", "conv_w")


def build(cfg):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.hybrid_statespace import HybridStateSpaceLM

    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    conf = HybridStateSpaceLM(
        cfg["vocab_size"], d_model=D, n_layers=cfg["num_hidden_layers"],
        attn_period=cfg["attn_layer_period"],
        attn_offset=cfg["attn_layer_offset"], n_heads=H,
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or D // H,
        mlp_hidden=cfg["intermediate_size"], d_state=cfg["mamba_d_state"],
        d_conv=cfg["mamba_d_conv"], expand=cfg["mamba_expand"],
        dt_rank=cfg["mamba_dt_rank"], eps=cfg["rms_norm_eps"],
        cache_len=cfg["serve_positions"], seed=0).conf()
    conf.dtype_policy = cfg["dtype_policy"]
    return MultiLayerNetwork(conf)


def _layer(w):
    return {k: (v.T if k in _TRANSPOSED else v) for k, v in w.items()}


def to_program(ref, cfg):
    """Reference-named weights -> the program's `params` tree: a layer's
    leaves under the reference's own names, two of them transposed; the
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
