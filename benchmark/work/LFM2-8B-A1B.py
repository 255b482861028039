"""Operations and bytes the algorithm of `LFM2-8B-A1B` needs, from shapes
alone.  2 FLOPs per multiply-accumulate; attention at the causal count; a
routed layer counts the `num_experts_per_tok` experts a token is sent to
and the router, never the experts it is not.  The short convolution's
three taps a channel and its gates are the vector unit's work and are
not counted against the matrix unit's peak."""

from __future__ import annotations


def _s(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:L]
    dense = cfg["num_dense_layers"]
    return dict(
        V=cfg["vocab_size"], D=D, H=H, Hkv=cfg["num_key_value_heads"],
        Dh=cfg.get("head_dim") or D // H, F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"], E=cfg["num_experts"],
        top=cfg["num_experts_per_tok"], K=cfg["conv_L_cache"], L=L,
        n_attn=sum(1 for k in kinds if k == "full_attention"),
        n_conv=sum(1 for k in kinds if k == "conv"),
        n_dense=dense, n_routed=L - dense)


def conv_matmul_params(cfg) -> int:
    """The two products of one short-convolution mixer: in and out."""
    s = _s(cfg)
    return s["D"] * 3 * s["D"] + s["D"] * s["D"]


def conv_params(cfg) -> int:
    """A short-convolution mixer whole: its products and its taps."""
    s = _s(cfg)
    return conv_matmul_params(cfg) + s["D"] * s["K"]


def attention_params(cfg) -> int:
    """q, k, v and o of one attention mixer."""
    s = _s(cfg)
    return 2 * s["D"] * s["H"] * s["Dh"] + 2 * s["D"] * s["Hkv"] * s["Dh"]


def dense_ffn_params(cfg) -> int:
    s = _s(cfg)
    return 3 * s["D"] * s["F"]


def expert_params(cfg) -> int:
    """One routed expert: 2048 -> 1792 -> 2048, gated."""
    s = _s(cfg)
    return 3 * s["D"] * s["Fe"]


def routed_ffn_params(cfg) -> int:
    """Every expert of a routed layer, its router and selection bias."""
    s = _s(cfg)
    return s["E"] * expert_params(cfg) + s["D"] * s["E"] + s["E"]


def held_params(cfg) -> int:
    """Every parameter: the tied table once, the mixers (attention with
    its two head gains), the FFNs, two gains a layer, the final gain."""
    s = _s(cfg)
    return (s["V"] * s["D"] + s["D"]
            + s["n_conv"] * conv_params(cfg)
            + s["n_attn"] * (attention_params(cfg) + 2 * s["Dh"])
            + s["n_dense"] * dense_ffn_params(cfg)
            + s["n_routed"] * routed_ffn_params(cfg)
            + s["L"] * 2 * s["D"])


def token_matmul_params(cfg) -> int:
    """Parameters one token is multiplied by in the layers: the mixers,
    the dense FFNs, and in a routed layer the router and the experts it
    is sent to."""
    s = _s(cfg)
    return (s["n_conv"] * conv_matmul_params(cfg)
            + s["n_attn"] * attention_params(cfg)
            + s["n_dense"] * dense_ffn_params(cfg)
            + s["n_routed"] * (s["top"] * expert_params(cfg)
                               + s["D"] * s["E"]))


def attention_flops(cfg, T: float) -> float:
    """Scores and weighted values of the attention layers over T
    positions: `H x 2 Dh x 2` operations a (query, key) pair of the
    causal triangle."""
    s = _s(cfg)
    return s["n_attn"] * T * (T + 1) / 2 * s["H"] * s["Dh"] * 4.0


def forward_flops(cfg, T: int, last_only: bool = False) -> float:
    """Matrix products and attention of one sequence of T positions; the
    head at every position, or at the last alone as prefill does."""
    s = _s(cfg)
    head = 2.0 * s["D"] * s["V"] * (1 if last_only else T)
    return 2.0 * token_matmul_params(cfg) * T + attention_flops(cfg, T) + head


def serve_flops(cfg, cell, serve) -> float:
    """Matrix-unit operations of every prompt and output position
    processed inside the window: a prompt's prefill (head at its last
    position), then one position a token against the cache."""
    s = _s(cfg)
    total = 0.0
    for p, o in zip(serve["prompt_tokens"], serve["output_tokens"]):
        decoded = max(o - 1, 0)
        total += forward_flops(cfg, p, last_only=True)
        total += decoded * (2.0 * token_matmul_params(cfg)
                            + 2.0 * s["D"] * s["V"])
        total += attention_flops(cfg, p + decoded) - attention_flops(cfg, p)
    return total


def state_bytes(cfg, n_slots: int, dtype_bytes: int = 2) -> int:
    """What the short-convolution layers keep for `n_slots` slots: the
    last `K - 1` rows of `v`, D wide, a layer."""
    s = _s(cfg)
    return s["n_conv"] * (s["K"] - 1) * n_slots * s["D"] * dtype_bytes


def page_bytes_per_position(cfg, dtype_bytes: int = 2) -> int:
    """What the attention layers' pages hold a position: K and V."""
    s = _s(cfg)
    return s["n_attn"] * 2 * s["Hkv"] * s["Dh"] * dtype_bytes
