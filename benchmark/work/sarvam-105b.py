"""Operations and bytes the algorithm of `sarvam-105b` needs, from shapes
alone, for this chip's share: the experts it holds, its slice of the
vocabulary, the layers it runs.  2 FLOPs per multiply-accumulate;
attention at the causal count."""

from __future__ import annotations


def _s(cfg):
    return dict(
        V=cfg["vocab_size"], D=cfg["hidden_size"],
        H=cfg["num_attention_heads"], R=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], I=cfg["intermediate_size"],
        F=cfg["moe_intermediate_size"], E=cfg["num_experts"],
        Er=cfg["router_num_experts"], top=cfg["num_experts_per_tok"],
        L=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        shared=cfg["num_shared_experts"])


def attention_params(cfg) -> int:
    """q, kv_a, kv_b and o of one layer."""
    s = _s(cfg)
    return (s["D"] * s["H"] * (s["dn"] + s["dr"]) + s["D"] * (s["R"] + s["dr"])
            + s["R"] * s["H"] * (s["dn"] + s["dv"]) + s["H"] * s["dv"] * s["D"])


def expert_params(cfg) -> int:
    s = _s(cfg)
    return 3 * s["D"] * s["F"]


def dense_layer_params(cfg) -> int:
    s = _s(cfg)
    return attention_params(cfg) + 3 * s["D"] * s["I"]


def expert_layer_params(cfg, experts=None) -> int:
    """Attention, the shared expert, the router and `experts` routed
    experts (default: those held here)."""
    s = _s(cfg)
    n = s["E"] if experts is None else experts
    return (attention_params(cfg) + s["shared"] * expert_params(cfg)
            + s["D"] * s["Er"] + n * expert_params(cfg))


def held_params(cfg) -> int:
    """Matrix parameters this chip holds: embedding, head, the dense
    layers and the expert layers with the experts held."""
    s = _s(cfg)
    return (2 * s["V"] * s["D"] + s["dense"] * dense_layer_params(cfg)
            + (s["L"] - s["dense"]) * expert_layer_params(cfg))


def token_matmul_params(cfg) -> float:
    """Parameters one token is multiplied by in the layers, on this chip
    and in expectation: attention and the dense layer whole, the shared
    expert, the router, and of its `top` routed experts the share that
    is held here (held / router width)."""
    s = _s(cfg)
    routed = s["top"] * s["E"] / s["Er"] * expert_params(cfg)
    moe = (attention_params(cfg) + s["shared"] * expert_params(cfg)
           + s["D"] * s["Er"] + routed)
    return s["dense"] * dense_layer_params(cfg) + (s["L"] - s["dense"]) * moe


def forward_flops(cfg, T: int, last_only: bool = False) -> float:
    """Forward pass of one sequence of T positions: the matrices, the
    expanded causal attention (keys `dn + dr` wide, values `dv`), and
    the head at every position, or at the last alone as prefill does."""
    s = _s(cfg)
    layers = 2.0 * token_matmul_params(cfg) * T
    attn = s["L"] * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * 2.0 * T * T / 2
    head = 2.0 * s["D"] * s["V"] * (1 if last_only else T)
    return layers + attn + head


def serve_flops(cfg, cell, serve) -> float:
    """Operations of every prompt and output position processed inside
    the window, the held experts' share only: a prompt's prefill (head
    at its last position), then one position a token against the cache
    in the absorbed form (`H x (R + dr + R) x 2` a cached position)."""
    s = _s(cfg)
    absorbed = s["H"] * (2 * s["R"] + s["dr"]) * 2.0
    total = 0.0
    for p, o in zip(serve["prompt_tokens"], serve["output_tokens"]):
        total += forward_flops(cfg, p, last_only=True)
        decoded = max(o - 1, 0)
        total += decoded * (2.0 * token_matmul_params(cfg)
                            + 2.0 * s["D"] * s["V"])
        # position j of the answer attends over p + j cached rows
        total += s["L"] * absorbed * (decoded * p + decoded * (decoded + 1) / 2)
    return total


def mla_decode(cfg, positions: float) -> dict:
    """`dl4tpu_mla_paged_decode` over `positions` cached positions (summed
    over slots, dispatches and layers): the least the absorbed algorithm
    needs: each cached row (latent and rotated key, bfloat16) read once,
    `H x ((R + dr) + R) x 2` operations a position (scores against the
    row, probabilities times the latent)."""
    s = _s(cfg)
    return {"flops": positions * s["H"] * (2 * s["R"] + s["dr"]) * 2.0,
            "bytes": positions * (s["R"] + s["dr"]) * 2.0}
