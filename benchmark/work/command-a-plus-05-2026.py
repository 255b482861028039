"""Operations and bytes the algorithm of `command-a-plus-05-2026` needs,
from shapes alone, for this chip's share: the experts it holds, its slice
of the tied vocabulary table, the layers it runs.  2 FLOPs per
multiply-accumulate; attention at the causal count, a window layer at the
banded one."""

from __future__ import annotations

SLIDING = "sliding_attention"


def _s(cfg):
    return dict(
        V=cfg["vocab_size"], D=cfg["hidden_size"],
        H=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
        Dh=cfg["head_dim"], F=cfg["intermediate_size"],
        E=cfg["num_experts"], Er=cfg["router_num_experts"],
        top=cfg["num_experts_per_tok"], ns=cfg["num_shared_experts"],
        L=cfg["num_hidden_layers"], W=cfg["sliding_window"],
        kinds=list(cfg["layer_types"])[:cfg["num_hidden_layers"]])


def attention_params(cfg) -> int:
    """q, k, v and o of one layer."""
    s = _s(cfg)
    return 2 * s["D"] * s["H"] * s["Dh"] + 2 * s["D"] * s["Hkv"] * s["Dh"]


def expert_params(cfg) -> int:
    s = _s(cfg)
    return 3 * s["D"] * s["F"]


def layer_params(cfg, experts=None) -> int:
    """Attention, the shared experts, the router and `experts` routed
    experts (default: those held here)."""
    s = _s(cfg)
    n = s["E"] if experts is None else experts
    return (attention_params(cfg) + s["D"] * s["Er"]
            + (s["ns"] + n) * expert_params(cfg))


def held_params(cfg) -> int:
    """Matrix parameters this chip holds: the tied table once and the
    layers with the experts held."""
    s = _s(cfg)
    return s["V"] * s["D"] + s["L"] * layer_params(cfg)


def token_matmul_params(cfg) -> float:
    """Parameters one token is multiplied by in the layers, on this chip
    and in expectation: attention, the shared experts and the router
    whole, and of its `top` routed experts the share that is held here
    (held / router width)."""
    s = _s(cfg)
    routed = s["top"] * s["E"] / s["Er"] * expert_params(cfg)
    return s["L"] * (attention_params(cfg) + s["D"] * s["Er"]
                     + s["ns"] * expert_params(cfg) + routed)


def _pairs(kind, W, T) -> float:
    """(query, key) pairs of one sequence of T positions: the causal
    triangle, or the band of a window layer."""
    if kind != SLIDING or T <= W:
        return T * (T + 1) / 2
    return W * (W + 1) / 2 + (T - W) * W


def attention_flops(cfg, T: int) -> float:
    """Scores and weighted values of every layer over T positions:
    `H x 2 Dh x 2` operations a (query, key) pair."""
    s = _s(cfg)
    return sum(_pairs(k, s["W"], T) for k in s["kinds"]) \
        * s["H"] * s["Dh"] * 4.0


def forward_flops(cfg, T: int, last_only: bool = False) -> float:
    """Forward pass of one sequence of T positions: the matrices,
    attention, and the head at every position, or at the last alone as
    prefill does."""
    s = _s(cfg)
    head = 2.0 * s["D"] * s["V"] * (1 if last_only else T)
    return 2.0 * token_matmul_params(cfg) * T + attention_flops(cfg, T) + head


def serve_flops(cfg, cell, serve) -> float:
    """Operations of every prompt and output position processed inside
    the window, the held experts' share only: a prompt's prefill (head
    at its last position), then one position a token against the cache
    (a window layer: the window's positions at most)."""
    s = _s(cfg)
    total = 0.0
    for p, o in zip(serve["prompt_tokens"], serve["output_tokens"]):
        decoded = max(o - 1, 0)
        total += forward_flops(cfg, p, last_only=True)
        total += decoded * (2.0 * token_matmul_params(cfg)
                            + 2.0 * s["D"] * s["V"])
        # the pairs of positions p .. p+decoded-1 against their caches
        total += attention_flops(cfg, p + decoded) - attention_flops(cfg, p)
    return total


def gqa_paged_decode(cfg, positions: float) -> dict:
    """`dl4tpu_paged_decode` over `positions` cached positions (summed
    over slots, dispatches and layers), 8 key heads under 128 query
    heads: the least the algorithm needs: each cached key row and value
    row (`Hkv x Dh`, bfloat16) read once, `H x 2 Dh x 2` operations a
    position (scores against the key, probabilities times the value)."""
    s = _s(cfg)
    return {"flops": positions * s["H"] * s["Dh"] * 4.0,
            "bytes": positions * 2 * s["Hkv"] * s["Dh"] * 2.0}
