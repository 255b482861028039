"""Operations and bytes the algorithm of `gpt2-medium` needs, from shapes
alone.  Recomputation is never counted; attention at the causal count
(half of the T x T products).  2 FLOPs per multiply-accumulate."""

from __future__ import annotations


def _sizes(cfg):
    d = cfg["n_embd"]
    return cfg["vocab_size"], d, cfg["n_layer"], cfg["n_head"], \
        cfg.get("n_inner") or 4 * d


def matmul_params(cfg) -> int:
    """Parameters that a token is multiplied by: the blocks' matrices and
    the output head (the embedding is a gather)."""
    V, D, L, _, F = _sizes(cfg)
    return L * (4 * D * D + 2 * D * F) + D * V


def forward_flops(cfg, rows: int, T: int) -> float:
    """Forward pass of `rows` sequences of T positions."""
    _, D, L, _, _ = _sizes(cfg)
    dense = 2.0 * matmul_params(cfg) * rows * T
    # QK^T and PV: 2 * 2 * T*T*D per layer, halved by causality
    attn = L * rows * 2.0 * T * T * D
    return dense + attn


def train_step_flops(cfg, cell) -> float:
    """Forward and backward (twice the forward) of one step."""
    return 3.0 * forward_flops(cfg, cell["batch"], cell["seq_len"])


def flash_fwd(cfg, cell) -> dict:
    """Flash attention forward, all layers of one step: reads q, k, v and
    writes o once (bf16), QK^T and PV at the causal count."""
    _, D, L, _, _ = _sizes(cfg)
    B, T = cell["batch"], cell["seq_len"]
    return {"flops": L * B * 2.0 * T * T * D,
            "bytes": L * B * 4.0 * T * D * 2}


def flash_bwd(cfg, cell) -> dict:
    """Flash attention backward (dq and dkv kernels together), all layers:
    five T x T x Dh products per head where the forward has two (S is
    formed again by the algorithm itself: dS needs P), at the causal
    count; reads q, k, v, o, do and writes dq, dk, dv once (bf16)."""
    _, D, L, _, _ = _sizes(cfg)
    B, T = cell["batch"], cell["seq_len"]
    return {"flops": L * B * 5.0 * T * T * D,
            "bytes": L * B * 8.0 * T * D * 2}


def fused_adam(cfg, cell) -> dict:
    """Adam over the blocks' parameters: reads p, m, v (float32) and the
    gradient (bf16), writes p, m, v."""
    _, D, L, _, F = _sizes(cfg)
    n = L * (4 * D * D + 2 * D * F + 9 * D + F)
    return {"flops": 12.0 * n, "bytes": n * (6 * 4 + 2)}


def serve_flops(cfg, cell, serve) -> float:
    """Forward operations of every prompt and output position processed
    inside the window: each request's positions through the matrices,
    and causal attention over the length it reached."""
    _, D, L, _, _ = _sizes(cfg)
    total = 0.0
    for p, o in zip(serve["prompt_tokens"], serve["output_tokens"]):
        n = p + o
        total += 2.0 * matmul_params(cfg) * n + L * 2.0 * n * n * D
    return total
