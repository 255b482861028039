"""Operations and bytes the algorithm of `AI21-Jamba2-3B` needs, from
shapes alone.  2 FLOPs per multiply-accumulate; attention at the causal
count.  The recurrence of a Mamba layer is no matrix product: its
operations are the vector unit's and are counted apart
(`selective_scan`), never added to what `step_mfu` holds against the
matrix unit's peak."""

from __future__ import annotations


def _s(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    n_attn = sum(1 for i in range(L)
                 if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"])
    return dict(
        V=cfg["vocab_size"], D=D, H=H, Hkv=cfg["num_key_value_heads"],
        Dh=cfg.get("head_dim") or D // H, F=cfg["intermediate_size"],
        C=cfg["mamba_expand"] * D, N=cfg["mamba_d_state"],
        K=cfg["mamba_d_conv"], R=cfg["mamba_dt_rank"], L=L,
        n_attn=n_attn, n_mamba=L - n_attn)


def attention_params(cfg) -> int:
    """q, k, v and o of one attention mixer."""
    s = _s(cfg)
    return 2 * s["D"] * s["H"] * s["Dh"] + 2 * s["D"] * s["Hkv"] * s["Dh"]


def mamba_matmul_params(cfg) -> int:
    """The four products of one Mamba mixer: in, x, dt and out."""
    s = _s(cfg)
    return (s["D"] * 2 * s["C"] + s["C"] * (s["R"] + 2 * s["N"])
            + s["R"] * s["C"] + s["C"] * s["D"])


def mamba_params(cfg) -> int:
    """A Mamba mixer whole: its products, the convolution's taps and
    bias, `A_log`, `D`, `b_dt`, the three gains."""
    s = _s(cfg)
    return (mamba_matmul_params(cfg) + s["C"] * (s["K"] + 1)
            + s["C"] * s["N"] + 2 * s["C"] + s["R"] + 2 * s["N"])


def mlp_params(cfg) -> int:
    s = _s(cfg)
    return 3 * s["D"] * s["F"]


def held_params(cfg) -> int:
    """Every parameter: the tied table once, the layers (two gains
    each), the final gain."""
    s = _s(cfg)
    return (s["V"] * s["D"] + s["D"]
            + s["n_mamba"] * mamba_params(cfg)
            + s["n_attn"] * attention_params(cfg)
            + s["L"] * (mlp_params(cfg) + 2 * s["D"]))


def token_matmul_params(cfg) -> int:
    """Parameters one token is multiplied by in the layers."""
    s = _s(cfg)
    return (s["n_mamba"] * mamba_matmul_params(cfg)
            + s["n_attn"] * attention_params(cfg)
            + s["L"] * mlp_params(cfg))


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
    position), then one position a token against the cache.  The
    recurrence's vector operations are `selective_scan`'s and not here."""
    s = _s(cfg)
    total = 0.0
    for p, o in zip(serve["prompt_tokens"], serve["output_tokens"]):
        decoded = max(o - 1, 0)
        total += forward_flops(cfg, p, last_only=True)
        total += decoded * (2.0 * token_matmul_params(cfg)
                            + 2.0 * s["D"] * s["V"])
        total += attention_flops(cfg, p + decoded) - attention_flops(cfg, p)
    return total


def selective_scan(cfg, positions: float) -> dict:
    """`dl4tpu_selective_scan` over `positions` (summed over rows and
    Mamba layers): the least the recurrence needs.  A position a layer,
    `N x C` state elements each take an exponential, three multiplies and
    an add for `h`, and a multiply and an add for `y`: 7 operations, on
    the VECTOR unit (`peaks.json` has no vector peak: the roofline this
    gives is the memory's).  Bytes: `x` and `Delta` read and `y` written
    in float32 (`C` each), `B` and `C` read (`N` each); `h` stays in
    VMEM."""
    s = _s(cfg)
    return {"flops": positions * 7.0 * s["N"] * s["C"],
            "bytes": positions * 4.0 * (3 * s["C"] + 2 * s["N"])}
