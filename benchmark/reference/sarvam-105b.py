"""Plain reference of the `sarvam-105b` configuration, as cut to one
chip's share of a four-chip deployment: the forward pass in float32
`jax.numpy` at `Precision.HIGHEST`, one sequence at a time, no cache, no
kernels, attention in its expanded form only, every token through every
held expert under a mask (nothing is sorted).  Imports nothing of the
program.

The block, as `benchmark/configs/sarvam-105b.json` describes it:
pre-RMSNorm (learned gain, eps 1e-6), no bias anywhere; latent attention
(`q` 64 heads of 128 | 64, `kv_a` -> 512 | 64, RMSNorm over the 512,
`kv_b` -> 64 heads of 128 | 128, an RMSNorm over each query head's 192
columns, DeepSeek-V2's YaRN rotation on the 64-wide parts); SwiGLU of
width 16,384 in layer 0; in the others a sigmoid router over 128 outputs
with a selection bias, 8 a token, gates normalised over the chosen and
scaled by 2.5, the experts this chip holds (`held_experts_first`,
`num_experts`) and one shared expert.  What the absent experts would add
is left out.

The weights keep the seed's values in the configuration's `param_dtype`
(bfloat16 at full size: 9 GB) and are widened one matrix at a time, one
expert at a time, so the float32 copies never exist together.
"""

from __future__ import annotations

import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402
from common import mm  # noqa: E402

QUERY_BLOCK = 512          # queries scored at once against all keys
DRAW_CHUNK = 1 << 25       # float32 values drawn at once (128 MB)


def sizes(cfg):
    """The widths, by the public config's own keys."""
    return dict(
        V=cfg["vocab_size"], D=cfg["hidden_size"],
        H=cfg["num_attention_heads"], R=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], I=cfg["intermediate_size"],
        F=cfg["moe_intermediate_size"], E=cfg["num_experts"],
        Er=cfg["router_num_experts"], first=cfg["held_experts_first"],
        top=cfg["num_experts_per_tok"], L=cfg["num_hidden_layers"],
        dense=cfg["first_k_dense_replace"], eps=cfg["rms_norm_eps"],
        scaling=cfg["routed_scaling_factor"])


# ----------------------------------------------------------------- weights
def _draw(key, shape, std, dtype):
    """std * N(0, 1) in float32, rounded to `dtype`, drawn a slab of the
    leading axis at a time so that no float32 copy of a 3 GB leaf lives."""
    n = int(np.prod(shape))
    lead = shape[0]
    parts = max(1, min(lead, -(-n // DRAW_CHUNK)))
    while lead % parts:
        parts += 1
    if parts == 1:
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    slab = (lead // parts,) + tuple(shape[1:])
    out = jax.lax.map(
        lambda k: (std * jax.random.normal(k, slab, jnp.float32)).astype(dtype),
        jax.random.split(key, parts))
    return out.reshape(shape)


def init_params(cfg, key, dtype=None):
    """Weights from the seed: N(0, `initializer_range`) for every matrix,
    unit gains, the router's selection bias N(0, `router_bias_std`) in
    float32 (it chooses and does not weigh; drawn so that it changes
    some choices).  `dtype` defaults to the configuration's
    `param_dtype`."""
    s = sizes(cfg)
    dtype = jnp.dtype(dtype or cfg.get("param_dtype", "bfloat16"))
    std = cfg.get("initializer_range", 0.02)
    D, H, R, dn, dr, dv = s["D"], s["H"], s["R"], s["dn"], s["dr"], s["dv"]

    def n(k, *shape):
        return _draw(k, shape, std, dtype)

    def layer(k, i):
        ks = jax.random.split(k, 12)
        p = {"attn_norm": jnp.ones((D,), dtype),
             "wq": n(ks[0], D, H * (dn + dr)),
             "q_norm": jnp.ones((dn + dr,), dtype),
             "wkv_a": n(ks[1], D, R + dr),
             "kv_norm": jnp.ones((R,), dtype),
             "wkv_b": n(ks[2], R, H * (dn + dv)),
             "wo": n(ks[3], H * dv, D),
             "ffn_norm": jnp.ones((D,), dtype)}
        if i < s["dense"]:
            p.update(w_gate=n(ks[4], D, s["I"]), w_up=n(ks[5], D, s["I"]),
                     w_down=n(ks[6], s["I"], D))
        else:
            E, F = s["E"], s["F"]
            p.update(router=n(ks[4], D, s["Er"]),
                     router_bias=cfg.get("router_bias_std", 0.1)
                     * jax.random.normal(ks[5], (s["Er"],), jnp.float32),
                     e_gate=n(ks[6], E, D, F), e_up=n(ks[7], E, D, F),
                     e_down=n(ks[8], E, F, D),
                     s_gate=n(ks[9], D, F), s_up=n(ks[10], D, F),
                     s_down=n(ks[11], F, D))
        return p

    ks = jax.random.split(key, s["L"] + 2)
    return {"embed": n(ks[0], s["V"], D),
            "layers": [layer(ks[i + 2], i) for i in range(s["L"])],
            "final_norm": jnp.ones((D,), dtype),
            "head": n(ks[1], D, s["V"])}


# ------------------------------------------------------------------ pieces
def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def yarn_inv_freq(cfg):
    """`deepseek_yarn` as DeepSeek-V2's modelling code defines it: the
    frequencies `f_i = theta^(-2i/d)` kept where a dimension turns more
    than `beta_fast` times over the original positions, divided by
    `factor` where it turns fewer than `beta_slow` times, and a linear
    ramp between the two correction dimensions."""
    d = cfg["qk_rope_head_dim"]
    rs = cfg["rope_scaling"]
    base = float(cfg["rope_theta"])
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    f = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    return f / rs["factor"] * ramp + f * (1.0 - ramp)


def yarn_mscale(cfg, which):
    rs = cfg["rope_scaling"]
    if rs["factor"] <= 1:
        return 1.0
    return 0.1 * rs[which] * math.log(rs["factor"]) + 1.0


def softmax_scale(cfg):
    """`q_head_dim^-0.5 * m^2`, `m` from `mscale_all_dim`."""
    m = yarn_mscale(cfg, "mscale_all_dim")
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rotate(x, positions, cfg):
    """x [T, ..., d]: the pairs (2i, 2i+1) are turned by `positions * f_i`
    and come out de-interleaved (first halves, then second halves), as in
    DeepSeek-V2's `apply_rotary_pos_emb`; queries and keys alike."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(cfg), jnp.float32)[None, :]
    scale = yarn_mscale(cfg, "mscale") / yarn_mscale(cfg, "mscale_all_dim")
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def swiglu(h, wg, wu, wd, mode):
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731
    return mm(jax.nn.silu(mm(h, f32(wg), mode)) * mm(h, f32(wu), mode),
              f32(wd), mode)


def attention(x, w, cfg, mode):
    """Expanded latent attention on x [T, D], causal over the T rows."""
    s = sizes(cfg)
    T = x.shape[0]
    H, R, dn, dr, dv = s["H"], s["R"], s["dn"], s["dr"], s["dv"]
    pos = jnp.arange(T)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = rms_norm(x, w["attn_norm"], s["eps"])
    q = mm(h, f32(w["wq"]), mode).reshape(T, H, dn + dr)
    q = rms_norm(q, w["q_norm"], s["eps"])
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], pos, cfg)], -1)
    kv = mm(h, f32(w["wkv_a"]), mode)
    c = rms_norm(kv[:, :R], w["kv_norm"], s["eps"])
    k_pe = rotate(kv[:, R:], pos, cfg)                        # [T, dr]
    kvb = mm(c, f32(w["wkv_b"]), mode).reshape(T, H, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe[:, None], (T, H, dr))], -1)
    v = kvb[..., dn:]
    qh, kh, vh = (a.transpose(1, 0, 2) for a in (q, k, v))     # [H, T, .]
    scale = softmax_scale(cfg)
    out = []
    for q0 in range(0, T, QUERY_BLOCK):
        q1 = min(T, q0 + QUERY_BLOCK)
        sc = mm(qh[:, q0:q1], kh[:, :q1].transpose(0, 2, 1), mode) * scale
        keep = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), -1)
        out.append(mm(p, vh[:, :q1], mode))
    o = jnp.concatenate(out, 1).transpose(1, 0, 2).reshape(T, H * dv)
    return x + mm(o, f32(w["wo"]), mode)


def route(h, w, cfg, mode):
    """-> gates [T, router width]: `routed_scaling_factor * s / sum(s)`
    over the chosen experts, nought elsewhere.  The bias chooses only."""
    s = sizes(cfg)
    score = jax.nn.sigmoid(mm(h, w["router"].astype(jnp.float32), mode))
    _, chosen = jax.lax.top_k(score + w["router_bias"], s["top"])
    picked = jnp.take_along_axis(score, chosen, -1)
    g = s["scaling"] * picked / jnp.sum(picked, -1, keepdims=True)
    return jnp.zeros_like(score).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(g)


def feed_forward(x, w, cfg, mode, held=None):
    """`held` (first, count) overrides the configuration's share: the
    tests add the four shares of a layer up."""
    s = sizes(cfg)
    h = rms_norm(x, w["ffn_norm"], s["eps"])
    if "w_gate" in w:
        return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mode)
    first, count = held or (s["first"], s["E"])
    gates = route(h, w, cfg, mode)[:, first:first + count]    # [T, E]

    def one(y, ew):
        g, wg, wu, wd = ew
        return y + g[:, None] * swiglu(h, wg, wu, wd, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (gates.T, w["e_gate"], w["e_up"], w["e_down"]))
    return x + y + swiglu(h, w["s_gate"], w["s_up"], w["s_down"], mode)


def block(x, w, cfg, mode="f32", held=None):
    return feed_forward(attention(x, w, cfg, mode), w, cfg, mode, held)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, mode):
    """One jitted program a kind of layer, so that a layer's float32
    copies are the only ones alive."""
    cfg = dict(cfg_key[0])
    cfg["rope_scaling"] = dict(cfg_key[1])
    s = sizes(cfg)
    layer = jax.jit(lambda x, w: block(x, w, cfg, mode))
    embed = jax.jit(lambda e, ids: e[ids].astype(jnp.float32))
    head = jax.jit(lambda x, g, w: mm(rms_norm(x, g, s["eps"]),
                                      w.astype(jnp.float32), mode))
    return embed, layer, head


def _key(cfg):
    flat = tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))
    return flat, tuple(sorted(cfg["rope_scaling"].items()))


def logits_row(params, x, cfg, mode="f32", rows=None):
    """x [T] int ids -> logits of one sequence by the full forward:
    [T, V], or [len(rows), V] at the positions `rows` alone."""
    embed, layer, head = _programs(_key(cfg), mode)
    h = embed(params["embed"], x)
    for w in params["layers"]:
        h = layer(h, w)
    if rows is not None:
        h = h[rows]
    return head(h, params["final_norm"], params["head"])


def served_gap(cfg, seed, sample, mode="f32"):
    """The serving comparison.  `sample`: [(prompt ids, served ids)] of
    greedy requests.  One full forward over each prompt with its served
    tokens; returns the widest gap by which a served token's logit lies
    below the reference's best at its position.

    With `mode` other than f32 this is the CONTROL: the same forward in
    that lower precision is put in the program's place, and at each of
    the same positions the gap is read of the token it puts first."""
    pad = int(cfg["serve_positions"])
    words = common.seed_words(seed)
    params = jax.jit(lambda w: init_params(cfg, common.key_of(w)))(words)
    out_pad = -(-max(len(o) for _, o in sample) // 64) * 64

    @jax.jit
    def gaps(ref, low, served):
        best = jnp.max(ref, -1)
        tok = served if low is None else jnp.argmax(low, -1)
        got = jnp.take_along_axis(ref, jnp.clip(tok, 0)[:, None], 1)[:, 0]
        return jnp.where(served >= 0, best - got, 0.0)

    widest = 0.0
    for prompt, out in sample:
        prompt, out = np.asarray(prompt), np.asarray(out)
        n = len(prompt) + len(out)
        if n > pad:
            raise ValueError(f"request of {n} positions exceeds {pad}")
        seq = np.zeros(pad, np.int32)
        seq[:n] = np.concatenate([prompt, out])
        served = np.full(out_pad, -1, np.int32)
        served[:len(out)] = out
        # served[j] was produced at position len(prompt) - 1 + j
        rows = jnp.asarray(np.clip(len(prompt) - 1 + np.arange(out_pad),
                                   0, pad - 1))
        ref = logits_row(params, jnp.asarray(seq), cfg, "f32", rows)
        low = None if mode == "f32" else logits_row(
            params, jnp.asarray(seq), cfg, mode, rows)
        widest = max(widest, float(jnp.max(gaps(ref, low, jnp.asarray(served)))))
    common.free(params)
    return widest
