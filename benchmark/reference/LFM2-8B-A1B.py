"""Plain reference of the `LFM2-8B-A1B` configuration, as cut to the first
stage of a two-stage pipeline: the forward pass in float32 `jax.numpy`
at `Precision.HIGHEST`, one sequence at a time, the short convolution as
three shifted products, attention as a full causal softmax, every token
through every expert under a mask (nothing is sorted); no kernel, no
cache, no batching.  Imports nothing of the program.

The model, as `benchmark/configs/LFM2-8B-A1B.json` describes it (x
[T, 2048]; no bias in a linear map):

    layer i:  h = x + Mixer_i(RMSNorm(x));  y = h + FFN_i(RMSNorm(h))
    Mixer_i is attention where layer_types[i] is "full_attention", the
    gated short convolution where it is "conv"; FFN_i is a dense SwiGLU
    2048 -> 7168 -> 2048 for i < num_dense_layers, else the routed
    experts.

RMSNorm is `g * x * rsqrt(mean(x^2) + 1e-5)`, the gain multiplying.
Short convolution (K = conv_L_cache = 3 taps a channel, no bias):

    [B_t, C_t, x_t] = W_in u_t            D -> 3D, split in that order
    v_t = B_t * x_t
    c_t = w[:, 0] v_{t-2} + w[:, 1] v_{t-1} + w[:, 2] v_t     zeros before 0
    out_t = W_out (C_t * c_t)

Attention: 32 query heads of 64 over 8 key heads (query head i reads key
head i // 4); an RMSNorm with a learned gain of 64 on each query head and
each key head; then rotate-half RoPE on all 64 columns (columns i and
i + 32 turned by `position * 1e6^(-2i/64)`); scores / 8, causal.

Experts (32 routed, 4 a token, no shared expert):

    s = sigmoid(u W_r)                    float32
    chosen = the 4 largest of s + b       b, the selection bias, only chooses
    g_e = s_e / (sum over the chosen of s + 1e-6) * routed_scaling_factor
    FFN(u) = sum over the chosen of g_e SwiGLU_e(u)    2048 -> 1792 -> 2048

Head: the final RMSNorm, then the embedding's own table (tied).

The weights keep the seed's values in the configuration's `param_dtype`
(bfloat16 at full size: 9.33 GB; the selection bias and the gains in
float32) and are widened a matrix at a time, an expert at a time, inside
one jitted program a kind of layer.

`served_gap`, the serving comparison, reads the served positions at which
this reference's own router chooses firmly in every expert layer
(`firmness` over `FIRM`): elsewhere any rounding routes a token otherwise
than float32 does, and the gap says nothing of the precision.
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402
from common import mm  # noqa: E402

CONV, ATTENTION = "conv", "full_attention"
QUERY_BLOCK = 1024         # queries scored at once against their keys
DRAW_CHUNK = 1 << 25       # float32 values drawn at once (128 MB)
# a position is read where `firmness` is over this in every expert layer
# (PERF.md section 4 gives the readings it was set from)
FIRM = 0.0075
# fewer firm positions than this and the sample says nothing: the gap
# reads as infinite, so the run is not correct
MIN_FIRM = 30


def sizes(cfg):
    """The widths, by the public config's own keys."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        V=cfg["vocab_size"], D=D, H=H, Hkv=cfg["num_key_value_heads"],
        Dh=cfg.get("head_dim") or D // H, F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"], E=cfg["num_experts"],
        top=cfg["num_experts_per_tok"], K=cfg["conv_L_cache"],
        L=cfg["num_hidden_layers"], dense=cfg["num_dense_layers"],
        eps=cfg["norm_eps"], theta=float(cfg["rope_theta"]),
        scaling=float(cfg["routed_scaling_factor"]),
        route_eps=float(cfg["route_eps"]))


def layer_kinds(cfg):
    """(mixer, routed) of each layer: the published order's first
    `num_hidden_layers` entries, experts from `num_dense_layers` on."""
    s = sizes(cfg)
    return [(kind, i >= s["dense"])
            for i, kind in enumerate(cfg["layer_types"][:s["L"]])]


# ----------------------------------------------------------------- weights
def _draw(key, shape, std, dtype):
    """std * N(0, 1) in float32, rounded to `dtype`, drawn a slab of the
    leading axis at a time so that no float32 copy of a large leaf lives."""
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
    """Weights from the seed.  Every matrix N(0, `initializer_range`) in
    `dtype` (default the configuration's `param_dtype`); the short
    convolution's taps U(+-1/sqrt(K)), PyTorch's `Conv1d` default for a
    depthwise layer of K taps, so that the two remembered rows weigh as
    much as the new one; the experts' selection bias N(0,
    `expert_bias_std`) in float32, so that choosing by `s + b` and
    weighing by `s` alone part ways; unit gains, float32.  The head is
    the embedding: there is no other table."""
    s = sizes(cfg)
    dtype = jnp.dtype(dtype or cfg.get("param_dtype", "bfloat16"))
    std = cfg.get("initializer_range", 0.02)
    f32 = jnp.float32
    D, F, Fe, E, K = s["D"], s["F"], s["Fe"], s["E"], s["K"]
    Q, KV = s["H"] * s["Dh"], s["Hkv"] * s["Dh"]

    def n(k, *shape):
        return _draw(k, shape, std, dtype)

    def layer(k, kind, routed):
        ks = jax.random.split(k, 8)
        w = {"mixer_norm": jnp.ones((D,), f32),
             "mlp_norm": jnp.ones((D,), f32)}
        if routed:
            w.update(router=n(ks[0], D, E),
                     expert_bias=cfg.get("expert_bias_std", 0.1)
                     * jax.random.normal(ks[1], (E,), f32),
                     e_gate=n(ks[2], E, D, Fe), e_up=n(ks[3], E, D, Fe),
                     e_down=n(ks[4], E, Fe, D))
        else:
            w.update(w_gate=n(ks[0], D, F), w_up=n(ks[1], D, F),
                     w_down=n(ks[2], F, D))
        if kind == ATTENTION:
            w.update(wq=n(ks[5], D, Q), wk=n(ks[6], D, KV),
                     wv=n(ks[7], D, KV), wo=n(jax.random.fold_in(k, 8), Q, D),
                     q_norm=jnp.ones((s["Dh"],), f32),
                     k_norm=jnp.ones((s["Dh"],), f32))
        else:
            bound = K ** -0.5
            w.update(in_proj=n(ks[5], D, 3 * D), out_proj=n(ks[6], D, D),
                     conv_w=jax.random.uniform(ks[7], (D, K), f32, -bound,
                                               bound).astype(dtype))
        return w

    ks = jax.random.split(key, s["L"] + 1)
    return {"embed": n(ks[0], s["V"], D),
            "layers": [layer(ks[i + 1], kind, routed)
                       for i, (kind, routed) in enumerate(layer_kinds(cfg))],
            "final_norm": jnp.ones((D,), f32)}


# ------------------------------------------------------------------ pieces
def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _f32(w):
    return w.astype(jnp.float32)


def swiglu(h, wg, wu, wd, mode):
    return mm(jax.nn.silu(mm(h, _f32(wg), mode)) * mm(h, _f32(wu), mode),
              _f32(wd), mode)


def short_conv(u, w, cfg, mode):
    """Conv(u) for u [T, D] from an empty state."""
    s = sizes(cfg)
    T, D, K = u.shape[0], s["D"], s["K"]
    bcx = mm(u, _f32(w["in_proj"]), mode)
    b, c, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    past = jnp.concatenate([jnp.zeros((K - 1, D), jnp.float32), b * x], 0)
    taps = _f32(w["conv_w"])                                      # [D, K]
    conv = sum(taps[:, k] * past[k:k + T] for k in range(K))
    return mm(c * conv, _f32(w["out_proj"]), mode)


def rope(x, cfg):
    """Rotate-half over x [T, heads, Dh] at positions 0..T-1."""
    s = sizes(cfg)
    T, half = x.shape[0], s["Dh"] // 2
    inv = s["theta"] ** (-np.arange(0, s["Dh"], 2, dtype=np.float64)
                         / s["Dh"])
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def attention(u, w, cfg, mode):
    """Attn(u) for u [T, D]: causal over the T rows."""
    s = sizes(cfg)
    T, H, Hkv, Dh = u.shape[0], s["H"], s["Hkv"], s["Dh"]
    G = H // Hkv
    q = rms_norm(mm(u, _f32(w["wq"]), mode).reshape(T, H, Dh),
                 w["q_norm"], s["eps"])
    k = rms_norm(mm(u, _f32(w["wk"]), mode).reshape(T, Hkv, Dh),
                 w["k_norm"], s["eps"])
    v = mm(u, _f32(w["wv"]), mode).reshape(T, Hkv, Dh)
    q, k = rope(q, cfg), rope(k, cfg)
    qh = q.reshape(T, Hkv, G, Dh).transpose(1, 2, 0, 3)   # [Hkv, G, T, Dh]
    kh, vh = k.transpose(1, 0, 2), v.transpose(1, 0, 2)   # [Hkv, T, Dh]
    out = []
    for q0 in range(0, T, QUERY_BLOCK):
        q1 = min(T, q0 + QUERY_BLOCK)
        sc = mm(qh[:, :, q0:q1], jnp.swapaxes(kh[:, None, :q1], -1, -2),
                mode) * Dh ** -0.5                        # [Hkv, G, q, k]
        keep = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), -1)
        out.append(mm(p, vh[:, None, :q1], mode))         # [Hkv, G, q, Dh]
    o = jnp.concatenate(out, 2).transpose(2, 0, 1, 3).reshape(T, H * Dh)
    return mm(o, _f32(w["wo"]), mode)


def route(h, w, cfg, mode):
    """-> gates [T, E]: the chosen experts' `s / (sum s + eps)` times the
    scaling, nought elsewhere; chosen by `s + b`, weighed by `s`."""
    s = sizes(cfg)
    score = jax.nn.sigmoid(mm(h, _f32(w["router"]), mode))
    _, chosen = jax.lax.top_k(score + w["expert_bias"].astype(jnp.float32),
                              s["top"])
    picked = jnp.take_along_axis(score, chosen, -1)
    g = picked / (jnp.sum(picked, -1, keepdims=True) + s["route_eps"]) \
        * s["scaling"]
    return jnp.zeros_like(score).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(g)


def experts(h, w, cfg, mode):
    """`sum over the chosen of g_e SwiGLU_e(h)`, every token through
    every expert, one expert at a time, its gate a mask."""
    gates = route(h, w, cfg, mode)                            # [T, E]

    def one(y, ew):
        g, wg, wu, wd = ew
        return y + g[:, None] * swiglu(h, wg, wu, wd, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (gates.T, w["e_gate"], w["e_up"], w["e_down"]))
    return y


def firmness(x, w, cfg):
    """How firmly the float32 router chooses at each row of x [T, D], a
    layer's input: the 4th largest `s + b` less the 5th, over the root
    mean square of the row's router logits.  -> [T].

    A rounding error in the hidden state moves a logit by a share of
    that root mean square, and a score by at most a quarter of what
    moves its logit; where the margin is within it, a precision below
    float32 sends the token to another expert than this reference does,
    and one swapped expert of a token's four moves its logits as far as
    fp8 moves them everywhere."""
    s = sizes(cfg)
    h = rms_norm(x, w["mlp_norm"], s["eps"])
    z = mm(h, _f32(w["router"]), "f32")
    top, _ = jax.lax.top_k(jax.nn.sigmoid(z)
                           + w["expert_bias"].astype(jnp.float32),
                           s["top"] + 1)
    return (top[:, -2] - top[:, -1]) * jax.lax.rsqrt(jnp.mean(z * z, -1))


def block(x, w, cfg, kind, routed, mode="f32", margins=False):
    """One layer; with `margins` (a routed layer) also its router's
    `firmness` at each row."""
    s = sizes(cfg)
    x = x.astype(jnp.float32)
    u = rms_norm(x, w["mixer_norm"], s["eps"])
    h = x + (attention(u, w, cfg, mode) if kind == ATTENTION
             else short_conv(u, w, cfg, mode))
    v = rms_norm(h, w["mlp_norm"], s["eps"])
    y = h + (experts(v, w, cfg, mode) if routed else
             swiglu(v, w["w_gate"], w["w_up"], w["w_down"], mode))
    return (y, firmness(h, w, cfg)) if margins else y


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, mode):
    """One jitted program a kind of layer, so that a layer's float32
    copies are the only ones alive."""
    cfg = dict(cfg_key[0])
    cfg["layer_types"] = list(cfg_key[1])
    s = sizes(cfg)
    layers = {(kind, routed, margins): jax.jit(functools.partial(
        lambda x, w, kind, routed, margins: block(
            x, w, cfg, kind, routed, mode, margins),
        kind=kind, routed=routed, margins=margins))
        for kind in (CONV, ATTENTION) for routed in (False, True)
        for margins in ((False, True) if routed else (False,))}
    embed = jax.jit(lambda e, ids: e[ids].astype(jnp.float32))
    head = jax.jit(lambda x, g, e: mm(
        rms_norm(x, g, s["eps"]), e.astype(jnp.float32).T, mode))
    return embed, layers, head


def _key(cfg):
    flat = tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))
    return flat, tuple(cfg["layer_types"])


def logits_row(params, x, cfg, mode="f32", rows=None, margins=False):
    """x [T] int ids -> logits of one sequence by the full forward:
    [T, V], or [len(rows), V] at the positions `rows` alone.  With
    `margins` also `firmness` of every expert layer at those positions,
    [expert layers, rows]."""
    embed, layers, head = _programs(_key(cfg), mode)
    h = embed(params["embed"], x)
    firms = []
    for (kind, routed), w in zip(layer_kinds(cfg), params["layers"]):
        if margins and routed:
            h, f = layers[(kind, routed, True)](h, w)
            firms.append(f)
        else:
            h = layers[(kind, routed, False)](h, w)
    if rows is not None:
        h = h[rows]
    out = head(h, params["final_norm"], params["embed"])
    if not margins:
        return out
    firms = jnp.stack(firms)                                  # [L, T]
    return out, firms if rows is None else firms[:, rows]


def served_gaps(cfg, seed, sample, mode="f32"):
    """-> (gaps [N], firmness [expert layers, N]) over the N served
    tokens of `sample`, request after request: the gap by which each
    served token's logit lies below the float32 reference's best at its
    position, and how firmly the float32 router chose there in each
    expert layer.  With `mode` other than f32 the gaps are the
    CONTROL's: the same forward in that lower precision is put in the
    program's place, and at each of the same positions the gap is read
    of the token it puts first."""
    pad = int(cfg["serve_positions"])
    words = common.seed_words(seed)
    params = jax.jit(lambda w: init_params(cfg, common.key_of(w)))(words)
    out_pad = -(-max(len(o) for _, o in sample) // 64) * 64

    @jax.jit
    def gaps(ref, low, served):
        best = jnp.max(ref, -1)
        tok = served if low is None else jnp.argmax(low, -1)
        got = jnp.take_along_axis(ref, jnp.clip(tok, 0)[:, None], 1)[:, 0]
        return best - got

    all_gaps, all_firm = [], []
    for prompt, out in sample:
        prompt, out = np.asarray(prompt), np.asarray(out)
        n = len(prompt) + len(out)
        if n > pad:
            raise ValueError(f"request of {n} positions exceeds {pad}")
        # padded after the end (causal: no effect before it), so that
        # every request runs the same programs
        seq = np.zeros(pad, np.int32)
        seq[:n] = np.concatenate([prompt, out])
        served = np.full(out_pad, -1, np.int32)
        served[:len(out)] = out
        # served[j] was produced at position len(prompt) - 1 + j
        rows = jnp.asarray(np.clip(len(prompt) - 1 + np.arange(out_pad),
                                   0, pad - 1))
        ref, firm = logits_row(params, jnp.asarray(seq), cfg, "f32", rows,
                               margins=True)
        low = None if mode == "f32" else logits_row(
            params, jnp.asarray(seq), cfg, mode, rows)
        all_gaps.append(np.asarray(gaps(ref, low, jnp.asarray(served)))
                        [:len(out)])
        all_firm.append(np.asarray(firm)[:, :len(out)])
    common.free(params)
    return np.concatenate(all_gaps), np.concatenate(all_firm, -1)


def served_gap(cfg, seed, sample, mode="f32"):
    """The serving comparison.  `sample`: [(prompt ids, served ids)] of
    greedy requests.  One full forward over each prompt with its served
    tokens; returns the widest of `served_gaps` over the positions where
    the float32 router chose firmly in every expert layer (`FIRM`), or
    infinity where fewer than `MIN_FIRM` positions are firm."""
    gaps, firm = served_gaps(cfg, seed, sample, mode)
    keep = np.min(firm, 0) > FIRM
    print(f"[reference] {int(keep.sum())} of {len(gaps)} served positions "
          f"are routed firmly", file=sys.stderr, flush=True)
    if keep.sum() < MIN_FIRM:
        return float("inf")
    return float(np.max(gaps[keep]))
