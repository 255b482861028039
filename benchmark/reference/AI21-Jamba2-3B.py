"""Plain reference of the `AI21-Jamba2-3B` configuration, whole: the
forward pass in float32 `jax.numpy` at `Precision.HIGHEST`, one sequence
at a time, the recurrence as a plain scan over time, attention as a full
causal softmax; no kernel, no cache, no batching.  Imports nothing of the
program.

The model, as `benchmark/configs/AI21-Jamba2-3B.json` describes it (x
[T, 2560]; no bias in a linear map, no position of any kind):

    layer i:  h = x + Mixer_i(RMSNorm(x));  y = h + MLP(RMSNorm(h))
    Mixer_i is attention where i % 14 == 7 (layers 7 and 21), a Mamba
    layer everywhere else; MLP is SwiGLU 2560 -> 8192 -> 2560 in every
    layer (`num_experts` 1: an "expert layer" is the dense MLP).

Attention: 20 query heads of 128 over ONE key head and one value head of
128, scores / sqrt(128), causal.  Mamba-1 (C = 5120 channels, N = 16
state columns, R = 160, a convolution over the last 4 inputs), token t:

    [x_t, z_t] = W_in u_t
    x_t  <- silu(b_c + sum_{k=0..3} w_c[:, k] * x_{t-3+k})   zeros before 0
    [dt_t, B_t, C_t] = W_x x_t;  RMSNorm (a learned gain) of each
    Delta_t = softplus(W_dt dt_t + b_dt)
    h_t = exp(Delta_t (x) A) * h_{t-1} + (Delta_t * x_t) (x) B_t   h_{-1} = 0
    y_t = h_t C_t + D * x_t;   out_t = W_out (y_t * silu(z_t))

with A = -exp(A_log) [C, N].  Head: the final RMSNorm, then the
embedding's own table (tied).

The weights keep the seed's values in the configuration's `param_dtype`
(bfloat16 at full size: 6.06 GB; `A_log`, `D`, `b_dt` and the norms'
gains in float32) and are widened a layer at a time inside one jitted
program a kind of layer.
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

MAMBA, ATTENTION = "mamba", "attention"
QUERY_BLOCK = 1024         # queries scored at once against their keys
DRAW_CHUNK = 1 << 25       # float32 values drawn at once (128 MB)


def sizes(cfg):
    """The widths, by the public config's own keys."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return dict(
        V=cfg["vocab_size"], D=D, H=H, Hkv=cfg["num_key_value_heads"],
        Dh=cfg.get("head_dim") or D // H, F=cfg["intermediate_size"],
        C=cfg["mamba_expand"] * D, N=cfg["mamba_d_state"],
        K=cfg["mamba_d_conv"], R=cfg["mamba_dt_rank"],
        L=cfg["num_hidden_layers"], eps=cfg["rms_norm_eps"])


def layer_kinds(cfg):
    """The family's rule: layer i is attention where
    `i % attn_layer_period == attn_layer_offset`, a Mamba layer else."""
    return [ATTENTION if i % cfg["attn_layer_period"]
            == cfg["attn_layer_offset"] else MAMBA
            for i in range(cfg["num_hidden_layers"])]


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
    """Weights from the seed.  Every matrix (the convolution's taps and
    bias with them) N(0, `initializer_range`) in `dtype` (default the
    configuration's `param_dtype`); Mamba's published initialisation of
    the recurrence, in float32: `A_log = log(1..N)` a channel, `D = 1`,
    `b_dt` the inverse softplus of a step log-uniform in [1e-3, 1e-1], so
    that the state decays on N time scales; unit gains, float32.  The
    head is the embedding: there is no other table."""
    s = sizes(cfg)
    dtype = jnp.dtype(dtype or cfg.get("param_dtype", "bfloat16"))
    std = cfg.get("initializer_range", 0.02)
    f32 = jnp.float32
    D, F, C, N, K, R = s["D"], s["F"], s["C"], s["N"], s["K"], s["R"]
    Q, KV = s["H"] * s["Dh"], s["Hkv"] * s["Dh"]

    def n(k, *shape):
        return _draw(k, shape, std, dtype)

    def layer(k, kind):
        ks = jax.random.split(k, 10)
        w = {"mixer_norm": jnp.ones((D,), f32),
             "mlp_norm": jnp.ones((D,), f32),
             "w_gate": n(ks[0], D, F), "w_up": n(ks[1], D, F),
             "w_down": n(ks[2], F, D)}
        if kind == ATTENTION:
            w.update(wq=n(ks[3], D, Q), wk=n(ks[4], D, KV),
                     wv=n(ks[5], D, KV), wo=n(ks[6], Q, D))
            return w
        step = jnp.exp(jax.random.uniform(ks[9], (C,), f32)
                       * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
        w.update(
            in_proj=n(ks[3], D, 2 * C), conv_w=n(ks[4], C, K),
            conv_b=n(ks[8], C), x_proj=n(ks[5], C, R + 2 * N),
            dt_norm=jnp.ones((R,), f32), b_norm=jnp.ones((N,), f32),
            c_norm=jnp.ones((N,), f32), dt_proj=n(ks[6], R, C),
            dt_bias=step + jnp.log(-jnp.expm1(-step)),
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=f32)), (C, N)),
            D=jnp.ones((C,), f32), out_proj=n(ks[7], C, D))
        return w

    ks = jax.random.split(key, s["L"] + 1)
    return {"embed": n(ks[0], s["V"], D),
            "layers": [layer(ks[i + 1], kind)
                       for i, kind in enumerate(layer_kinds(cfg))],
            "final_norm": jnp.ones((D,), f32)}


# ------------------------------------------------------------------ pieces
def rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _f32(w):
    return w.astype(jnp.float32)


def mlp(h, w, mode):
    return mm(jax.nn.silu(mm(h, _f32(w["w_gate"]), mode))
              * mm(h, _f32(w["w_up"]), mode), _f32(w["w_down"]), mode)


def attention(u, w, cfg, mode):
    """Attn(u) for u [T, D]: causal over the T rows, every query head
    against the one key head."""
    s = sizes(cfg)
    T, H, Hkv, Dh = u.shape[0], s["H"], s["Hkv"], s["Dh"]
    G = H // Hkv
    q = mm(u, _f32(w["wq"]), mode).reshape(T, Hkv, G, Dh)
    k = mm(u, _f32(w["wk"]), mode).reshape(T, Hkv, Dh)
    v = mm(u, _f32(w["wv"]), mode).reshape(T, Hkv, Dh)
    qh = q.transpose(1, 2, 0, 3)                          # [Hkv, G, T, Dh]
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


def mamba(u, w, cfg, mode):
    """Mamba(u) for u [T, D] from an empty state: the recurrence a token
    at a time (a plain scan over time), everything but the matrix
    products in float32 whatever the mode."""
    s = sizes(cfg)
    T, C, N, K, R = u.shape[0], s["C"], s["N"], s["K"], s["R"]
    xz = mm(u, _f32(w["in_proj"]), mode)
    x, z = xz[:, :C], xz[:, C:]
    past = jnp.concatenate([jnp.zeros((K - 1, C), jnp.float32), x], 0)
    taps = _f32(w["conv_w"])                                      # [C, K]
    x = jax.nn.silu(_f32(w["conv_b"]) + sum(
        taps[:, k] * past[k:k + T] for k in range(K)))
    dbc = mm(x, _f32(w["x_proj"]), mode)
    dt = rms_norm(dbc[:, :R], w["dt_norm"], s["eps"])
    B = rms_norm(dbc[:, R:R + N], w["b_norm"], s["eps"])
    Cm = rms_norm(dbc[:, R + N:], w["c_norm"], s["eps"])
    delta = jax.nn.softplus(mm(dt, _f32(w["dt_proj"]), mode)
                            + _f32(w["dt_bias"]))                 # [T, C]
    A = -jnp.exp(_f32(w["A_log"]))                                # [C, N]

    def step(h, t):
        d_t, x_t, b_t, c_t = t
        h = jnp.exp(d_t[:, None] * A) * h + (d_t * x_t)[:, None] * b_t[None]
        return h, jnp.sum(h * c_t[None], -1)

    _, y = jax.lax.scan(step, jnp.zeros((C, N), jnp.float32),
                        (delta, x, B, Cm))
    y = y + _f32(w["D"]) * x
    return mm(y * jax.nn.silu(z), _f32(w["out_proj"]), mode)


def block(x, w, cfg, kind, mode="f32"):
    s = sizes(cfg)
    x = x.astype(jnp.float32)
    u = rms_norm(x, w["mixer_norm"], s["eps"])
    h = x + (attention(u, w, cfg, mode) if kind == ATTENTION
             else mamba(u, w, cfg, mode))
    return h + mlp(rms_norm(h, w["mlp_norm"], s["eps"]), w, mode)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, mode):
    """One jitted program a kind of layer, so that a layer's float32
    copies are the only ones alive."""
    cfg = dict(cfg_key)
    s = sizes(cfg)
    layers = {kind: jax.jit(functools.partial(
        lambda x, w, kind: block(x, w, cfg, kind, mode), kind=kind))
        for kind in (MAMBA, ATTENTION)}
    embed = jax.jit(lambda e, ids: e[ids].astype(jnp.float32))
    head = jax.jit(lambda x, g, e: mm(
        rms_norm(x, g, s["eps"]), e.astype(jnp.float32).T, mode))
    return embed, layers, head


def _key(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def logits_row(params, x, cfg, mode="f32", rows=None):
    """x [T] int ids -> logits of one sequence by the full forward:
    [T, V], or [len(rows), V] at the positions `rows` alone."""
    embed, layers, head = _programs(_key(cfg), mode)
    h = embed(params["embed"], x)
    for kind, w in zip(layer_kinds(cfg), params["layers"]):
        h = layers[kind](h, w)
    if rows is not None:
        h = h[rows]
    return head(h, params["final_norm"], params["embed"])


def served_gap(cfg, seed, sample, mode="f32"):
    """The serving comparison.  `sample`: [(prompt ids, served ids)] of
    greedy requests.  One full forward over each prompt with its served
    tokens, float32 at HIGHEST; returns the widest gap by which a served
    token's logit lies below the reference's best at its position.

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
        # padded after the end (causal and recurrent alike: no effect
        # before it), so that every request runs the same programs
        seq = np.zeros(pad, np.int32)
        seq[:n] = np.concatenate([prompt, out])
        served = np.full(out_pad, -1, np.int32)
        served[:len(out)] = out
        # served[j] was produced at position len(prompt) - 1 + j
        rows = jnp.asarray(np.clip(len(prompt) - 1 + np.arange(out_pad),
                                   0, len(seq) - 1))
        ref = logits_row(params, jnp.asarray(seq), cfg, "f32", rows)
        low = None if mode == "f32" else logits_row(
            params, jnp.asarray(seq), cfg, mode, rows)
        widest = max(widest, float(jnp.max(
            gaps(ref, low, jnp.asarray(served)))))
    common.free(params)
    return widest
