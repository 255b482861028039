"""Plain reference of the `command-a-plus-05-2026` configuration, as cut
to one chip's share of an eight-chip deployment: the forward pass in
float32 `jax.numpy` at `Precision.HIGHEST`, one sequence at a time, no
cache, no kernels, no batching, every token through every held expert
under a mask (nothing is sorted).  Imports nothing of the program.

The block, as `benchmark/configs/command-a-plus-05-2026.json` describes
it (x [T, 4096]; no bias anywhere):

    h = LN(x)                      one LayerNorm a layer, a gain, no bias
    y = x + Attn(h) + MoE(h)       the parallel block

Attention: 128 query heads of 128 over 8 key heads (query head i reads
key head i // 16), no query or key norm, scores / sqrt(128).  In a
`sliding_attention` layer queries and keys are rotated GPT-J style (pairs
(2i, 2i+1) of a head turned by `position * 50000^(-2i/128)`, back in their
own two columns) and query t sees keys j with `0 <= t - j < 4096`; a
`full_attention` layer has no rotation and no position of any kind and is
causal.  Experts: `s = sigmoid(h Wr)` over 128 outputs, the 8 largest
chosen, gates `s / sum of the chosen s`; the experts this chip holds
(`held_experts_first`, `num_experts`) weigh in, what the absent ones would
add is left out; plus the AVERAGE of the four shared experts.  Head: the
final LayerNorm, then the embedding's own table (tied), times
`logit_scale`.

Attention is computed a key head at a time (its 16 query heads against
the one key head, which is what repeating the key head 16 times gives)
and a block of queries at a time, so that 9,216 positions of 128 heads
fit beside the weights.  The weights keep the seed's values in the
configuration's `param_dtype` (bfloat16 at full size: 9.5 GB) and are
widened one matrix at a time, one expert at a time.

`served_gap`, the serving comparison, reads the served positions at which
this reference's own router chooses firmly in every layer (`firmness`
over `FIRM`): elsewhere any rounding routes a token otherwise than
float32 does, and the gap says nothing of the precision.
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

QUERY_BLOCK = 1024         # queries of one key head's group scored at once
DRAW_CHUNK = 1 << 25       # float32 values drawn at once (128 MB)
SLIDING, FULL = "sliding_attention", "full_attention"
# a position is read where `firmness` is over this in every layer: on the
# chip, of 8,412 served positions on six seeds, the 22 at which the
# program's gap passed 0.08 all had a margin under 0.012 in some layer
# (PERF.md section 4, PR 33)
FIRM = 0.03


def sizes(cfg):
    """The widths, by the public config's own keys."""
    return dict(
        V=cfg["vocab_size"], D=cfg["hidden_size"],
        H=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
        Dh=cfg["head_dim"], F=cfg["intermediate_size"],
        E=cfg["num_experts"], Er=cfg["router_num_experts"],
        first=cfg["held_experts_first"], top=cfg["num_experts_per_tok"],
        ns=cfg["num_shared_experts"], L=cfg["num_hidden_layers"],
        eps=cfg["layer_norm_eps"], window=cfg["sliding_window"],
        theta=float(cfg["rope_theta"]), logit_scale=cfg["logit_scale"])


def layer_kinds(cfg):
    """The kind of each layer that is run: the head of `layer_types`."""
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {SLIDING, FULL}:
        raise ValueError(f"layer_types does not name {cfg['num_hidden_layers']}"
                         f" layers of {SLIDING} | {FULL}: {kinds}")
    return kinds


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
    unit gains.  The head is the embedding: there is no other table.
    `dtype` defaults to the configuration's `param_dtype`."""
    s = sizes(cfg)
    dtype = jnp.dtype(dtype or cfg.get("param_dtype", "bfloat16"))
    std = cfg.get("initializer_range", 0.02)
    D, F, E, ns = s["D"], s["F"], s["E"], s["ns"]
    Q, KV = s["H"] * s["Dh"], s["Hkv"] * s["Dh"]

    def n(k, *shape):
        return _draw(k, shape, std, dtype)

    def layer(k):
        ks = jax.random.split(k, 11)
        return {"norm": jnp.ones((D,), dtype),
                "wq": n(ks[0], D, Q), "wk": n(ks[1], D, KV),
                "wv": n(ks[2], D, KV), "wo": n(ks[3], Q, D),
                "router": n(ks[4], D, s["Er"]),
                "e_gate": n(ks[5], E, D, F), "e_up": n(ks[6], E, D, F),
                "e_down": n(ks[7], E, F, D),
                "s_gate": n(ks[8], ns, D, F), "s_up": n(ks[9], ns, D, F),
                "s_down": n(ks[10], ns, F, D)}

    ks = jax.random.split(key, s["L"] + 1)
    return {"embed": n(ks[0], s["V"], D),
            "layers": [layer(ks[i + 1]) for i in range(s["L"])],
            "final_norm": jnp.ones((D,), dtype)}


# ------------------------------------------------------------------ pieces
def layer_norm(x, g, eps):
    x = x.astype(jnp.float32)
    xc = x - jnp.mean(x, -1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def rotate(x, positions, cfg):
    """x [T, heads, Dh]: the pairs (2i, 2i+1) of each head are turned by
    `positions * theta^(-2i/Dh)` and stay in their own two columns
    (`rope_gptj`, `rotary_pct` 1: all the columns); queries and keys
    alike."""
    dh = x.shape[-1]
    f = float(cfg["rope_theta"]) ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = positions.astype(jnp.float32)[:, None, None] \
        * jnp.asarray(f, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def swiglu(h, wg, wu, wd, mode):
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731
    return mm(jax.nn.silu(mm(h, f32(wg), mode)) * mm(h, f32(wu), mode),
              f32(wd), mode)


def attention(h, w, cfg, kind, mode):
    """Attn(h) for h [T, D] = LN(x): causal over the T rows, inside the
    window in a sliding layer."""
    s = sizes(cfg)
    T = h.shape[0]
    H, Hkv, Dh = s["H"], s["Hkv"], s["Dh"]
    G = H // Hkv
    pos = jnp.arange(T)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    q = mm(h, f32(w["wq"]), mode).reshape(T, H, Dh)
    k = mm(h, f32(w["wk"]), mode).reshape(T, Hkv, Dh)
    v = mm(h, f32(w["wv"]), mode).reshape(T, Hkv, Dh)
    if kind == SLIDING:
        q, k = rotate(q, pos, cfg), rotate(k, pos, cfg)
    qh, kh, vh = (a.transpose(1, 0, 2) for a in (q, k, v))     # [heads, T, Dh]

    def one_key_head(args):
        # the G query heads that read this key head: the one key head
        # against each of them is the key head repeated G times
        qg, kg, vg = args                          # [G, T, Dh], [T, Dh] x 2
        out = []
        for q0 in range(0, T, QUERY_BLOCK):
            q1 = min(T, q0 + QUERY_BLOCK)
            lo = max(0, q0 - s["window"] + 1) if kind == SLIDING else 0
            sc = mm(qg[:, q0:q1], kg[lo:q1].T, mode) * Dh ** -0.5
            t, j = jnp.arange(q0, q1)[:, None], jnp.arange(lo, q1)[None, :]
            keep = j <= t
            if kind == SLIDING:
                keep = keep & (t - j < s["window"])
            p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), -1)
            out.append(mm(p, vg[lo:q1], mode))
        return jnp.concatenate(out, 1)                        # [G, T, Dh]

    # one key head after another (a sequential map: one head's scores
    # alive at a time)
    heads = jax.lax.map(one_key_head,
                        (qh.reshape(Hkv, G, T, Dh), kh, vh))  # [Hkv, G, T, Dh]
    o = heads.reshape(H, T, Dh).transpose(1, 0, 2).reshape(T, H * Dh)
    return mm(o, f32(w["wo"]), mode)


def firmness(x, w, cfg):
    """How firmly the float32 router chooses at each row of x [T, D], a
    layer's input: the router's logit of the last expert chosen less
    that of the first one left out, over the root mean square of the
    row's logits (the sigmoid keeps their order; a rounding error in the
    hidden state moves a logit by a share of that).  -> [T].

    Where the margin is within rounding, any precision below float32
    sends the token to other experts than this reference does, and one
    swapped expert of a token's eight moves its logits as far as fp8
    moves them everywhere: such a position says nothing of the
    precision, so `served_gap` leaves it out, by this rule on the
    reference's own numbers and not by what was served.  No expert,
    held or absent, crosses a margin wider than the error: counting
    only margins whose two experts are held missed a held expert ranked
    tenth behind two absent ones in a near tie (seen on the chip)."""
    s = sizes(cfg)
    h = layer_norm(x, w["norm"], s["eps"])
    z = mm(h, w["router"].astype(jnp.float32), "f32")
    top, _ = jax.lax.top_k(z, s["top"] + 1)
    return (top[:, -2] - top[:, -1]) * jax.lax.rsqrt(jnp.mean(z * z, -1))


def route(h, w, cfg, mode):
    """-> gates [T, router width]: `s / sum(s)` over the chosen experts,
    nought elsewhere: no bias chooses and no factor scales."""
    s = sizes(cfg)
    score = jax.nn.sigmoid(mm(h, w["router"].astype(jnp.float32), mode))
    _, chosen = jax.lax.top_k(score, s["top"])
    picked = jnp.take_along_axis(score, chosen, -1)
    g = picked / jnp.sum(picked, -1, keepdims=True)
    return jnp.zeros_like(score).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(g)


def routed(h, w, cfg, mode, held=None):
    """The held experts' part of `sum over the chosen of gate_e E_e(h)`.
    `held` (first, count) overrides the configuration's share: the tests
    add the eight shares of a layer up."""
    s = sizes(cfg)
    first, count = held or (s["first"], s["E"])
    gates = route(h, w, cfg, mode)[:, first:first + count]    # [T, E]

    def one(y, ew):
        g, wg, wu, wd = ew
        return y + g[:, None] * swiglu(h, wg, wu, wd, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (gates.T, w["e_gate"], w["e_up"], w["e_down"]))
    return y


def shared(h, w, cfg, mode):
    """`(1/4) sum over the 4 shared experts of S_j(h)`: four experts
    kept apart, their outputs averaged."""
    def one(y, sw):
        return y + swiglu(h, *sw, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["s_gate"], w["s_up"], w["s_down"]))
    return y / w["s_gate"].shape[0]


def block(x, w, cfg, kind, mode="f32", held=None):
    h = layer_norm(x, w["norm"], sizes(cfg)["eps"])
    return (x.astype(jnp.float32) + attention(h, w, cfg, kind, mode)
            + routed(h, w, cfg, mode, held) + shared(h, w, cfg, mode))


@functools.lru_cache(maxsize=None)
def _programs(cfg_key, mode):
    """One jitted program a kind of layer, so that a layer's float32
    copies are the only ones alive."""
    cfg = dict(cfg_key[0])
    cfg["layer_types"] = list(cfg_key[1])
    s = sizes(cfg)
    layers = {kind: jax.jit(functools.partial(
        lambda x, w, kind: block(x, w, cfg, kind, mode), kind=kind))
        for kind in (SLIDING, FULL)}
    embed = jax.jit(lambda e, ids: e[ids].astype(jnp.float32))
    head = jax.jit(lambda x, g, e: mm(
        layer_norm(x, g, s["eps"]), e.astype(jnp.float32).T, mode)
        * s["logit_scale"])
    firm = jax.jit(lambda x, w: firmness(
        x, {"norm": w["norm"], "router": w["router"]}, cfg))
    return embed, layers, head, firm


def _key(cfg):
    flat = tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))
    return flat, tuple(cfg["layer_types"])


def logits_row(params, x, cfg, mode="f32", rows=None, margins=False):
    """x [T] int ids -> logits of one sequence by the full forward:
    [T, V], or [len(rows), V] at the positions `rows` alone.  With
    `margins` also `firmness` of every layer at those positions,
    [L, rows]."""
    embed, layers, head, firm = _programs(_key(cfg), mode)
    h = embed(params["embed"], x)
    firms = []
    for kind, w in zip(layer_kinds(cfg), params["layers"]):
        if margins:
            firms.append(firm(h, w))
        h = layers[kind](h, w)
    if rows is not None:
        h = h[rows]
    out = head(h, params["final_norm"], params["embed"])
    if not margins:
        return out
    firms = jnp.stack(firms)                                  # [L, T]
    return out, firms if rows is None else firms[:, rows]


def served_gaps(cfg, seed, sample, mode="f32"):
    """-> (gaps [N], firmness [L, N]) over the N served tokens of
    `sample`, request after request: the gap by which each served
    token's logit lies below the float32 reference's best at its
    position, and how firmly the float32 router chose there in each
    layer.  With `mode` other than f32 the gaps are the CONTROL's: the
    same forward in that lower precision is put in the program's place,
    and at each of the same positions the gap is read of the token it
    puts first."""
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
    the float32 router chose firmly in every layer (`FIRM`)."""
    gaps, firm = served_gaps(cfg, seed, sample, mode)
    keep = np.min(firm, 0) > FIRM
    print(f"[reference] {int(keep.sum())} of {len(gaps)} served positions "
          f"are routed firmly", file=sys.stderr, flush=True)
    return float(np.max(gaps[keep], initial=0.0))
