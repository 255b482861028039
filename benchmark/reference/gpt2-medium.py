"""Plain reference of the `gpt2-medium` configuration: forward, loss,
gradients and Adam in float32 `jax.numpy` at `Precision.HIGHEST`, one row
of the batch at a time (so it fits beside nothing else on one chip), the
24 blocks as one `lax.scan` with rematerialisation (same mathematics,
shorter compile, less memory).  Imports nothing of the program.

The block is the one the configuration's file describes, departures and
all: pre-LN, sinusoidal additive positions (GPT-2 learns them), an
embedding bias, no final LayerNorm, an untied biased output head, tanh
GELU, LayerNorm eps 1e-5, mean cross-entropy over all B*T positions.
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

STACKED = ("blocks",)


def sizes(cfg):
    d = cfg["n_embd"]
    return cfg["vocab_size"], d, cfg["n_layer"], cfg["n_head"], \
        cfg.get("n_inner") or 4 * d


def init_params(cfg, key):
    """Weights from the seed, GPT-2's own initialisation: N(0, 0.02) for
    every matrix, zero biases, unit gains."""
    V, D, L, _, F = sizes(cfg)
    std = cfg.get("initializer_range", 0.02)
    ks = jax.random.split(key, 8)

    def n(k, shape):
        return std * jax.random.normal(k, shape, jnp.float32)

    z, o = jnp.zeros, jnp.ones
    blocks = {
        "ln1_g": o((L, D)), "ln1_b": z((L, D)),
        "wq": n(ks[1], (L, D, D)), "bq": z((L, D)),
        "wk": n(ks[2], (L, D, D)), "bk": z((L, D)),
        "wv": n(ks[3], (L, D, D)), "bv": z((L, D)),
        "wo": n(ks[4], (L, D, D)), "bo": z((L, D)),
        "ln2_g": o((L, D)), "ln2_b": z((L, D)),
        "w1": n(ks[5], (L, D, F)), "b1": z((L, F)),
        "w2": n(ks[6], (L, F, D)), "b2": z((L, D)),
    }
    return {"wte": n(ks[0], (V, D)), "wte_b": z((D,)), "blocks": blocks,
            "head_w": n(ks[7], (D, V)), "head_b": z((V,))}


def positions(T, D):
    """Vaswani et al.'s sinusoids: sin on even columns, cos on odd."""
    pos = np.arange(T)[:, None]
    i = np.arange(D // 2)[None, :]
    ang = pos / np.power(10000.0, 2.0 * i / D)
    tab = np.zeros((T, D), np.float32)
    tab[:, 0::2] = np.sin(ang)
    tab[:, 1::2] = np.cos(ang)
    return jnp.asarray(tab)


def layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi) * (x + 0.044715 * x ** 3)))


def block(h, w, n_head, mode, pos0=0):
    """One pre-LN block on h [T, D]; causal over the T rows."""
    T, D = h.shape
    dh = D // n_head
    a = layer_norm(h, w["ln1_g"], w["ln1_b"])

    def heads(x):
        return x.reshape(T, n_head, dh).transpose(1, 0, 2)

    q = heads(mm(a, w["wq"], mode) + w["bq"])
    k = heads(mm(a, w["wk"], mode) + w["bk"])
    v = heads(mm(a, w["wv"], mode) + w["bv"])
    s = mm(q, k.transpose(0, 2, 1), mode) / np.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm(p, v, mode).transpose(1, 0, 2).reshape(T, D)
    h = h + mm(o, w["wo"], mode) + w["bo"]
    a = layer_norm(h, w["ln2_g"], w["ln2_b"])
    f = gelu(mm(a, w["w1"], mode) + w["b1"])
    return h + mm(f, w["w2"], mode) + w["b2"]


def logits_row(params, x, n_head, mode="f32"):
    """x [T] int ids -> logits [T, V] of one sequence, full forward."""
    T = x.shape[0]
    D = params["wte"].shape[1]
    h = params["wte"][x] + params["wte_b"] + positions(T, D)

    @jax.checkpoint
    def body(h, w):
        return block(h, w, n_head, mode), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    return mm(h, params["head_w"], mode) + params["head_b"]


def row_loss_sum(params, ids, n_head, mode):
    """Sum over positions of the next-token cross-entropy of one row
    ids [T+1]."""
    lg = logits_row(params, ids[:-1], n_head, mode)
    lse = jax.nn.logsumexp(lg, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(lg, ids[1:, None], 1)[:, 0])


@functools.lru_cache(maxsize=None)
def _programs(n_head, mode, lr):
    grad_row = jax.jit(jax.value_and_grad(
        functools.partial(row_loss_sum, n_head=n_head, mode=mode)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))

    def update(p, g, m, v, step, scale):
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        out = jax.tree_util.tree_map(
            lambda p_, g_, m_, v_: common.adam(p_, g_, m_, v_, step, lr),
            p, g, m, v)
        tup = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda _, o: o[i], p, out)
        return tup(0), tup(1), tup(2), common.leaf_norms(g, STACKED)

    return grad_row, add, jax.jit(update, donate_argnums=(0, 2, 3))


def train_readings(cfg, cell, seed, batches, mode="f32", rows=None,
                   other_first_gradient=None, keep_first_gradient=False):
    """Follow the first len(batches) training steps from the seed's
    weights.  `batches`: list of int arrays [B, T+1].  `rows` limits the
    rows used of each batch (the "half of the batch left out" fault: the
    mean is then taken over the rest).  Returns losses, the first
    gradient's norm and the change's norm after the last step, by leaf."""
    _, _, _, n_head, _ = sizes(cfg)
    lr = float(cfg["learning_rate"])
    grad_row, add, update = _programs(n_head, mode, lr)
    words = common.seed_words(seed)
    init = jax.jit(lambda w: init_params(cfg, common.key_of(w)))
    p = init(words)
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, gnorm, extra = [], None, {}
    for step, ids in enumerate(batches):
        ids = np.asarray(ids)[:rows]
        tot, g = None, None
        for r in ids:
            l, gr = grad_row(p, jnp.asarray(r, jnp.int32))
            tot = l if tot is None else tot + l
            g = gr if g is None else add(g, gr)
        count = ids.shape[0] * (ids.shape[1] - 1)
        losses.append(float(tot) / count)
        if step == 0 and other_first_gradient is not None:
            extra["grad_cosine_gap"] = float(common.cosine_gap(
                g, jax.device_put(other_first_gradient)))
        p, m, v, gn = update(p, g, m, v, jnp.float32(step),
                             jnp.float32(1.0 / count))
        if step == 0:
            gnorm = common.flatten_norms(gn)
            if keep_first_gradient:
                extra["first_gradient"] = g
                continue
        common.free(g)
    dn = jax.jit(lambda p_, w: common.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, p_, init_params(
            cfg, common.key_of(w))), STACKED))(p, words)
    dnorm = common.flatten_norms(dn)
    common.free(p, m, v)
    return {"losses": losses, "grad_norm": gnorm, "change_norm": dnorm,
            **extra}


def served_gap(cfg, seed, sample, mode="f32"):
    """The serving comparison.  `sample`: [(prompt ids, served ids)] of
    greedy requests.  One full forward over each prompt with its served
    tokens, float32 at HIGHEST; returns the widest gap by which a served
    token's logit lies below the reference's best at its position.

    With `mode` other than f32 this is the CONTROL: the same forward in
    that lower precision is put in the program's place, and at each of
    the same positions the gap is read of the token it puts first."""
    _, _, _, n_head, _ = sizes(cfg)
    pad = cfg["n_positions"]
    words = common.seed_words(seed)
    params = jax.jit(lambda w: init_params(cfg, common.key_of(w)))(words)

    @functools.partial(jax.jit, static_argnames=("mode",))
    def gaps(params, seq, served, first, mode):
        # seq [pad] ids, padded after the end (causal: no effect before
        # it); served[j] was produced at position first + j
        ref = logits_row(params, seq, n_head, "f32")
        pos = first + jnp.arange(served.shape[0])
        rows = ref[jnp.clip(pos, 0, pad - 1)]
        best = jnp.max(rows, -1)
        if mode == "f32":
            tok = served
        else:
            low = logits_row(params, seq, n_head, mode)
            tok = jnp.argmax(low[jnp.clip(pos, 0, pad - 1)], -1)
        got = jnp.take_along_axis(rows, jnp.clip(tok, 0)[:, None], 1)[:, 0]
        return jnp.where(served >= 0, best - got, 0.0)

    widest = 0.0
    out_pad = max(len(o) for _, o in sample)
    out_pad = -(-out_pad // 64) * 64
    for prompt, out in sample:
        prompt, out = np.asarray(prompt), np.asarray(out)
        n = len(prompt) + len(out)
        if n > pad:
            raise ValueError(f"request of {n} positions exceeds {pad}")
        seq = np.zeros(pad, np.int32)
        seq[:n] = np.concatenate([prompt, out])
        served = np.full(out_pad, -1, np.int32)
        served[:len(out)] = out
        g = gaps(params, jnp.asarray(seq), jnp.asarray(served),
                 len(prompt) - 1, mode)
        widest = max(widest, float(jnp.max(g)))
    common.free(params)
    return widest
