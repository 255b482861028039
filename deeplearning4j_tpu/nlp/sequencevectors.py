"""SequenceVectors — the generic embedding-training engine, TPU-first.

Reference: `models/sequencevectors/SequenceVectors.java:192` (`fit()`):
vocab scan → AsyncSequencer prefetch thread → N Hogwild
`VectorCalculationsThread`s doing per-pair scalar updates through the
fused native `AggregateSkipGram` op (`SkipGram.java:224`,
`iterateSample`).

TPU redesign (same capability, device-friendly schedule): the host side
streams sequences, applies frequent-word subsampling and the
reduced-window trick, and packs (center, context, negatives) into
fixed-shape batches; the device side runs ONE jitted step per batch —
embedding gathers, a [B,K] dot-product block (MXU), log-sigmoid loss,
and autodiff scatter-add updates. Batched minibatch SGD replaces
Hogwild (which does not map to SPMD hardware); gradients are averaged
over the batch (minibatch SGD), trading the reference's per-pair
sequential updates for device-sized steps. Both learning regimes are kept: negative sampling and
hierarchical softmax over Huffman codes (padded [B, C] with masks so
shapes stay static for XLA).

Skip-gram and CBOW both supported (`elements_learning_algorithm`);
ParagraphVectors reuses this engine by extending the embedding table
with label rows (see paragraphvectors.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.vocab import VocabCache, VocabConstructor


from deeplearning4j_tpu.nd.donation import donate_argnums as _donate
from deeplearning4j_tpu.nd.donation import jit_donated as _jit_donated


@dataclasses.dataclass
class SequenceVectorsConfig:
    vector_length: int = 100
    window: int = 5
    min_word_frequency: int = 1
    negative: int = 5           # K negative samples; 0 → hierarchical softmax
    use_hierarchic_softmax: bool = False
    learning_rate: float = 0.025
    min_learning_rate: float = 1e-4
    epochs: int = 1
    iterations: int = 1         # passes per batch (reference `iterations`)
    batch_size: int = 2048      # pairs per device step
    steps_per_flush: int = 8    # skip-gram batches fused into one scan dispatch
    subsampling: float = 0.0    # frequent-word discard threshold (e.g. 1e-3)
    seed: int = 42
    cbow: bool = False          # elements learning algorithm: CBOW vs SkipGram
    unigram_power: float = 0.75  # negative-table exponent (word2vec standard)
    # AsyncSequencer role (`SequenceVectors.java:288`): pack pair
    # arrays on a producer thread while the device runs the previous
    # fused scan — the jax dispatch is async, so the two overlap.
    # Applies to the fast path (skip-gram/neg, iterations=1, no
    # pair_hook); the trainer records host/device wait ms either way.
    async_producer: bool = True
    producer_queue_depth: int = 2


# ------------------------------------------------------------ jitted steps
def _row_counts(n_rows, *index_sets):
    """How many times each table row is touched in the batch. Each
    entry is an index array, or (indices, weights) for masked refs."""
    c = jnp.zeros((n_rows,), jnp.float32)
    for s in index_sets:
        if isinstance(s, tuple):
            idx, w = s
            c = c.at[idx.reshape(-1)].add(w.reshape(-1).astype(jnp.float32))
        else:
            c = c.at[s.reshape(-1)].add(1.0)
    return jnp.clip(c, 1.0, None)[:, None]


# Batched treatment of word2vec's sequential per-pair updates: the
# scatter-added (sum) row gradient is divided by the row's occurrence
# count, so every touched row moves ~one per-pair step per flush
# regardless of batch size. A plain batch mean shrinks steps by 1/B and
# stalls small corpora; a plain sum diverges for frequent rows.


def _sg_neg_math(syn0, syn1neg, centers, contexts, negs, lr, trainable_from,
                 valid=None):
    """Skip-gram negative-sampling update math (shared by the single-step
    jit and the fused scan). trainable_from: row index from which syn0
    rows are trainable (0 = all; used by inferVector).

    Sparse closed-form update: the gradient of the SGNS loss only
    touches the B center rows and B·(K+1) output rows, so the update is
    computed per pair ([B,D]/[B,K,D] intermediates) and scatter-added —
    never materializing the [V,D] dense gradient autodiff would produce.
    At real vocabulary sizes (10⁵–10⁶ rows) the dense route is
    memory-bound garbage; this is the Pallas-guide "sparse-update"
    shape, expressed with XLA scatters (`.at[].add`). Row sums are
    divided by per-row occurrence counts (see note above) — identical
    math to the autodiff version, verified by test.

    `valid` (optional [B] 0/1 mask) lets ragged epoch-end tails run
    padded to the full compiled batch shape: masked entries contribute
    nothing to loss, counts, or updates — bitwise the same result as a
    ragged-shape flush, without paying an XLA compile per distinct tail
    length."""
    f32 = jnp.float32
    v = jnp.take(syn0, centers, axis=0)                        # [B,D]
    u_pos = jnp.take(syn1neg, contexts, axis=0)                # [B,D]
    u_neg = jnp.take(syn1neg, negs, axis=0)                    # [B,K,D]
    s_pos = jnp.sum(v * u_pos, axis=-1)                        # [B]
    s_neg = jnp.einsum("bd,bkd->bk", v, u_neg)                 # [B,K]
    lp, ln = jax.nn.log_sigmoid(s_pos), jax.nn.log_sigmoid(-s_neg)
    # dL/ds: σ(s)-1 for the positive, σ(s) for negatives
    c_pos = -jax.nn.sigmoid(-s_pos)                            # [B]
    c_neg = jax.nn.sigmoid(s_neg)                              # [B,K]
    if valid is None:
        n_eff = centers.shape[0]
        loss = -(jnp.sum(lp) + jnp.sum(ln))
        one = None
    else:
        n_eff = jnp.clip(jnp.sum(valid), 1.0, None)
        loss = -(jnp.sum(lp * valid) + jnp.sum(ln * valid[:, None]))
        c_pos = c_pos * valid
        c_neg = c_neg * valid[:, None]
        one = valid
    dv = c_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", c_neg, u_neg)
    du_pos = c_pos[:, None] * v                                # [B,D]
    du_neg = c_neg[..., None] * v[:, None, :]                  # [B,K,D]

    w1 = 1.0 if one is None else one
    wk = 1.0 if one is None else jnp.broadcast_to(one[:, None], negs.shape)
    counts0 = jnp.zeros((syn0.shape[0],), f32).at[centers].add(w1)
    counts0 = jnp.clip(counts0, 1.0, None)
    counts1 = (jnp.zeros((syn1neg.shape[0],), f32)
               .at[contexts].add(w1)
               .at[negs.reshape(-1)].add(
                   wk.reshape(-1) if one is not None else 1.0))
    counts1 = jnp.clip(counts1, 1.0, None)

    scale0 = (lr / counts0[centers])[:, None]                  # [B,1]
    if trainable_from > 0:
        # inference mode: only rows >= trainable_from learn; the output
        # table is frozen entirely (reference inferVector semantics)
        scale0 = scale0 * (centers >= trainable_from)[:, None]
        new_syn1neg = syn1neg
    else:
        s_ctx = (lr / counts1[contexts])[:, None]
        s_negs = (lr / counts1[negs])[..., None]               # [B,K,1]
        new_syn1neg = (syn1neg
                       .at[contexts].add(-(du_pos * s_ctx)
                                         .astype(syn1neg.dtype))
                       .at[negs.reshape(-1)].add(
                           -(du_neg * s_negs)
                           .reshape(-1, syn1neg.shape[1])
                           .astype(syn1neg.dtype)))
    new_syn0 = syn0.at[centers].add(-(dv * scale0).astype(syn0.dtype))
    return new_syn0, new_syn1neg, loss / n_eff


@_jit_donated(donate=(0, 1), static_argnums=(6,))
def _sg_neg_step(syn0, syn1neg, centers, contexts, negs, lr, trainable_from):
    return _sg_neg_math(syn0, syn1neg, centers, contexts, negs, lr,
                        trainable_from)


@_jit_donated(donate=(0, 1), static_argnums=(6,))
def _sg_neg_step_masked(syn0, syn1neg, centers, contexts, negs, lr,
                        trainable_from, valid):
    """Tail flush: ragged batch padded to the compiled [B] shape with a
    validity mask — one compile serves every tail length."""
    return _sg_neg_math(syn0, syn1neg, centers, contexts, negs, lr,
                        trainable_from, valid)


def _sg_neg_scan(syn0, syn1neg, centers, contexts, negs, lrs, trainable_from):
    """k fused skip-gram batches in ONE dispatch (`lax.scan` over the
    per-batch update). The reference amortizes its per-pair update cost
    across Hogwild threads (`SequenceVectors.java:294`); on TPU the
    equivalent lever is fewer, bigger dispatches — the host packs k
    [B]-shaped batches while the device drains the previous group
    (async dispatch, no host sync in between).

    centers/contexts: [k,B]; negs: [k,B,K]; lrs: [k]. This is the one
    copy of the fused math; it gets jitted twice — plain and
    mesh-sharded (`_mesh_steps`)."""

    def body(carry, inp):
        s0, s1 = carry
        c, x, n, lr = inp
        s0, s1, loss = _sg_neg_math(s0, s1, c, x, n, lr, trainable_from)
        return (s0, s1), loss

    (syn0, syn1neg), losses = jax.lax.scan(
        body, (syn0, syn1neg), (centers, contexts, negs, lrs))
    return syn0, syn1neg, losses[-1]


_sg_neg_multi = _jit_donated(_sg_neg_scan, donate=(0, 1),
                            static_argnums=(6,))


def _cbow_neg_math(syn0, syn1neg, ctx, ctx_mask, centers, negs, lr,
                   trainable_from, valid=None):
    """CBOW negative-sampling step (sparse closed form, same reasoning
    as `_sg_neg_math`). ctx: [B, 2W] indices, ctx_mask 0/1. `valid` as
    in `_sg_neg_math` — padded tail rows (ctx_mask all zero) contribute
    nothing to loss, counts, or either table."""
    f32 = jnp.float32
    vecs = jnp.take(syn0, ctx, axis=0)                         # [B,W2,D]
    m = ctx_mask[..., None]
    M = jnp.clip(jnp.sum(ctx_mask, axis=1, keepdims=True), 1.0, None)
    h = jnp.sum(vecs * m, axis=1) / M                          # [B,D]
    u_pos = jnp.take(syn1neg, centers, axis=0)
    u_neg = jnp.take(syn1neg, negs, axis=0)                    # [B,K,D]
    s_pos = jnp.sum(h * u_pos, axis=-1)
    s_neg = jnp.einsum("bd,bkd->bk", h, u_neg)
    lp, ln = jax.nn.log_sigmoid(s_pos), jax.nn.log_sigmoid(-s_neg)
    c_pos = -jax.nn.sigmoid(-s_pos)                            # [B]
    c_neg = jax.nn.sigmoid(s_neg)                              # [B,K]
    if valid is None:
        n_eff = centers.shape[0]
        loss = -(jnp.sum(lp) + jnp.sum(ln))
        w1, wk = 1.0, 1.0
    else:
        n_eff = jnp.clip(jnp.sum(valid), 1.0, None)
        loss = -(jnp.sum(lp * valid) + jnp.sum(ln * valid[:, None]))
        c_pos = c_pos * valid
        c_neg = c_neg * valid[:, None]
        w1 = valid
        wk = jnp.broadcast_to(valid[:, None], negs.shape)
    dh = c_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", c_neg, u_neg)
    # dL/dv_slot = (mask/M) * dh, per context slot
    dctx = (m / M[..., None]) * dh[:, None, :]                 # [B,W2,D]
    du_pos = c_pos[:, None] * h
    du_neg = c_neg[..., None] * h[:, None, :]

    counts0 = (jnp.zeros((syn0.shape[0],), f32)
               .at[ctx.reshape(-1)].add(ctx_mask.reshape(-1)))
    counts0 = jnp.clip(counts0, 1.0, None)
    counts1 = (jnp.zeros((syn1neg.shape[0],), f32)
               .at[centers].add(w1)
               .at[negs.reshape(-1)].add(
                   wk.reshape(-1) if valid is not None else 1.0))
    counts1 = jnp.clip(counts1, 1.0, None)

    scale0 = (lr / counts0[ctx])[..., None] * m                # [B,W2,1]
    if trainable_from > 0:
        scale0 = scale0 * (ctx >= trainable_from)[..., None]
        new_syn1neg = syn1neg
    else:
        s_ctr = (lr / counts1[centers])[:, None]
        s_negs = (lr / counts1[negs])[..., None]
        new_syn1neg = (syn1neg
                       .at[centers].add(-(du_pos * s_ctr)
                                        .astype(syn1neg.dtype))
                       .at[negs.reshape(-1)].add(
                           -(du_neg * s_negs)
                           .reshape(-1, syn1neg.shape[1])
                           .astype(syn1neg.dtype)))
    new_syn0 = syn0.at[ctx.reshape(-1)].add(
        -(dctx * scale0).reshape(-1, syn0.shape[1]).astype(syn0.dtype))
    return new_syn0, new_syn1neg, loss / n_eff


@_jit_donated(donate=(0, 1), static_argnums=(7,))
def _cbow_neg_step(syn0, syn1neg, ctx, ctx_mask, centers, negs, lr,
                   trainable_from):
    return _cbow_neg_math(syn0, syn1neg, ctx, ctx_mask, centers, negs,
                          lr, trainable_from)


@_jit_donated(donate=(0, 1), static_argnums=(7,))
def _cbow_neg_step_masked(syn0, syn1neg, ctx, ctx_mask, centers, negs, lr,
                          trainable_from, valid):
    return _cbow_neg_math(syn0, syn1neg, ctx, ctx_mask, centers, negs,
                          lr, trainable_from, valid)


def _hs_path_grads(h, syn1, points, codes, code_mask):
    """Shared HS math: dL/dh and the per-path-node output deltas for a
    batch of hidden vectors classified down Huffman paths."""
    u = jnp.take(syn1, points, axis=0)                         # [B,C,D]
    sign = 1.0 - 2.0 * codes
    logits = jnp.einsum("bd,bcd->bc", h, u) * sign
    loss = -jnp.sum(jax.nn.log_sigmoid(logits) * code_mask)
    dlogit = -jax.nn.sigmoid(-logits) * code_mask              # [B,C]
    coef = dlogit * sign
    dh = jnp.einsum("bc,bcd->bd", coef, u)
    du = coef[..., None] * h[:, None, :]                       # [B,C,D]
    return loss, dh, du


def _cbow_hs_math(syn0, syn1, ctx, ctx_mask, centers, points, codes,
                  code_mask, lr, valid=None):
    """CBOW + hierarchical softmax: context mean classified down the
    center word's Huffman path (reference `CBOW.java` HS branch).
    Sparse closed form like the NS steps. `valid` as in `_sg_hs_math`
    (padded rows' path mask is neutralized here; their ctx_mask rows
    are already all-zero)."""
    f32 = jnp.float32
    if valid is not None:
        code_mask = code_mask * valid[:, None]
    n_eff = (centers.shape[0] if valid is None
             else jnp.clip(jnp.sum(valid), 1.0, None))
    vecs = jnp.take(syn0, ctx, axis=0)
    m = ctx_mask[..., None]
    M = jnp.clip(jnp.sum(ctx_mask, axis=1, keepdims=True), 1.0, None)
    h = jnp.sum(vecs * m, axis=1) / M
    loss, dh, du = _hs_path_grads(h, syn1, points, codes, code_mask)
    dctx = (m / M[..., None]) * dh[:, None, :]

    counts0 = (jnp.zeros((syn0.shape[0],), f32)
               .at[ctx.reshape(-1)].add(ctx_mask.reshape(-1)))
    counts0 = jnp.clip(counts0, 1.0, None)
    counts1 = (jnp.zeros((syn1.shape[0],), f32)
               .at[points.reshape(-1)].add(code_mask.reshape(-1)))
    counts1 = jnp.clip(counts1, 1.0, None)

    scale0 = (lr / counts0[ctx])[..., None] * m
    scale1 = (lr / counts1[points])[..., None]
    new_syn0 = syn0.at[ctx.reshape(-1)].add(
        -(dctx * scale0).reshape(-1, syn0.shape[1]).astype(syn0.dtype))
    new_syn1 = syn1.at[points.reshape(-1)].add(
        -(du * scale1).reshape(-1, syn1.shape[1]).astype(syn1.dtype))
    return new_syn0, new_syn1, loss / n_eff


@_jit_donated(donate=(0, 1))
def _cbow_hs_step(syn0, syn1, ctx, ctx_mask, centers, points, codes,
                  code_mask, lr):
    return _cbow_hs_math(syn0, syn1, ctx, ctx_mask, centers, points,
                         codes, code_mask, lr)


@_jit_donated(donate=(0, 1))
def _cbow_hs_step_masked(syn0, syn1, ctx, ctx_mask, centers, points, codes,
                         code_mask, lr, valid):
    return _cbow_hs_math(syn0, syn1, ctx, ctx_mask, centers, points,
                         codes, code_mask, lr, valid)


def _sg_hs_math(syn0, syn1, centers, points, codes, code_mask, lr,
                valid=None):
    """Skip-gram hierarchical-softmax step over Huffman paths
    (reference `SkipGram.iterateSample` HS branch, `SkipGram.java:224`).
    Sparse closed form like the NS steps. `valid` as in `_sg_neg_math`:
    padded tail entries are masked out of the path mask here, so callers
    only need to pad index arrays with zeros."""
    f32 = jnp.float32
    if valid is not None:
        # padded rows index word 0's Huffman path — neutralize it fully
        code_mask = code_mask * valid[:, None]
    v = jnp.take(syn0, centers, axis=0)                        # [B,D]
    loss, dv, du = _hs_path_grads(v, syn1, points, codes, code_mask)

    w1 = 1.0 if valid is None else valid
    n_eff = (centers.shape[0] if valid is None
             else jnp.clip(jnp.sum(valid), 1.0, None))
    counts0 = jnp.clip(jnp.zeros((syn0.shape[0],), f32)
                       .at[centers].add(w1), 1.0, None)
    counts1 = (jnp.zeros((syn1.shape[0],), f32)
               .at[points.reshape(-1)].add(code_mask.reshape(-1)))
    counts1 = jnp.clip(counts1, 1.0, None)

    scale0 = (lr / counts0[centers])[:, None]
    scale1 = (lr / counts1[points])[..., None]
    new_syn0 = syn0.at[centers].add(-(dv * scale0).astype(syn0.dtype))
    new_syn1 = syn1.at[points.reshape(-1)].add(
        -(du * scale1).reshape(-1, syn1.shape[1]).astype(syn1.dtype))
    return new_syn0, new_syn1, loss / n_eff


@_jit_donated(donate=(0, 1))
def _sg_hs_step(syn0, syn1, centers, points, codes, code_mask, lr):
    return _sg_hs_math(syn0, syn1, centers, points, codes, code_mask, lr)


@_jit_donated(donate=(0, 1))
def _sg_hs_step_masked(syn0, syn1, centers, points, codes, code_mask, lr,
                       valid):
    return _sg_hs_math(syn0, syn1, centers, points, codes, code_mask, lr,
                       valid)


class SequenceVectors:
    """Trains an embedding table over token sequences."""

    def __init__(self, config: Optional[SequenceVectorsConfig] = None, *,
                 mesh=None, data_axis: str = "data", **kw):
        if config is None:
            config = SequenceVectorsConfig(**kw)
        self.conf = config
        self.vocab: Optional[VocabCache] = None
        self.syn0 = None       # np.ndarray [V(+labels), D]
        self.syn1 = None       # HS inner-node table
        self.syn1neg = None    # negative-sampling output table
        self._neg_table = None
        self._rng = np.random.default_rng(config.seed)
        self._negs_rng = None   # flush-side stream (see _sample_negatives)
        self.etl_stats = None   # producer/consumer wait accounting
        # mesh-sharded training (the dl4j-spark-nlp distributed Word2Vec
        # capability, `spark/models/embeddings/word2vec/Word2Vec.java`):
        # the pair batch shards over `data_axis`, tables stay replicated,
        # and XLA inserts the grad all-reduce. Global-view jit semantics
        # make the result bitwise-equivalent (up to reduction order) to
        # single-device training. Covers the skip-gram paths; CBOW/HS
        # fall back to unsharded steps.
        self.mesh = mesh
        self.data_axis = data_axis
        self._sharded_step = None
        self._sharded_multi = None
        self._warmed_key = None

    # ------------------------------------------------------------- vocab
    def build_vocab(self, sequences: Iterable[List[str]]):
        self.vocab = VocabConstructor(
            min_word_frequency=self.conf.min_word_frequency).build(sequences)
        return self

    def _init_tables(self, extra_rows: int = 0):
        V = self.vocab.num_words()
        D = self.conf.vector_length
        # word2vec init: U(-0.5, 0.5)/D for syn0, zeros for output tables
        self.syn0 = ((self._rng.random((V + extra_rows, D)) - 0.5) / D
                     ).astype(np.float32)
        self.syn1neg = np.zeros((V, D), np.float32)
        max_inner = max(V, 2)
        self.syn1 = np.zeros((max_inner, D), np.float32)
        self._init_aux_tables()

    def _init_aux_tables(self):
        """Sampler + Huffman lookup state derived from the vocab. Split
        from `_init_tables` so a model warm-started from
        `WordVectorSerializer` (which restores vocab + syn0 and zeroed
        output tables, but none of this derived state) can resume
        `fit()` without resetting its trained embeddings."""
        V = self.vocab.num_words()
        D = self.syn0.shape[1]
        # guards for manually-assembled models (syn0/vocab set directly)
        if self.syn1neg is None:
            self.syn1neg = np.zeros((V, D), np.float32)
        if self.syn1 is None:
            self.syn1 = np.zeros((max(V, 2), D), np.float32)
        # deserialized vocabs carry no Huffman codes — without this, HS
        # warm-start training would be fully masked out (a silent no-op)
        if V > 1 and all(not self.vocab.element_at_index(i).codes
                         for i in range(V)):
            from deeplearning4j_tpu.nlp.vocab import build_huffman
            build_huffman(self.vocab)
        # unigram^0.75 negative-sampling distribution (word2vec standard)
        self._freqs = np.array([self.vocab.element_at_index(i).frequency
                                for i in range(V)])
        probs = self._freqs ** self.conf.unigram_power
        self._neg_cdf = np.cumsum(probs / probs.sum())
        self._neg_cdf[-1] = 1.0
        # quantized unigram table: one searchsorted at build time, O(1)
        # integer draws afterwards (the reference's negative table idea;
        # per-draw CDF searchsorted measured at 40% of steady-state fit)
        tsize = max(1 << 20, 16 * V)
        self._neg_table = np.searchsorted(
            self._neg_cdf, (np.arange(tsize) + 0.5) / tsize).astype(np.int32)
        # Huffman paths as dense [V, C] tables → batch assembly is pure
        # fancy indexing (fixed pad width keeps XLA shapes static)
        C = max((len(self.vocab.element_at_index(i).codes)
                 for i in range(V)), default=1) or 1
        self._max_code = C
        self._hs_points = np.zeros((V, C), np.int32)
        self._hs_codes = np.zeros((V, C), np.float32)
        self._hs_mask = np.zeros((V, C), np.float32)
        for i in range(V):
            vw = self.vocab.element_at_index(i)
            L = len(vw.codes)
            if L:
                self._hs_points[i, :L] = vw.points
                self._hs_codes[i, :L] = vw.codes
                self._hs_mask[i, :L] = 1.0

    # ------------------------------------------------------- pair batching
    def _tokens_to_indices(self, tokens: Sequence[str]) -> np.ndarray:
        """Vocab lookup + frequent-word subsampling, vectorised."""
        conf = self.conf
        idx_of = self.vocab.index_of
        idxs = np.fromiter((idx_of(t) for t in tokens), np.int64, len(tokens))
        idxs = idxs[idxs >= 0]
        if conf.subsampling > 0 and self.vocab.total_word_count > 0 and len(idxs):
            f = self._freqs[idxs] / self.vocab.total_word_count
            keep_p = (np.sqrt(f / conf.subsampling) + 1) * conf.subsampling / f
            idxs = idxs[self._rng.random(len(idxs)) < keep_p]
        return idxs

    def _sequence_to_pair_arrays(self, tokens: Sequence[str]):
        """Skip-gram (center, context) arrays with the reduced-window
        trick, fully vectorised (no per-position Python loop)."""
        conf = self.conf
        idxs = self._tokens_to_indices(tokens)
        n = len(idxs)
        if n < 2:
            return None
        b = self._rng.integers(1, conf.window + 1, n)
        pos = np.arange(n)
        cs, xs = [], []
        for off in range(1, conf.window + 1):
            ok = b >= off
            left = np.nonzero(ok & (pos >= off))[0]
            cs.append(idxs[left]); xs.append(idxs[left - off])
            right = np.nonzero(ok & (pos + off < n))[0]
            cs.append(idxs[right]); xs.append(idxs[right + off])
        return (np.concatenate(cs).astype(np.int32),
                np.concatenate(xs).astype(np.int32))

    def _sequence_to_pairs(self, tokens: Sequence[str]):
        """CBOW pair lists: (center, center, ctx_indices)."""
        conf = self.conf
        idxs = self._tokens_to_indices(tokens).tolist()
        pairs = []
        n = len(idxs)
        for p, center in enumerate(idxs):
            bb = int(self._rng.integers(1, conf.window + 1))
            ctx = idxs[max(0, p - bb):p] + idxs[p + 1:p + bb + 1]
            if ctx:
                pairs.append((center, center, ctx))
        return pairs

    def _sample_negatives(self, B: int) -> np.ndarray:
        # own stream, not self._rng: negatives are drawn at FLUSH time
        # (consumer side) while the pair packer may be running on the
        # producer thread — one shared generator would race and break
        # sync/async determinism parity
        if self._negs_rng is None:
            self._negs_rng = np.random.default_rng(self.conf.seed + 0x5EED)
        K = max(self.conf.negative, 1)
        idx = self._negs_rng.integers(0, len(self._neg_table), (B, K))
        return self._neg_table[idx]

    def _mesh_steps(self):
        """Sharded jit variants of the skip-gram/neg steps (built lazily:
        batch dims shard over `data_axis`, tables replicate)."""
        if self._sharded_step is None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            mesh = self.mesh
            repl = NamedSharding(mesh, P())
            b1 = NamedSharding(mesh, P(self.data_axis))
            b2 = NamedSharding(mesh, P(None, self.data_axis))
            bk = NamedSharding(mesh, P(self.data_axis, None))
            b3 = NamedSharding(mesh, P(None, self.data_axis, None))
            self._sharded_step = jax.jit(
                _sg_neg_math, static_argnums=(6,), donate_argnums=_donate(0, 1),
                in_shardings=(repl, repl, b1, b1, bk, None),
                out_shardings=(repl, repl, None))
            self._sharded_multi = jax.jit(
                _sg_neg_scan, static_argnums=(6,), donate_argnums=_donate(0, 1),
                in_shardings=(repl, repl, b2, b2, b3, None),
                out_shardings=(repl, repl, None))
        return self._sharded_step, self._sharded_multi

    def _flush_sg_neg(self, centers, contexts, lr):
        step = _sg_neg_step
        if self.mesh is not None and len(centers) % self.mesh.size == 0:
            # ragged tails (not divisible by the mesh) run unsharded —
            # replicated tables make that transparently correct
            step, _ = self._mesh_steps()
        self.syn0, self.syn1neg, loss = step(
            self.syn0, self.syn1neg, centers, contexts,
            self._sample_negatives(len(centers)),
            np.float32(lr), self._trainable_from)
        return loss

    def _flush_sg_neg_multi(self, centers, contexts, lrs):
        """centers/contexts: [k,B]; lrs: [k]. One fused dispatch, no
        host sync — the loss comes back as a device array."""
        multi = _sg_neg_multi
        if self.mesh is not None and centers.shape[1] % self.mesh.size == 0:
            _, multi = self._mesh_steps()
        k, B = centers.shape
        negs = self._sample_negatives(k * B).reshape(k, B, -1)
        self.syn0, self.syn1neg, loss = multi(
            self.syn0, self.syn1neg, centers, contexts, negs,
            lrs.astype(np.float32), self._trainable_from)
        return loss

    def _pack_cbow(self, pairs):
        # +1 slot so a DM label row fits even at the max reduced window
        W2 = 2 * self.conf.window + 1
        B = len(pairs)
        ctx = np.zeros((B, W2), np.int32)
        mask = np.zeros((B, W2), np.float32)
        centers = np.zeros((B,), np.int32)
        for i, (center, _, cs) in enumerate(pairs):
            centers[i] = center
            cs = cs[:W2]
            ctx[i, :len(cs)] = cs
            mask[i, :len(cs)] = 1.0
        return ctx, mask, centers

    def _flush_cbow_neg(self, pairs, lr):
        ctx, mask, centers = self._pack_cbow(pairs)
        self.syn0, self.syn1neg, loss = _cbow_neg_step(
            self.syn0, self.syn1neg, ctx, mask, centers,
            self._sample_negatives(len(pairs)),
            np.float32(lr), self._trainable_from)
        return loss

    def _flush_cbow_hs(self, pairs, lr):
        ctx, mask, centers = self._pack_cbow(pairs)
        self.syn0, self.syn1, loss = _cbow_hs_step(
            self.syn0, self.syn1, ctx, mask, centers,
            self._hs_points[centers], self._hs_codes[centers],
            self._hs_mask[centers], np.float32(lr))
        return loss

    def _flush_sg_hs(self, centers, contexts, lr):
        # Huffman paths precomputed as [V, C] tables → pure array indexing
        self.syn0, self.syn1, loss = _sg_hs_step(
            self.syn0, self.syn1, centers,
            self._hs_points[contexts], self._hs_codes[contexts],
            self._hs_mask[contexts], np.float32(lr))
        return loss

    def _flush_cbow_neg_tail(self, pairs, lr):
        B = self.conf.batch_size
        n = len(pairs)
        if n == B:
            return self._flush_cbow_neg(pairs, lr)
        padded = pairs + [(0, 0, ())] * (B - n)   # empty ctx -> zero mask
        ctx, mask, centers = self._pack_cbow(padded)
        valid = self._valid_mask(B, n)
        negs = np.zeros((B, max(self.conf.negative, 1)), np.int32)
        negs[:n] = self._sample_negatives(n)      # rng stream == ragged path
        self.syn0, self.syn1neg, loss = _cbow_neg_step_masked(
            self.syn0, self.syn1neg, ctx, mask, centers, negs,
            np.float32(lr), self._trainable_from, valid)
        return loss

    def _flush_cbow_hs_tail(self, pairs, lr):
        B = self.conf.batch_size
        n = len(pairs)
        if n == B:
            return self._flush_cbow_hs(pairs, lr)
        padded = pairs + [(0, 0, ())] * (B - n)
        ctx, mask, centers = self._pack_cbow(padded)
        valid = self._valid_mask(B, n)
        self.syn0, self.syn1, loss = _cbow_hs_step_masked(
            self.syn0, self.syn1, ctx, mask, centers,
            self._hs_points[centers], self._hs_codes[centers],
            self._hs_mask[centers], np.float32(lr), valid)
        return loss

    # Ragged epoch-end tails run PADDED to the compiled [B] shape with a
    # validity mask (exact math, see `_sg_neg_math`): without this,
    # every distinct tail length costs a fresh XLA compile — measured at
    # ~0.6 s per fit on the word2vec bench, since the reduced-window rng
    # makes each epoch's tail length unique.
    @staticmethod
    def _valid_mask(B, n):
        valid = np.zeros(B, np.float32)
        valid[:n] = 1.0
        return valid

    def _pad_tail(self, centers, contexts):
        B = self.conf.batch_size
        n = len(centers)
        pc = np.zeros(B, np.int32); pc[:n] = centers
        px = np.zeros(B, np.int32); px[:n] = contexts
        return pc, px, self._valid_mask(B, n)

    def _warm_drain_executables(self, use_hs, array_path):
        """Pre-compile every drain executable a fit can reach. Which
        shapes a given fit hits depends on the subsampling rng — a >=B
        epoch tail drains per-batch [B], a ragged tail hits the masked
        step — so without this a late tail can stall mid-fit on a fresh
        XLA compile (seconds on a TPU), landing inside a
        user's or the bench's steady-state window. Zero-lr, zero-index
        calls at the exact production avals; outputs are assigned back
        (lr=0 makes the update an exact no-op on finite tables) because
        the steps donate the table buffers. No host rng is consumed, so
        seeded training streams are unchanged. Mesh-sharded fits skip
        this: their drain set depends on divisibility and is exercised
        on virtual devices where compiles are cheap. Inference-mode fits
        (trainable_from > 0, i.e. infer_vector over one document) skip
        it too: their pair count is a document, not a corpus, so they
        only ever touch the masked tail step — pre-compiling the full-
        batch executables they cannot reach would ADD a compile stall."""
        if self.mesh is not None or self._trainable_from > 0:
            return
        B = self.conf.batch_size
        key = (self.syn0.shape, B, bool(use_hs), bool(array_path),
               self._trainable_from)
        # the skip additionally requires device-resident tables: jit
        # caches on argument sharding, so host-resident tables (fresh
        # _init_tables — the normal start of every fit) must be warmed
        # through to device arrays again or the first real flush of a
        # refit compiles a second, host-input cache entry
        if self._warmed_key == key and not isinstance(self.syn0, np.ndarray):
            return
        lr0 = np.float32(0.0)
        zc = np.zeros(B, np.int32)
        zvalid = self._valid_mask(B, 0)
        zn = np.zeros((B, max(self.conf.negative, 1)), np.int32)
        if array_path:
            if use_hs:
                pts, cds, msk = (self._hs_points[zc], self._hs_codes[zc],
                                 self._hs_mask[zc])
                self.syn0, self.syn1, _ = _sg_hs_step(
                    self.syn0, self.syn1, zc, pts, cds, msk, lr0)
                self.syn0, self.syn1, _ = _sg_hs_step_masked(
                    self.syn0, self.syn1, zc, pts, cds, msk, lr0, zvalid)
            else:
                self.syn0, self.syn1neg, _ = _sg_neg_step(
                    self.syn0, self.syn1neg, zc, zc, zn, lr0,
                    self._trainable_from)
                self.syn0, self.syn1neg, _ = _sg_neg_step_masked(
                    self.syn0, self.syn1neg, zc, zc, zn, lr0,
                    self._trainable_from, zvalid)
        else:
            W2 = 2 * self.conf.window + 1
            zctx = np.zeros((B, W2), np.int32)
            zmask = np.zeros((B, W2), np.float32)
            if use_hs:
                pts, cds, msk = (self._hs_points[zc], self._hs_codes[zc],
                                 self._hs_mask[zc])
                self.syn0, self.syn1, _ = _cbow_hs_step(
                    self.syn0, self.syn1, zctx, zmask, zc, pts, cds, msk,
                    lr0)
                self.syn0, self.syn1, _ = _cbow_hs_step_masked(
                    self.syn0, self.syn1, zctx, zmask, zc, pts, cds, msk,
                    lr0, zvalid)
            else:
                self.syn0, self.syn1neg, _ = _cbow_neg_step(
                    self.syn0, self.syn1neg, zctx, zmask, zc, zn, lr0,
                    self._trainable_from)
                self.syn0, self.syn1neg, _ = _cbow_neg_step_masked(
                    self.syn0, self.syn1neg, zctx, zmask, zc, zn, lr0,
                    self._trainable_from, zvalid)
        self._warmed_key = key

    def _flush_sg_neg_tail(self, centers, contexts, lr):
        if len(centers) == self.conf.batch_size:
            return self._flush_sg_neg(centers, contexts, lr)
        pc, px, valid = self._pad_tail(centers, contexts)
        # negatives drawn for the REAL entries only: the host rng stream
        # stays identical to a ragged-shape flush, so results match the
        # unpadded path exactly (padded rows are masked out anyway)
        negs = np.zeros((len(pc), max(self.conf.negative, 1)), np.int32)
        negs[:len(centers)] = self._sample_negatives(len(centers))
        self.syn0, self.syn1neg, loss = _sg_neg_step_masked(
            self.syn0, self.syn1neg, pc, px, negs, np.float32(lr),
            self._trainable_from, valid)
        return loss

    def _flush_sg_hs_tail(self, centers, contexts, lr):
        if len(centers) == self.conf.batch_size:
            return self._flush_sg_hs(centers, contexts, lr)
        pc, px, valid = self._pad_tail(centers, contexts)
        self.syn0, self.syn1, loss = _sg_hs_step_masked(
            self.syn0, self.syn1, pc, self._hs_points[px],
            self._hs_codes[px], self._hs_mask[px],
            np.float32(lr), valid)
        return loss

    # ----------------------------------------------------------------- fit
    def fit(self, sequences, extra_rows: int = 0, trainable_from: int = 0,
            pair_hook=None, total_words: Optional[int] = None):
        """Train. `sequences`: iterable (re-iterable across epochs) of
        token lists. Returns self."""
        conf = self.conf
        if self.vocab is None:
            self.build_vocab(sequences)
        warm_start = self.syn0 is not None and self._neg_table is None
        if self.syn0 is None or (not warm_start and extra_rows and
                                 self.syn0.shape[0] == self.vocab.num_words()):
            self._init_tables(extra_rows)
        elif warm_start:
            # warm start (deserialized model): vocab + syn0 exist but the
            # sampler/Huffman state was never built. Keep the trained
            # embeddings; label rows (ParagraphVectors) are appended, not
            # re-randomized with the rest of the table.
            if extra_rows and self.syn0.shape[0] == self.vocab.num_words():
                D = self.syn0.shape[1]
                new_rows = ((self._rng.random((extra_rows, D)) - 0.5) / D
                            ).astype(np.float32)
                self.syn0 = np.concatenate([np.asarray(self.syn0), new_rows])
            self._init_aux_tables()
        self._trainable_from = trainable_from

        use_hs = conf.use_hierarchic_softmax or conf.negative <= 0
        array_path = not conf.cbow  # skip-gram variants carry index arrays
        sg_flush = self._flush_sg_hs if use_hs else self._flush_sg_neg
        sg_flush_tail = (self._flush_sg_hs_tail if use_hs
                         else self._flush_sg_neg_tail)
        cbow_flush = self._flush_cbow_hs if use_hs else self._flush_cbow_neg
        cbow_flush_tail = (self._flush_cbow_hs_tail if use_hs
                           else self._flush_cbow_neg_tail)

        # lr decays linearly over the full corpus; when the training
        # corpus differs from the vocab-construction corpus (graph
        # walks vs degree sequences), the caller passes the real size.
        # For in-memory corpora the exact size is one cheap pass — this
        # also keeps warm-started models (whose deserialized vocab has
        # no real counts) from collapsing the lr schedule immediately.
        if total_words is None and isinstance(sequences, (list, tuple)):
            total_words = sum(len(s) for s in sequences)
        if total_words is None:
            total_words = self.vocab.total_word_count
        corpus_words = total_words
        total_words = max(total_words * conf.epochs, 1)
        # warm only when a full-batch flush is reachable: an epoch emits
        # at most 2*window pairs per center word (1 for CBOW), so a
        # corpus whose pair upper bound is below B can only ever hit the
        # masked tail step — pre-compiling [B] executables for it would
        # ADD the compile stall this exists to remove. pair_hook makes
        # the count uncallerable, so it always warms.
        pairs_per_word = 1 if conf.cbow else 2 * conf.window
        if (pair_hook is not None
                or corpus_words * pairs_per_word >= conf.batch_size):
            self._warm_drain_executables(use_hs, array_path)
        self.last_loss = 0.0
        self.etl_stats = None   # per-fit accounting — never stale
        loss_dev = None      # device-side last loss — read ONCE after fit
        B = conf.batch_size
        # fused flush group: skip-gram/neg drains k batches per dispatch;
        # HS and iterations>1 keep per-batch flushes
        k_group = (max(1, conf.steps_per_flush)
                   if (array_path and not use_hs and conf.iterations == 1)
                   else 1)
        if array_path:
            items = self._pair_work_items(sequences, pair_hook, total_words,
                                          k_group)
            # AsyncSequencer role: pair packing on a producer thread,
            # overlapped with the (async) device dispatches. pair_hook
            # runs arbitrary user code against self — keep it on the
            # caller's thread.
            use_async = conf.async_producer and pair_hook is None
            if use_async:
                items = self._produce_async(items)
            loss_dev = self._drain_items(items, sg_flush, sg_flush_tail,
                                         conf.iterations)
        else:
            loss_dev = self._fit_cbow_list_path(
                sequences, pair_hook, total_words, cbow_flush,
                cbow_flush_tail)
        self.syn0 = np.asarray(self.syn0)
        self.syn1 = np.asarray(self.syn1)
        self.syn1neg = np.asarray(self.syn1neg)
        if loss_dev is not None:
            self.last_loss = float(loss_dev)
        return self

    def _pair_work_items(self, sequences, pair_hook, total_words, k_group):
        """Generator of flush work items for the skip-gram array path:
        ("group", c[k,B], x[k,B], lrs[k]) fused groups, ("single",
        c[B], x[B], lr) compiled-shape batches, ("tail", c[<B], x[<B],
        lr) one ragged flush per epoch."""
        conf = self.conf
        B = conf.batch_size
        words_seen = 0
        lr_prev = conf.learning_rate
        for epoch in range(conf.epochs):
            abuf_c, abuf_x, abuf_n = [], [], 0
            for si, tokens in enumerate(sequences):
                frac = words_seen / total_words
                lr = max(conf.learning_rate * (1.0 - frac),
                         conf.min_learning_rate)
                words_seen += len(tokens)
                if pair_hook is not None:
                    new = pair_hook(self, si, tokens)
                    if isinstance(new, list):
                        if not new:
                            continue
                        new = (np.fromiter((p[0] for p in new), np.int32,
                                           len(new)),
                               np.fromiter((p[1] for p in new), np.int32,
                                           len(new)))
                else:
                    new = self._sequence_to_pair_arrays(tokens)
                if new is None:
                    continue
                abuf_c.append(new[0])
                abuf_x.append(new[1])
                abuf_n += len(new[0])
                while abuf_n >= k_group * B:
                    cs = np.concatenate(abuf_c)
                    xs = np.concatenate(abuf_x)
                    take = k_group * B
                    batch_c, rest_c = cs[:take], cs[take:]
                    batch_x, rest_x = xs[:take], xs[take:]
                    abuf_c, abuf_x, abuf_n = [rest_c], [rest_x], len(rest_c)
                    if k_group > 1:
                        # lr interpolated across the group — same decay
                        # granularity the per-batch path would apply
                        lrs = np.linspace(lr_prev, lr, k_group,
                                          dtype=np.float32)
                        yield ("group", batch_c.reshape(k_group, B),
                               batch_x.reshape(k_group, B), lrs)
                    else:
                        yield ("single", batch_c, batch_x, lr)
                    lr_prev = lr
            tail_lr = max(conf.learning_rate * (1 - words_seen / total_words),
                          conf.min_learning_rate)
            if abuf_n:
                cs = np.concatenate(abuf_c)
                xs = np.concatenate(abuf_x)
                # drain full-B batches at the compiled shape, then one
                # ragged tail flush
                while len(cs) >= B:
                    yield ("single", cs[:B], xs[:B], tail_lr)
                    cs, xs = cs[B:], xs[B:]
                if len(cs):
                    yield ("tail", cs, xs, tail_lr)

    def _produce_async(self, items):
        """Run the work-item generator on a producer thread through a
        bounded queue (AsyncSequencer, `SequenceVectors.java:288`).
        Wait accounting lands in `self.etl_stats`: consumer_wait_ms is
        time the device-feeding side starved for host packing (the
        number to drive to ~0), producer_wait_ms is host time absorbed
        by the queue bound while the device was busy (healthy)."""
        import queue as _queue
        import threading

        q = _queue.Queue(maxsize=max(1, self.conf.producer_queue_depth))
        stats = {"producer_wait_ms": 0.0, "consumer_wait_ms": 0.0,
                 "mode": "async"}
        self.etl_stats = stats
        DONE = object()
        stop = threading.Event()   # consumer abandoned (flush raised)

        def produce():
            try:
                for item in items:
                    t0 = time.perf_counter()
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.25)
                            break
                        except _queue.Full:
                            continue
                    if stop.is_set():
                        return
                    stats["producer_wait_ms"] += (
                        (time.perf_counter() - t0) * 1e3)
                q.put(DONE)
            except BaseException as e:   # surface in the consumer
                q.put(("__error__", e))

        t = threading.Thread(target=produce, daemon=True,
                             name="sequencevectors-producer")
        t.start()
        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                stats["consumer_wait_ms"] += (time.perf_counter() - t0) * 1e3
                if item is DONE:
                    break
                if isinstance(item, tuple) and item[0] == "__error__":
                    raise item[1]
                yield item
        finally:
            # a raising flush closes this generator mid-iteration: wake
            # the producer out of its bounded put so the thread (and
            # its queued batches) cannot leak
            stop.set()
            t.join()

    def _drain_items(self, items, sg_flush, sg_flush_tail, iterations):
        loss_dev = None
        if self.etl_stats is None:
            self.etl_stats = {"mode": "sync"}
        for kind, c, x, lr in items:
            if kind == "group":
                loss_dev = self._flush_sg_neg_multi(c, x, lr)
            elif kind == "single":
                for _ in range(iterations):
                    loss_dev = sg_flush(c, x, lr)
            else:
                for _ in range(iterations):
                    loss_dev = sg_flush_tail(c, x, lr)
        return loss_dev

    def _fit_cbow_list_path(self, sequences, pair_hook, total_words,
                            cbow_flush, cbow_flush_tail):
        conf = self.conf
        B = conf.batch_size
        words_seen = 0
        loss_dev = None
        for epoch in range(conf.epochs):
            lbuf = []
            for si, tokens in enumerate(sequences):
                frac = words_seen / total_words
                lr = max(conf.learning_rate * (1.0 - frac),
                         conf.min_learning_rate)
                words_seen += len(tokens)
                if pair_hook is not None:
                    new = pair_hook(self, si, tokens)
                else:
                    new = self._sequence_to_pairs(tokens)
                lbuf.extend(new)
                while len(lbuf) >= B:
                    batch, lbuf = lbuf[:B], lbuf[B:]
                    for _ in range(conf.iterations):
                        loss_dev = cbow_flush(batch, lr)
            tail_lr = max(conf.learning_rate * (1 - words_seen / total_words),
                          conf.min_learning_rate)
            if lbuf:
                for _ in range(conf.iterations):
                    loss_dev = cbow_flush_tail(lbuf, tail_lr)
        return loss_dev

    # ------------------------------------------------------------- queries
    def get_word_vector(self, word: str):
        i = self.vocab.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])

    def has_word(self, word: str) -> bool:
        return self.vocab is not None and self.vocab.contains_word(word)

    def _unit_table(self):
        t = np.asarray(self.syn0[:self.vocab.num_words()])
        norms = np.linalg.norm(t, axis=1, keepdims=True)
        return t / np.clip(norms, 1e-9, None)

    def similarity(self, w1: str, w2: str) -> float:
        v1, v2 = self.get_word_vector(w1), self.get_word_vector(w2)
        if v1 is None or v2 is None:
            return float("nan")
        denom = np.linalg.norm(v1) * np.linalg.norm(v2)
        return float(np.dot(v1, v2) / denom) if denom > 0 else 0.0

    def words_nearest(self, word_or_vec, top_n: int = 10) -> List[str]:
        if isinstance(word_or_vec, str):
            vec = self.get_word_vector(word_or_vec)
            exclude = {word_or_vec}
        else:
            vec, exclude = np.asarray(word_or_vec), set()
        if vec is None:
            return []
        unit = self._unit_table()
        q = vec / max(np.linalg.norm(vec), 1e-9)
        sims = unit @ q
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.vocab.word_at_index(int(i))
            if w not in exclude:
                out.append(w)
            if len(out) >= top_n:
                break
        return out
