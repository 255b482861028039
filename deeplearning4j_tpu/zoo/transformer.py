"""Transformer encoder zoo models (beyond-reference: the 2017 zoo tops
out at InceptionResNet/LSTMs; this is the long-context flagship the TPU
rebuild adds, riding the Pallas flash-attention fast path and — over a
mesh — ring/Ulysses sequence parallelism).

Two configurations:
- `TransformerClassifier`: token ids → embedding + positions → N
  encoder blocks → masked global average pool → softmax.
- `TransformerLM`: causal blocks → per-position softmax over the
  vocabulary (RnnOutputLayer), the TextGenerationLSTM successor.
"""

from __future__ import annotations

from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.common.weights import WeightInit
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (
    EmbeddingLayer,
    GlobalPoolingLayer,
    OutputLayer,
    PositionalEncodingLayer,
    RnnOutputLayer,
    TransformerEncoderBlock,
)
from deeplearning4j_tpu.nn.layers.pooling import PoolingType
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.zoo.base import ZooModel


class TransformerClassifier(ZooModel):
    def __init__(self, vocab_size: int, num_classes: int, *,
                 d_model: int = 128, n_layers: int = 2, n_heads: int = 8,
                 ff_multiplier: int = 4, max_len: int = 512,
                 dropout: float = None, pooling: PoolingType = PoolingType.AVG,
                 remat: bool = False, remat_policy: str = None,
                 sequence_parallel: str = None, seed: int = 123):
        super().__init__(num_classes=num_classes, seed=seed)
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.ff_multiplier = ff_multiplier
        self.max_len = max_len
        self.dropout = dropout
        self.pooling = pooling
        self.remat = remat
        self.remat_policy = remat_policy
        self.sequence_parallel = sequence_parallel

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(Adam(1e-3))
             .weight_init(WeightInit.XAVIER)
             .list()
             .layer(EmbeddingLayer(n_in=self.vocab_size, n_out=self.d_model))
             .layer(PositionalEncodingLayer(max_len=self.max_len)))
        # the block run scans by default (scan-over-layers — identical
        # blocks roll into one lax.scan; nn/scan_stack.py)
        for _ in range(self.n_layers):
            b.layer(TransformerEncoderBlock(
                n_heads=self.n_heads, ff_multiplier=self.ff_multiplier,
                dropout=self.dropout, remat=self.remat,
                remat_policy=self.remat_policy,
                sequence_parallel=self.sequence_parallel))
        b.layer(GlobalPoolingLayer(pooling_type=self.pooling))
        b.layer(OutputLayer(n_out=self.num_classes, activation="softmax",
                            loss="mcxent"))
        b.set_input_type(InputType.recurrent(self.vocab_size))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init(self.seed)


class TransformerLM(ZooModel):
    def __init__(self, vocab_size: int, *, d_model: int = 128,
                 n_layers: int = 2, n_heads: int = 8,
                 ff_multiplier: int = 4, max_len: int = 512,
                 remat: bool = False, remat_policy: str = None,
                 sequence_parallel: str = None, seed: int = 123):
        super().__init__(num_classes=vocab_size, seed=seed)
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.ff_multiplier = ff_multiplier
        self.max_len = max_len
        self.remat = remat
        self.remat_policy = remat_policy
        self.sequence_parallel = sequence_parallel

    def conf(self):
        b = (NeuralNetConfiguration.builder()
             .seed(self.seed).updater(Adam(1e-3))
             .weight_init(WeightInit.XAVIER)
             .list()
             .layer(EmbeddingLayer(n_in=self.vocab_size, n_out=self.d_model))
             .layer(PositionalEncodingLayer(max_len=self.max_len)))
        # identical causal blocks — the containers roll this run into
        # one lax.scan by default (scan-over-layers, nn/scan_stack.py)
        for _ in range(self.n_layers):
            b.layer(TransformerEncoderBlock(
                n_heads=self.n_heads, ff_multiplier=self.ff_multiplier,
                causal=True, remat=self.remat,
                remat_policy=self.remat_policy, cache_len=self.max_len,
                sequence_parallel=self.sequence_parallel))
        b.layer(RnnOutputLayer(n_out=self.vocab_size, activation="softmax",
                               loss="mcxent"))
        b.set_input_type(InputType.recurrent(self.vocab_size))
        return b.build()

    def init(self) -> MultiLayerNetwork:
        return MultiLayerNetwork(self.conf()).init(self.seed)


def _check_cache_budget(net, prompt_len: int, n_tokens: int):
    """The fixed-size KV caches silently clamp writes past their length
    (dynamic_update_slice semantics), which would corrupt every token
    beyond the limit while still emitting valid-looking ids — so both
    decoders enforce the budget eagerly where the lengths are known."""
    from deeplearning4j_tpu.nn.layers.transformer import stream_budget
    budget = stream_budget(net.layers)
    total = prompt_len + n_tokens
    if budget is not None and total > budget:
        raise ValueError(
            f"prompt ({prompt_len}) + n_tokens ({n_tokens}) = {total} "
            f"exceeds the decode budget {budget} (min over KV cache "
            f"lengths and positional-encoding max_len); decode fewer "
            f"tokens or rebuild with a larger max_len")


def filter_logits(logits, top_k, top_p):
    """Shared vocabulary filters for sampled decoding — `generate()`'s
    fused scan AND the serving engine's per-slot sampler run THIS body
    (one copy; the chains must not drift). `top_k` is static
    (lax.top_k), `top_p` rides TRACED — a scalar (generate: sweeping p
    reuses one executable) or a per-row column (serving: per-slot p).
    Nucleus rule: keep tokens whose PRECEDING cumulative mass is < p
    (the most probable token always survives)."""
    import jax
    import jax.numpy as jnp

    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    if top_p is not None:
        sorted_l = jnp.sort(logits, axis=-1)[..., ::-1]
        sp = jax.nn.softmax(sorted_l, axis=-1)
        keep_sorted = (jnp.cumsum(sp, axis=-1) - sp) < top_p
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_l, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits >= cutoff, logits, -jnp.inf)
    return logits


def get_prefill(net: MultiLayerNetwork):
    """The cached prompt-prefill jit shared by `generate`, `beam_search`
    and the serving tier's admission path (serving/engine.py): one XLA
    program per (batch, prompt-length) shape that runs the full forward
    with KV-cache carries and returns ([B, V] next-token probs, the
    filled carries). Int8-quantized params trees (nd/quant.py) key
    their own trace of the same jit — the program then reads int8
    weights from HBM."""
    import jax

    jit_cache = net.__dict__.setdefault("_transformer_gen_jit", {})
    if "prefill" not in jit_cache:
        @jax.jit
        def prefill(params, state, x, carries):
            h, _, new_carries, _, _ = net._forward_core(
                params, state, x, train=False, rng=None, carries=carries)
            return h[:, -1], new_carries      # [B, V] next-token probs
        jit_cache["prefill"] = prefill
    return jit_cache["prefill"]


def get_prefill_bucketed(net: MultiLayerNetwork):
    """Mixed-length prefill (the serving tier's bucketed admission
    waves, serving/engine.py): `x` [B, Pb] holds prompts RIGHT-padded
    to a shared bucket length and `last_idx` [B] each row's final real
    position (`P_b - 1`). Returns that row's next-token probs plus the
    filled carries.

    Right padding is sound because the blocks are causal: position
    `P_b - 1`'s activations never see the padding tokens behind it,
    and the padding rows' K/V land at cache positions `>= P_b` which
    every later read masks by the slot's own position (the same
    0-weight-x-garbage invariant the paged pool rests on). The probs
    gather is the only difference from `get_prefill` — the forward is
    the same program family."""
    import jax
    import jax.numpy as jnp

    jit_cache = net.__dict__.setdefault("_transformer_gen_jit", {})
    if "prefill_bucketed" not in jit_cache:
        @jax.jit
        def prefill_bucketed(params, state, x, carries, last_idx):
            h, _, new_carries, _, _ = net._forward_core(
                params, state, x, train=False, rng=None, carries=carries)
            probs = h[jnp.arange(h.shape[0]), last_idx]   # [B, V]
            return probs, new_carries
        jit_cache["prefill_bucketed"] = prefill_bucketed
    return jit_cache["prefill_bucketed"]


def paged_score_forward(net, plan, params, state, kv, block_tables,
                        token_mat, pos, n_valid):
    """The K-POSITION score forward over the paged pool — one program
    scoring k proposed tokens per slot instead of k programs (the
    dataflow-batching argument applied to the decode loop): the target
    model's half of speculative decoding AND the suffix-extension
    prefill of copy-on-write shared-prefix admission
    (serving/engine.py; docs/SERVING.md).

    `token_mat` [S, K] holds K consecutive tokens per slot occupying
    positions `pos[s] .. pos[s]+K-1`; `n_valid` [S] bounds each slot's
    real lanes (0 = slot sits this dispatch out — its writes land in
    the garbage block, its output rows are discarded). `plan` is the
    engine's layer walk (("plain"|"pos", i) / ("block", i, pool_j), or
    ("block", i, pool_j, kind) where `block_tables` is the (full,
    window) pair of a net with two kinds of pool).
    Returns (kv', probs [S, K, V]) where probs[s, j] is the target's
    next-token distribution AFTER consuming token j — per-lane
    bit-equal to K sequential single-token decode dispatches, which is
    the acceptance oracle's whole foundation. Lives next to
    `get_prefill`/`get_prefill_bucketed` because it is the same program
    family: the engine jits it per (K, sampling-variant)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.serving.paged import plan_table

    layers = net.layers
    K = token_mat.shape[1]
    positions = pos[:, None] + jnp.arange(K)[None, :]    # [S, K]
    h = token_mat                                        # [S, K] int ids
    kv = list(kv)
    for entry in plan:
        kind, i = entry[0], entry[1]
        layer = layers[i]
        lp = params.get(str(i), {})
        ls = state.get(str(i), {})
        if kind == "plain":
            h, _ = layer.forward(lp, ls, h, train=False, rng=None)
        elif kind == "pos":
            h, _ = layer.forward_at_positions(lp, ls, h, positions)
        else:
            j = entry[2]
            h, kv[j] = layer.paged_step_multi(
                lp, h, kv[j], plan_table(block_tables, entry), pos, n_valid)
    return tuple(kv), h                                  # [S, K, V]


def rejection_sample_drafts(probs, token_mat, n_valid, keys, emit_idx,
                            temp, top_p, top_k):
    """Speculative REJECTION SAMPLING over delta drafts — the sampled
    counterpart of the greedy acceptance oracle (arXiv:2211.17192
    specialized to point-mass draft distributions; serving/engine.py's
    `_spec_step` sampled path; docs/SERVING.md).

    Both proposers (n-gram suffix cache, truncated-layer drafter) emit
    CONCRETE tokens, i.e. the draft distribution is a delta at the
    proposed id `d`. The general rule — accept with prob
    `min(1, p_t(x)/p_d(x))`, on rejection resample from the normalized
    residual `max(0, p_t - p_d)` — then collapses to: accept draft `d`
    with prob `q_t(d)`, and the residual is `q_t` with `d` masked out,
    where `q_t` is the TARGET's filtered/tempered distribution (the
    exact `filter_logits(log(p)/T, top_k, top_p)` chain `_sample_ids`
    runs — one copy, no drift). Each emitted token is marginally
    distributed as a vanilla sample from `q_t` given its prefix (the
    chi-square harness in tests/test_serving_statistical.py holds this
    to a distributional contract), and the acceptance identity
    `E[#accepted at lane j] = sum_x min(q_t(x), p_d(x)) = q_t(d)`
    falls out of the delta specialization (unit-tested).

    Randomness keys off the SAME per-slot chain as vanilla decode —
    position t consumes `fold_in(key, emit_idx + t)` — with sub-folds
    (1 = acceptance uniform, 2 = resample/bonus categorical) so one
    position's accept test and its resample draw are independent.
    Fully deterministic under fixed keys.

    `probs` [S, K, V] from `paged_score_forward` (probs[s, j] is the
    target distribution AFTER consuming token_mat[s, j]); lanes
    `1..n_valid-1` of `token_mat` are drafts. Rows with `temp == 0`
    are computed under a guard temperature and their outputs ignored —
    the host keeps greedy slots on the bit-exact argmax oracle.
    Zero-support drafts (q_t(d) = 0, e.g. filtered out by top-k) are
    always rejected: `u ~ U[0,1) < 0` never fires. Returns
    `(n_acc [S], final [S])`: the count of leading accepted drafts and
    the resampled/bonus token at lane `n_acc` — the slot emits
    `n_acc + 1` tokens. Only these two small vectors cross d2h."""
    import jax
    import jax.numpy as jnp

    S, K, V = probs.shape
    safe_t = jnp.where(temp > 0, temp, 1.0)[:, None, None]
    logits = jnp.log(jnp.clip(probs, 1e-9)) / safe_t
    logits = filter_logits(
        logits, top_k, None if top_p is None else top_p[:, None, None])
    qt = jax.nn.softmax(logits, axis=-1)                   # [S, K, V]

    # per-(slot, lane) keys: the vanilla chain's fold_in(key, t)
    lanes = emit_idx[:, None] + jnp.arange(K)[None, :]     # [S, K]
    pos_keys = jax.vmap(jax.vmap(jax.random.fold_in, (None, 0)),
                        (0, 0))(keys, lanes)               # [S, K, 2]

    # accept draft at lane j+1 iff u < q_t[s, j](d) and the lane is real
    drafts = token_mat[:, 1:]                              # [S, K-1]
    p_acc = jnp.take_along_axis(
        qt[:, :-1, :], drafts[..., None], axis=-1)[..., 0]
    u = jax.vmap(jax.vmap(
        lambda k: jax.random.uniform(jax.random.fold_in(k, 1))))(
        pos_keys[:, :-1])                                  # [S, K-1]
    lane_ok = jnp.arange(K - 1)[None, :] < (n_valid[:, None] - 1)
    acc = (u < p_acc) & lane_ok
    n_acc = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)

    # resample lane n_acc: residual masks the rejected draft (a
    # rejection implies q_t(d) < 1, so at least one other token
    # survives the filters); all-accepted rows sample the bonus token
    # from the last lane unmasked
    lane = jnp.clip(n_acc, 0, K - 1)
    final_logits = jnp.take_along_axis(
        logits, lane[:, None, None], axis=1)[:, 0, :]      # [S, V]
    rejected = n_acc < jnp.maximum(n_valid - 1, 0)
    rej_tok = jnp.take_along_axis(
        token_mat, jnp.clip(n_acc + 1, 0, K - 1)[:, None], axis=1)[:, 0]
    mask = (jax.nn.one_hot(rej_tok, V, dtype=bool)
            & rejected[:, None])
    final_logits = jnp.where(mask, -jnp.inf, final_logits)
    fin_keys = jax.vmap(lambda k: jax.random.fold_in(k, 2))(
        jnp.take_along_axis(
            pos_keys, lane[:, None, None], axis=1)[:, 0])  # [S, 2]
    final = jax.vmap(jax.random.categorical)(fin_keys, final_logits)
    return n_acc, final


def generate(net: MultiLayerNetwork, prompt_ids, n_tokens: int, *,
             temperature: float = 1.0, top_k: int = None,
             top_p: float = None, rng=None, quantize: str = None):
    """Autoregressive decoding with per-layer KV caches — the
    transformer counterpart of the reference's `rnnTimeStep` sampling
    loop (`MultiLayerNetwork.rnnTimeStep` :2605; the char-LM examples
    sample the same way). Static cache shapes mean exactly TWO XLA
    compiles (prompt prefill + the fused decode scan, keyed by the
    sampling config), and the decode loop runs entirely on-device —
    one dispatch, no per-token host round-trip.

    `prompt_ids` [B, T_prompt] int token ids; returns [B, n_tokens]
    sampled ids. `temperature=0` → greedy argmax; `top_k` keeps only
    the k most probable tokens; `top_p` nucleus sampling keeps the
    smallest set of tokens whose cumulative probability reaches p
    (both filters run on-device inside the fused scan).

    `quantize="int8"` serves the decode from per-output-channel int8
    matmul weights (nd/quant.py) — the prefill AND the fused decode
    scan read int8 from HBM and compute in the policy's compute dtype.
    The quantized tree is cached on the net; `net.params` (the
    training master) is untouched. Greedy decode agrees top-1 with the
    fp path over full generations (the serving parity contract,
    docs/SERVING.md)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nd import quant

    from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer

    from jax import lax

    # ids stay INTEGER while carried standalone: a float32 round-trip
    # silently collapses ids at the 2^24 precision edge (16777217.0 ==
    # 16777216.0) — the embedding gather is the only consumer and it
    # indexes with int32 either way
    prompt = jnp.asarray(np.asarray(prompt_ids), jnp.int32)
    B = prompt.shape[0]
    _check_cache_budget(net, prompt.shape[1], n_tokens)
    carries = {str(i): layer.init_carry(B, net.dtype.compute_dtype)
               for i, layer in enumerate(net.layers)
               if isinstance(layer, BaseRecurrentLayer)}

    # jitted closures CACHED on the net (a fresh jax.jit per call would
    # re-trace every generate() — seconds of fixed overhead per call
    # against milliseconds per token of actual decode compute)
    jit_cache = net.__dict__.setdefault("_transformer_gen_jit", {})
    prefill = get_prefill(net)

    # eager argument validation (same pattern as the cache budget above:
    # a bad value must fail HERE, not as a cryptic trace error — or
    # worse, top_p<=0 silently sampling token 0 forever)
    vocab = getattr(net.layers[-1], "n_out", None)
    if top_k is not None and not (1 <= int(top_k) <= (vocab or top_k)):
        raise ValueError(f"top_k must be in [1, vocab={vocab}]; "
                         f"got {top_k}")
    if top_p is not None and not (0.0 < float(top_p) <= 1.0):
        raise ValueError(f"top_p must be in (0, 1]; got {top_p}")
    # top_p rides as a TRACED scalar (only used in a comparison), so
    # sweeping it reuses one executable; top_k must stay static
    # (lax.top_k needs a static k) and keys the cache
    key = (float(temperature), int(n_tokens),
           None if top_k is None else int(top_k), top_p is not None)
    if key not in jit_cache:
        # the ENTIRE decode loop is one fused lax.scan dispatch —
        # sampling (categorical / argmax) happens on-device with the
        # rng carried, so no host round-trip per token
        @jax.jit
        def decode(params, state, probs0, carries, rng0, top_p_val):
            def filt(logits):
                # static-shape vocabulary filters (masked, not
                # gathered) — the ONE filter body the serving engine
                # shares (filter_logits)
                return filter_logits(logits, top_k,
                                     top_p_val if top_p is not None
                                     else None)

            def body(carry, _):
                probs, carries, rng = carry
                if temperature == 0:
                    nxt = jnp.argmax(probs, axis=-1)
                else:
                    rng, k = jax.random.split(rng)
                    logits = jnp.log(
                        jnp.clip(probs, 1e-9, None)) / temperature
                    nxt = jax.random.categorical(k, filt(logits))
                h, _, new_carries, _, _ = net._forward_core(
                    params, state, nxt[:, None],
                    train=False, rng=None, carries=carries)
                return (h[:, -1], new_carries, rng), nxt
            _, toks = lax.scan(body, (probs0, carries, rng0), None,
                               length=n_tokens)
            return toks.T                      # [B, n_tokens]
        jit_cache[key] = decode
    decode = jit_cache[key]

    params = quant.serving_params(net, quantize)
    probs, carries = prefill(params, net.net_state, prompt, carries)
    rng = jax.random.PRNGKey(0) if rng is None else rng
    return np.asarray(decode(params, net.net_state, probs, carries,
                             rng, 1.0 if top_p is None else top_p))


def beam_search(net: MultiLayerNetwork, prompt_ids, n_tokens: int, *,
                beam_width: int = 4, eos_id: int = None,
                length_penalty: float = 0.0):
    """Beam-search decoding over the same per-layer KV caches as
    `generate` — the whole search runs as ONE fused `lax.scan` dispatch
    (beams ride the batch dimension; each step re-gathers every cache
    by the surviving beams' indices, all static shapes).

    `prompt_ids` [B, T_prompt] int ids → (ids [B, beam_width,
    n_tokens], log_probs [B, beam_width]) sorted best-first. With
    `eos_id`, finished beams extend with eos at no cost and keep their
    score. `length_penalty` α ranks the FINAL beams by
    score / ((5 + len) / 6)^α (the GNMT normalization; len counts
    tokens up to and incl. eos) — without it, sum-logprob ranking
    systematically favors short eos'd beams. The returned log_probs
    stay unnormalized sums (so they remain teacher-forceable);
    only the ordering changes. Deterministic (no rng)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer

    prompt = jnp.asarray(np.asarray(prompt_ids), jnp.int32)
    B, Tp = prompt.shape
    W = int(beam_width)
    _check_cache_budget(net, Tp, n_tokens)
    # eager validation (generate's pattern): silent-garbage modes
    # otherwise — beam_width=0 returns empty arrays, an out-of-range
    # eos_id never matches any token so EOS handling no-ops
    if W < 1:
        raise ValueError(f"beam_width must be >= 1; got {beam_width}")
    vocab = getattr(net.layers[-1], "n_out", None)
    if eos_id is not None and vocab and not (0 <= int(eos_id) < vocab):
        raise ValueError(
            f"eos_id must be in [0, vocab={vocab}); got {eos_id}")

    jit_cache = net.__dict__.setdefault("_transformer_gen_jit", {})
    # length_penalty deliberately NOT in the key: the rerank happens
    # host-side after the scan, so sweeping alpha reuses one executable
    key = ("beam", int(n_tokens), W,
           None if eos_id is None else int(eos_id))
    if key not in jit_cache:
        @jax.jit
        def search(params, state, prompt, carries0):
            h, _, carries, _, _ = net._forward_core(
                params, state, prompt, train=False, rng=None,
                carries=carries0)
            logp0 = jnp.log(jnp.clip(h[:, -1], 1e-9, None))  # [B, V]
            V = logp0.shape[-1]
            # beams ride the batch dim: replicate the prompt's caches
            carries = jax.tree_util.tree_map(
                lambda a: (jnp.repeat(a[:, None], W, 1)
                           .reshape((B * W,) + a.shape[1:])
                           if a.ndim > 0 else a),
                carries)
            logp = jnp.repeat(logp0[:, None], W, 1)      # [B, W, V]
            # only beam 0 is live initially (all beams identical after
            # replication; -inf scores stop duplicate selections)
            scores = jnp.broadcast_to(
                jnp.where(jnp.arange(W) == 0, 0.0, -jnp.inf),
                (B, W))                                  # [B, W]
            seqs = jnp.zeros((B, W, n_tokens), jnp.int32)
            fin = jnp.zeros((B, W), bool)

            def body(carry, t):
                logp, scores, seqs, fin, carries = carry
                cand = scores[..., None] + logp          # [B, W, V]
                if eos_id is not None:
                    # finished beams may only extend with eos, cost 0
                    only_eos = jnp.full((V,), -jnp.inf
                                        ).at[eos_id].set(0.0)
                    cand = jnp.where(fin[..., None],
                                     scores[..., None] + only_eos, cand)
                flat = cand.reshape(B, W * V)
                top_s, top_i = lax.top_k(flat, W)        # [B, W]
                beam_idx = top_i // V
                token = top_i % V
                # re-gather histories and caches by surviving beams
                seqs = jnp.take_along_axis(
                    seqs, beam_idx[..., None], axis=1)
                seqs = lax.dynamic_update_slice_in_dim(
                    seqs, token[..., None], t, axis=2)
                fin = jnp.take_along_axis(fin, beam_idx, axis=1)
                if eos_id is not None:
                    fin = jnp.logical_or(fin, token == eos_id)
                gather = jax.vmap(lambda a, i: a[i])     # per batch row

                def regather(a):
                    if a.ndim == 0:
                        return a
                    aw = a.reshape((B, W) + a.shape[1:])
                    return gather(aw, beam_idx).reshape(a.shape)
                carries = jax.tree_util.tree_map(regather, carries)
                h, _, carries, _, _ = net._forward_core(
                    params, state, token.reshape(B * W, 1),
                    train=False, rng=None, carries=carries)
                logp = jnp.log(jnp.clip(h[:, -1], 1e-9, None)
                               ).reshape(B, W, V)
                return (logp, top_s, seqs, fin, carries), None

            (logp, scores, seqs, fin, carries), _ = lax.scan(
                body, (logp, scores, seqs, fin, carries),
                jnp.arange(n_tokens))
            return seqs, scores
        jit_cache[key] = search
    search = jit_cache[key]

    carries0 = {str(i): layer.init_carry(B, net.dtype.compute_dtype)
                for i, layer in enumerate(net.layers)
                if isinstance(layer, BaseRecurrentLayer)}
    seqs, scores = search(net.params, net.net_state, prompt, carries0)
    seqs, scores = np.asarray(seqs), np.asarray(scores)
    # GNMT length normalization — host-side rerank only (the returned
    # scores stay raw sums so they remain teacher-forceable)
    if eos_id is not None:
        hits = np.cumsum(seqs == eos_id, axis=2) > 0
        lengths = np.where(hits.any(2), hits.argmax(2) + 1, n_tokens)
    else:
        lengths = np.full(scores.shape, n_tokens)
    norm = ((5.0 + lengths.astype(np.float64)) / 6.0) ** length_penalty
    order = np.argsort(-scores / norm, axis=1, kind="stable")
    return (np.take_along_axis(seqs, order[..., None], axis=1),
            np.take_along_axis(scores, order, axis=1))
