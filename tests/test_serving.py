"""Serving tier: paged KV-cache pool + continuous-batching scheduler.

The decode-parity contract (docs/SERVING.md) is the spine of this
suite: continuous-batched decode must emit EXACTLY the tokens
whole-batch `generate()` emits — greedy bit-equal — including
sequences that join/leave mid-stream, blocks that get freed and
reused, and pools too small to hold every request at once.
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu.serving import (
    GARBAGE_BLOCK,
    BlockAllocator,
    GenerationServer,
    PagedDecodeEngine,
    ShedError,
    blocks_needed,
)
from deeplearning4j_tpu.zoo.transformer import TransformerLM, generate

V, D, HEADS, LAYERS, MAXLEN = 23, 16, 4, 2, 16
BL = 4          # block_len; MAXLEN/BL = 4 blocks per full sequence


def tiny_lm(seed=3):
    return TransformerLM(vocab_size=V, d_model=D, n_layers=LAYERS,
                         n_heads=HEADS, max_len=MAXLEN, seed=seed).init()


@pytest.fixture(scope="module")
def net():
    return tiny_lm()


@pytest.fixture(scope="module")
def prompts():
    return np.random.default_rng(5).integers(0, V, (6, 3))


@pytest.fixture(scope="module")
def ref_tokens(net, prompts):
    return generate(net, prompts, 6, temperature=0)     # [6, 6]


def drain_engine(eng, slot2req, out):
    """Step until idle, routing emissions into `out[request]`."""
    guard = 0
    while eng.active.any():
        emitted, finished = eng.step()
        for slot, toks in emitted.items():
            out[slot2req[slot]].extend(toks)
        for slot in finished:
            del slot2req[slot]
        guard += 1
        assert guard < 200, "engine failed to drain"


class TestBlockAllocator:
    def test_allocate_free_cycle(self):
        a = BlockAllocator(8)            # 7 usable, id 0 reserved
        assert a.free_blocks == 7
        got = a.allocate(3)
        assert got is not None and len(got) == 3
        assert GARBAGE_BLOCK not in got
        assert a.allocate(5) is None     # all-or-nothing
        assert a.free_blocks == 4
        a.free(got)
        assert a.free_blocks == 7

    def test_double_free_and_bad_ids_rejected(self):
        a = BlockAllocator(4)
        got = a.allocate(1)
        a.free(got)
        with pytest.raises(ValueError, match="double-free"):
            a.free(got)
        with pytest.raises(ValueError, match="invalid block"):
            a.free([0])

    def test_blocks_needed(self):
        assert blocks_needed(1, 4) == 1
        assert blocks_needed(4, 4) == 1
        assert blocks_needed(5, 4) == 2


class TestPagedAttentionParity:
    def test_paged_block_matches_monolithic_carry(self, net):
        """Stepwise: the paged path (non-contiguous blocks, garbage in
        every unowned page) must be BIT-equal to the monolithic KV
        carry — the property the serving parity contract rests on."""
        blk_i = 2     # first encoder block in the stack
        blk = net.layers[blk_i]
        params = net.params[str(blk_i)]
        rng = np.random.default_rng(0)
        B, N = 2, 12
        shape = (N, BL, D)
        k_pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        v_pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        bt = jnp.asarray([[3, 5, 7, 9], [2, 4, 6, 8]], jnp.int32)
        pos = jnp.zeros(B, jnp.int32)
        carry = blk.init_carry(B, jnp.float32)
        for _ in range(5):
            x = jnp.asarray(rng.standard_normal((B, 1, D)), jnp.float32)
            y_mono, _, carry = blk.forward_with_carry(
                params, {}, x, carry)
            y_paged, k_pool, v_pool = blk.forward_paged(
                params, x, k_pool, v_pool, bt, pos)
            pos = pos + 1
            np.testing.assert_array_equal(np.asarray(y_mono),
                                          np.asarray(y_paged))

    def test_positional_at_positions_matches_carry(self, net):
        pe = net.layers[1]
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((3, 1, D)), jnp.float32)
        for p in (0, 3, 9):
            want, _, _ = pe.forward_with_carry(
                {}, {}, x[:1], jnp.asarray(p, jnp.int32))
            got, _ = pe.forward_at_positions(
                {}, {}, x[:1], jnp.asarray([p], jnp.int32))
            np.testing.assert_array_equal(np.asarray(want),
                                          np.asarray(got))


class TestEngineGreedyParity:
    def test_staggered_admissions_bit_equal(self, net, prompts,
                                            ref_tokens):
        """2 slots, 4 requests: sequences join as others finish —
        every stream must match its whole-batch generate() row."""
        eng = PagedDecodeEngine(net, n_slots=2, n_blocks=16,
                                block_len=BL)
        out = {r: [] for r in range(4)}
        slot2req = {}
        pending = list(range(4))
        guard = 0
        while pending or eng.active.any():
            while pending and eng.can_admit(prompts.shape[1], 6):
                r = pending.pop(0)
                (slot, first, done), = eng.admit_many(
                    [dict(prompt_ids=prompts[r], n_tokens=6)])
                out[r].append(first)
                if not done:
                    slot2req[slot] = r
            emitted, finished = eng.step()
            for slot, toks in emitted.items():
                out[slot2req[slot]].extend(toks)
            for slot in finished:
                del slot2req[slot]
            guard += 1
            assert guard < 100
        got = np.asarray([out[r] for r in range(4)])
        np.testing.assert_array_equal(got, ref_tokens[:4])

    def test_chunked_dispatch_same_tokens(self, net, prompts,
                                          ref_tokens):
        """steps_per_dispatch > 1 (fused micro-step scan) emits the
        same streams as one-token-per-dispatch, including a slot
        finishing mid-chunk (6 tokens, J=4 -> 2nd chunk half-valid)."""
        for J in (4, 8):
            eng = PagedDecodeEngine(net, n_slots=4, n_blocks=16,
                                    block_len=BL, steps_per_dispatch=J)
            out = {r: [] for r in range(4)}
            slot2req = {}
            admitted = eng.admit_many(
                [dict(prompt_ids=prompts[r], n_tokens=6)
                 for r in range(4)])
            for r, (slot, first, done) in enumerate(admitted):
                out[r].append(first)
                if not done:
                    slot2req[slot] = r
            drain_engine(eng, slot2req, out)
            got = np.asarray([out[r] for r in range(4)])
            np.testing.assert_array_equal(got, ref_tokens[:4], err_msg=f"J={J}")

    def test_evict_readmit_reuses_blocks_correctly(self, net, prompts,
                                                   ref_tokens):
        """Mid-stream eviction frees blocks; a new sequence admitted
        into those SAME pool blocks must decode exactly (the freed
        pages' stale content is dead weight, not state)."""
        eng = PagedDecodeEngine(net, n_slots=1, n_blocks=4,
                                block_len=BL)   # 3 usable blocks
        (slot, first, done), = eng.admit_many(
            [dict(prompt_ids=prompts[0], n_tokens=6)])
        blocks_first = list(eng.slots[slot].blocks)
        eng.step()
        eng.evict(slot)                  # mid-stream cancel
        assert eng.free_blocks == 3
        # readmit a DIFFERENT request: must land on the same block ids
        (slot2, first2, _), = eng.admit_many(
            [dict(prompt_ids=prompts[1], n_tokens=6)])
        assert set(eng.slots[slot2].blocks) & set(blocks_first), \
            "allocator did not reuse the freed blocks"
        out = {1: [first2]}
        drain_engine(eng, {slot2: 1}, out)
        np.testing.assert_array_equal(np.asarray(out[1]), ref_tokens[1])

    def test_admission_wave_batched_prefill_parity(self, net, prompts,
                                                   ref_tokens):
        """A k>1 admission wave (one batched prefill + one fused
        page-write/first-token dispatch) admits every request with the
        same tokens as separate k=1 admissions."""
        eng = PagedDecodeEngine(net, n_slots=4, n_blocks=16,
                                block_len=BL)
        admitted = eng.admit_many([
            dict(prompt_ids=prompts[r], n_tokens=6) for r in range(4)])
        assert len(admitted) == 4
        out = {r: [admitted[r][1]] for r in range(4)}
        drain_engine(eng, {admitted[r][0]: r for r in range(4)}, out)
        got = np.asarray([out[r] for r in range(4)])
        np.testing.assert_array_equal(got, ref_tokens[:4])

    def test_pool_exhaustion_admits_prefix_only(self, net, prompts):
        # upfront (the PR-9 policy): each request reserves its FULL
        # 9-token budget = 3 blocks -> 6 usable blocks admit only 2
        eng = PagedDecodeEngine(net, n_slots=4, n_blocks=7,
                                block_len=BL, allocation="upfront")
        admitted = eng.admit_many([
            dict(prompt_ids=prompts[r], n_tokens=6) for r in range(4)])
        assert len(admitted) == 2
        assert eng.free_blocks == 0
        # incremental (default): admission grants only the PROMPT
        # footprint (3 tokens = 1 block) — the same pool admits all 4
        eng = PagedDecodeEngine(net, n_slots=4, n_blocks=7,
                                block_len=BL)
        admitted = eng.admit_many([
            dict(prompt_ids=prompts[r], n_tokens=6) for r in range(4)])
        assert len(admitted) == 4
        assert eng.free_blocks == 2

    def test_budget_rejected_eagerly(self, net):
        eng = PagedDecodeEngine(net, n_slots=2, n_blocks=16,
                                block_len=BL)
        with pytest.raises(ValueError, match="page budget"):
            eng.check_budget(10, 10)    # 20 > 16
        with pytest.raises(ValueError, match="must divide"):
            PagedDecodeEngine(net, n_slots=2, n_blocks=16, block_len=5)


class TestIncrementalAllocation:
    """allocation="incremental" (the default): admission grants only
    the PROMPT footprint, `step()` grows block tables lazily as writes
    cross block boundaries, and pool pressure preempts-and-requeues the
    lowest-progress slot instead of deadlocking (ISSUE 10 tentpole b)."""

    def test_lazy_growth_across_block_boundaries(self, net, prompts):
        """One slot, 13 generated tokens (3 + 13 = 16 = 4 blocks):
        the table must track the write frontier exactly — after every
        step, owned blocks == blocks_needed(pos) — and the lazily-grown
        stream must stay bit-equal to whole-batch generate()."""
        ref = generate(net, prompts[:1], 13, temperature=0)[0]
        eng = PagedDecodeEngine(net, n_slots=1, n_blocks=8, block_len=BL)
        (slot, first, done), = eng.admit_many(
            [dict(prompt_ids=prompts[0], n_tokens=13)])
        assert not done
        assert len(eng.slots[slot].blocks) == 1      # prompt (3) only
        out, guard = [first], 0
        while eng.active.any():
            emitted, _ = eng.step()
            out.extend(emitted.get(slot, []))
            if eng.slots[slot] is not None:
                assert len(eng.slots[slot].blocks) == blocks_needed(
                    int(eng.pos[slot]), BL)
            guard += 1
            assert guard < 40
        np.testing.assert_array_equal(np.asarray(out), ref)
        assert eng.block_grants_total == 4           # 1 admit + 3 lazy
        assert eng.evict_requeue_total == 0          # no pressure here

    def test_concurrency_2x_vs_upfront_same_pool(self, net, prompts):
        """The acceptance bar: at the SAME pool size, incremental
        allocation admits >= 2x the up-front-grant baseline's
        concurrent short-generation streams (each stream's budget is 4
        blocks but its prompt occupies 1)."""

        def burst(allocation):
            eng = PagedDecodeEngine(net, n_slots=4, n_blocks=9,
                                    block_len=BL, allocation=allocation)
            return len(eng.admit_many(
                [dict(prompt_ids=prompts[r], n_tokens=13)
                 for r in range(4)]))

        upfront, incremental = burst("upfront"), burst("incremental")
        assert upfront == 2                  # 8 usable // 4-block grants
        assert incremental == 4              # prompt footprint only
        assert incremental >= 2 * upfront

    def test_pool_pressure_preempts_lowest_progress(self, net, prompts):
        """Growth under a full pool must evict the slot whose request
        emitted the FEWEST tokens (requeue costs it the least re-prefill
        work), hand it to drain_preempted(), and let the survivor
        finish exactly."""
        ref_a = generate(net, prompts[:1], 13, temperature=0)[0]
        ref_b = generate(net, prompts[1:2], 6, temperature=0)[0]
        eng = PagedDecodeEngine(net, n_slots=2, n_blocks=5,
                                block_len=BL)   # 4 usable
        (sa, fa, _), = eng.admit_many(
            [dict(prompt_ids=prompts[0], n_tokens=13, request_id="A")])
        out_a = [fa]
        for _ in range(3):                   # A builds a progress lead
            emitted, _ = eng.step()
            out_a.extend(emitted.get(sa, []))
        (sb, fb, _), = eng.admit_many(
            [dict(prompt_ids=prompts[1], n_tokens=6, request_id="B")])
        out_b = [fb]
        guard = 0
        while eng.active.any():
            emitted, _ = eng.step()
            out_a.extend(emitted.get(sa, []))
            out_b.extend(emitted.get(sb, []))
            guard += 1
            assert guard < 40
        # A (emitted 5+) and B (emitted 2) both needed growth with the
        # pool exhausted: the LOWEST-progress slot must be the victim
        notes = eng.drain_preempted()
        assert [n["request_id"] for n in notes] == ["B"], \
            "pool pressure must evict the lowest-progress slot"
        assert notes[0]["emitted"] == len(out_b)
        assert 1 <= len(out_b) < 6            # preempted mid-stream
        np.testing.assert_array_equal(np.asarray(out_a), ref_a)
        # requeue B as a continuation: original prompt + every emitted
        # token, generating the remainder — the stream must complete
        # exactly as if never interrupted
        cont = np.concatenate([prompts[1], np.asarray(out_b)])
        (sb2, f2, _), = eng.admit_many(
            [dict(prompt_ids=cont, n_tokens=6 - len(out_b),
                  request_id="B", emit_start=len(out_b))])
        out_b.append(f2)
        drain_engine(eng, {sb2: 0}, {0: out_b})
        np.testing.assert_array_equal(np.asarray(out_b), ref_b)
        assert eng.evict_requeue_total == 1

    def test_fragmented_free_list_churn(self):
        """Evict/readmit reuse across a FRAGMENTED free list: grants
        interleave with frees, all-or-nothing holds at every point, and
        the double-free guard survives the churn."""
        a = BlockAllocator(10)               # 9 usable
        s1, s2, s3 = a.allocate(3), a.allocate(3), a.allocate(3)
        a.free(s1)
        a.free(s3)                           # free list now fragmented
        assert a.free_blocks == 6
        got = a.allocate(5)                  # spans both fragments
        assert got is not None and len(set(got)) == 5
        assert set(got) <= set(s1) | set(s3)
        assert a.allocate(2) is None         # 1 left: all-or-nothing
        a.free(got[:1])
        with pytest.raises(ValueError, match="double-free"):
            a.free(got[:1])                  # churn must not erode it
        a.free(got[1:])
        a.free(s2)
        assert a.free_blocks == 9            # full pool recovered

    def test_server_requeue_completes_with_parity(self, net, prompts,
                                                  ref_tokens):
        """End-to-end: a pool too small for every stream's full length
        forces preempt-and-requeue mid-serving; every stream must still
        complete bit-equal to whole-batch generate() (continuation
        prefill reproduces the decode-path numerics)."""
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        reg = monitor.enable(registry=MetricsRegistry())
        srv = GenerationServer(net, n_slots=4, n_blocks=5,
                               block_len=BL).start()   # 4 usable blocks
        try:
            streams = [srv.generate_async(prompts[r], 6)
                       for r in range(4)]
            got = np.stack([s.result(timeout=120) for s in streams])
        finally:
            srv.stop()
            monitor.disable()
        np.testing.assert_array_equal(got, ref_tokens[:4])
        assert srv.engine.evict_requeue_total >= 1, \
            "pool pressure never fired — the test pool is too large"
        assert (reg.counter("serving_evict_requeue_total").value
                == srv.engine.evict_requeue_total)
        assert (reg.counter("serving_block_grants_total").value
                == srv.engine.block_grants_total)
        expo = reg.exposition()
        assert "serving_pool_blocks_free" in expo
        assert "serving_pool_blocks_used" in expo


class TestQuantizedDecode:
    """Int8 weight-only quantization (nd/quant.py): the parity contract
    is greedy top-1 agreement over FULL generations on the zoo LM plus
    bounded logit error, and the engine must serve quantized weights
    bit-equal to `generate(quantize="int8")` (ISSUE 10 tentpole a)."""

    def test_quantize_roundtrip_and_seam_units(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.nd import quant
        rng = np.random.default_rng(2)
        w = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
        qt = quant.quantize(w)
        assert qt.q.dtype == jnp.int8 and qt.shape == w.shape
        # symmetric per-output-channel: |error| <= scale/2 everywhere
        deq = quant.dequantize(qt)
        assert np.all(np.abs(np.asarray(deq - w))
                      <= np.asarray(qt.scale) / 2 + 1e-7)
        # the matmul seam scales AFTER the contraction — numerically
        # the same product (per-channel scale commutes with the sum)
        x = jnp.asarray(rng.standard_normal((5, 24)), jnp.float32)
        np.testing.assert_allclose(np.asarray(quant.matmul(x, qt)),
                                   np.asarray(x @ deq), rtol=1e-5,
                                   atol=1e-6)
        # an all-zero output channel must quantize exactly, not NaN
        wz = w.at[:, 3].set(0.0)
        qz = quant.quantize(wz)
        assert np.all(np.asarray(qz.q)[:, 3] == 0)
        assert np.isfinite(np.asarray(qz.scale)).all()
        with pytest.raises(ValueError, match="ndim"):
            quant.quantize(jnp.zeros(7))
        with pytest.raises(ValueError, match="unknown quantization"):
            quant.quantize_net_params(tiny_lm(), "int4")

    def test_quantized_params_tree_and_bytes(self, net):
        from deeplearning4j_tpu.nd import quant
        qp = quant.serving_params(net, "int8")
        plan = quant.quantized_weight_keys(net)
        assert plan, "zoo LM declared no quantizable weights"
        for lk, pks in plan.items():
            for pk in pks:
                assert isinstance(qp[lk][pk], quant.QuantizedTensor)
                # the training master is untouched
                assert not isinstance(net.params[lk][pk],
                                      quant.QuantizedTensor)
        # one quantization pass per net per mode (admission + decode +
        # prefill all share the tree)
        assert quant.serving_params(net, "int8") is qp
        assert quant.serving_params(net, None) is net.params
        mm_fp = quant.weight_bytes(
            {lk: {pk: net.params[lk][pk] for pk in pks}
             for lk, pks in plan.items()})
        mm_q = quant.weight_bytes(
            {lk: {pk: qp[lk][pk] for pk in pks}
             for lk, pks in plan.items()})
        # int8 + per-channel fp32 scale vs fp32: ~3.9x on the matmul
        # weights themselves (the tiny d16 test net bounds it lower)
        assert mm_fp / mm_q > 3.0, (mm_fp, mm_q)
        assert (quant.weight_bytes(net.params)
                / quant.weight_bytes(qp)) > 2.5

    def test_quantized_cache_invalidates_on_fit(self):
        """serving_params caches per net — but fit() reassigns
        net.params, and the cache MUST follow: a fine-tuned net must
        never silently serve pre-training int8 weights while its fp
        path serves the fresh ones."""
        from deeplearning4j_tpu.nd import quant
        net = tiny_lm(seed=5)
        qp1 = quant.serving_params(net, "int8")
        assert quant.serving_params(net, "int8") is qp1   # cached
        rng = np.random.default_rng(0)
        X = rng.integers(0, V, (8, 4)).astype(np.float32)
        Y = np.eye(V, dtype=np.float32)[rng.integers(0, V, (8, 4))]
        net.fit(X, Y, epochs=1, batch_size=8)
        qp2 = quant.serving_params(net, "int8")
        assert qp2 is not qp1, "stale quantized cache survived fit()"
        w_new = np.asarray(quant.dequantize(qp2["0"]["W"]))
        w_old = np.asarray(quant.dequantize(qp1["0"]["W"]))
        assert not np.array_equal(w_new, w_old), \
            "refreshed quantized tree does not reflect the new weights"

    def test_engine_serves_live_params_after_fit(self):
        """The engine resolves its params tree PER DISPATCH: a fit()
        (or checkpoint restore) between engine construction and decode
        must serve the fresh weights, not a construction-time
        snapshot — in fp mode (identity with net.params) and int8 mode
        (re-quantized via the identity-keyed cache)."""
        from deeplearning4j_tpu.nd import quant
        net = tiny_lm(seed=8)
        eng = PagedDecodeEngine(net, n_slots=1, n_blocks=8,
                                block_len=BL)
        qeng = PagedDecodeEngine(net, n_slots=1, n_blocks=8,
                                 block_len=BL, quantize="int8")
        assert eng._params is net.params
        qp_before = qeng._params
        rng = np.random.default_rng(1)
        X = rng.integers(0, V, (8, 4)).astype(np.float32)
        Y = np.eye(V, dtype=np.float32)[rng.integers(0, V, (8, 4))]
        net.fit(X, Y, epochs=1, batch_size=8)
        assert eng._params is net.params, \
            "engine kept a stale fp params snapshot across fit()"
        assert qeng._params is not qp_before, \
            "engine kept stale int8 weights across fit()"
        assert quant.serving_params(net, "int8") is qeng._params

    def test_greedy_top1_agreement_trained_lm(self):
        """The parity contract on a TRAINED zoo LM (random-init logits
        are near-ties — argmax there measures noise, not the
        quantization): full-generation top-1 agreement, plus the
        bounded-probability-error clause.

        Training windows span the FULL position range the generations
        visit (max_len - 1 = 23). They used to be 8 long while decode
        ran to position 20: past position 8 the fp model itself left
        the cycle (on-cycle 41%, top1-top2 margins ~0.01), so "fp ==
        int8" there compared near-ties and held only by the luck of one
        XLA:CPU's rounding (0.961 under jax 0.9 — PR 21). With every
        position trained the margin is >= 0.9 everywhere, both paths
        stay on the cycle, and the equality is about quantization
        again."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.nd import quant
        from deeplearning4j_tpu.nn.layers.recurrent import (
            BaseRecurrentLayer)
        from deeplearning4j_tpu.zoo.transformer import get_prefill
        net = TransformerLM(vocab_size=V, d_model=32, n_layers=2,
                            n_heads=4, max_len=24, seed=11).init()
        corpus = (np.arange(512) * 3) % V    # learnable cyclic stream
        X = np.stack([corpus[i:i + 23] for i in range(0, 480, 2)])
        Y = np.stack([corpus[i + 1:i + 24] for i in range(0, 480, 2)])
        net.fit(X.astype(np.float32), np.eye(V, dtype=np.float32)[Y],
                epochs=20, batch_size=50, shuffle=False)
        pr = np.stack([corpus[i:i + 4]
                       for i in (0, 7, 20, 33, 46, 59, 72, 85)])
        fp = generate(net, pr, 16, temperature=0)
        q8 = generate(net, pr, 16, temperature=0, quantize="int8")
        # the premise: the fp model continues the cycle at EVERY
        # position compared (wide margins, not near-ties)
        truth = (pr[:, -1:] + 3 * np.arange(1, 17)[None]) % V
        assert (fp == truth).all(), f"fp model left the cycle:\n{fp}"
        agree = float((fp == q8).mean())
        assert agree == 1.0, \
            f"greedy top-1 agreement {agree:.3f} < 1.0 over full " \
            f"generations:\nfp={fp}\nint8={q8}"
        # bounded logit error: next-token distributions of the same
        # prefill program under fp vs int8 weights (measured ~4e-4 on
        # this config; the bound leaves a 10x margin)
        prefill = get_prefill(net)

        def carries():
            return {str(i): l.init_carry(len(pr),
                                         net.dtype.compute_dtype)
                    for i, l in enumerate(net.layers)
                    if isinstance(l, BaseRecurrentLayer)}

        p_fp, _ = prefill(net.params, net.net_state, jnp.asarray(pr),
                          carries())
        p_q8, _ = prefill(quant.serving_params(net, "int8"),
                          net.net_state, jnp.asarray(pr), carries())
        err = float(jnp.abs(p_fp - p_q8).max())
        assert err < 5e-3, f"probability error {err} out of bound"

    def test_quantized_engine_bit_equal_noise_pools(self, net, prompts):
        """The engine's quantized decode must be BIT-equal to
        `generate(quantize='int8')` — through noise-filled pools and a
        non-contiguous, fragmented block table (garbage pages must
        contribute exactly 0.0)."""
        import jax.numpy as jnp
        qref = generate(net, prompts[:3], 6, temperature=0,
                        quantize="int8")
        eng = PagedDecodeEngine(net, n_slots=3, n_blocks=16,
                                block_len=BL, quantize="int8")
        key = np.random.default_rng(9)
        eng.pool.kv = tuple(
            (k + jnp.asarray(key.standard_normal(k.shape), k.dtype),
             v + jnp.asarray(key.standard_normal(v.shape), v.dtype))
            for k, v in eng.pool.kv)
        # fragment the free list so the real requests' tables are
        # non-contiguous
        decoys = eng.admit_many(
            [dict(prompt_ids=prompts[3], n_tokens=6),
             dict(prompt_ids=prompts[4], n_tokens=6)])
        for slot, _, _ in decoys:
            eng.evict(slot)
        admitted = eng.admit_many(
            [dict(prompt_ids=prompts[r], n_tokens=6) for r in range(3)])
        assert len(admitted) == 3
        out = {r: [admitted[r][1]] for r in range(3)}
        drain_engine(eng, {admitted[r][0]: r for r in range(3)}, out)
        got = np.asarray([out[r] for r in range(3)])
        np.testing.assert_array_equal(got, qref)

    def test_mixed_length_wave_admits_heterogeneous_prompts(self, net):
        """ONE admission wave with three DIFFERENT prompt lengths
        (bucket-padded into a single prefill dispatch) must admit all
        of them with streams equal to their whole-batch generate()
        rows — the same-length-wave restriction is gone (tentpole c)."""
        rng = np.random.default_rng(4)
        mixed = [rng.integers(0, V, n) for n in (2, 3, 5)]
        refs = [generate(net, p[None], 6, temperature=0)[0]
                for p in mixed]
        eng = PagedDecodeEngine(net, n_slots=4, n_blocks=16,
                                block_len=BL)
        admitted = eng.admit_many(
            [dict(prompt_ids=p, n_tokens=6) for p in mixed])
        assert len(admitted) == 3
        out = {r: [admitted[r][1]] for r in range(3)}
        drain_engine(eng, {admitted[r][0]: r for r in range(3)}, out)
        for r, ref in enumerate(refs):
            np.testing.assert_array_equal(np.asarray(out[r]), ref,
                                          err_msg=f"prompt len "
                                          f"{mixed[r].shape[0]}")

    def test_server_quantized_mixed_length_parity(self, net):
        """Server-level: quantize='int8' + heterogeneous prompt lengths
        submitted concurrently — every stream bit-equal to
        generate(quantize='int8') of its own prompt."""
        rng = np.random.default_rng(6)
        mixed = [rng.integers(0, V, (3, 2, 5, 3, 2, 5)[r])
                 for r in range(6)]
        refs = [generate(net, p[None], 6, temperature=0,
                         quantize="int8")[0] for p in mixed]
        srv = GenerationServer(net, n_slots=4, n_blocks=16,
                               block_len=BL, quantize="int8").start()
        try:
            streams = [srv.generate_async(p, 6) for p in mixed]
            got = [s.result(timeout=120) for s in streams]
        finally:
            srv.stop()
        for r, ref in enumerate(refs):
            np.testing.assert_array_equal(got[r], ref)


class TestMixedPolicyServingCopy:
    """A net whose policy is mixed (float32 masters, bfloat16 compute)
    is served from ONE copy of its weights in the compute dtype, cast
    when the tree is first asked for and again only when `net.params`
    is another object; a net that is not mixed is read in place
    (nd/quant.py `serving_tree`). The programs compute on the operands
    their own `cast_params` made of the masters: streams bit-equal."""

    @staticmethod
    def lm(policy, seed=3):
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        conf = TransformerLM(vocab_size=V, d_model=D, n_layers=LAYERS,
                             n_heads=HEADS, max_len=MAXLEN,
                             seed=seed).conf()
        conf.dtype_policy = policy
        return MultiLayerNetwork(conf).init()

    @staticmethod
    def dtypes(tree):
        import jax
        return {str(x.dtype) for x in jax.tree_util.tree_leaves(tree)}

    @pytest.fixture(scope="class")
    def mixed(self):
        return self.lm("mixed_bf16")

    def test_engine_reads_one_bf16_copy_and_masters_stay(self, mixed,
                                                         prompts):
        eng = PagedDecodeEngine(mixed, n_slots=2, n_blocks=8, block_len=BL)
        served = eng._params
        assert served is not mixed.params
        assert self.dtypes(served) == {"bfloat16"}
        assert self.dtypes(mixed.params) == {"float32"}
        slot, _, _ = eng.admit(prompts[0], 4)
        eng.step()
        assert eng._params is served
        eng.step()
        assert eng._params is served, "the copy was remade between steps"
        # the copy holds the values the in-program cast made of the
        # masters, leaf for leaf
        import jax
        for m, c in zip(jax.tree_util.tree_leaves(mixed.params),
                        jax.tree_util.tree_leaves(served)):
            np.testing.assert_array_equal(
                np.asarray(m.astype(jnp.bfloat16).astype(jnp.float32)),
                np.asarray(c.astype(jnp.float32)))

    @pytest.mark.parametrize("policy", ["float32", "bf16_params"])
    def test_a_net_that_is_not_mixed_is_read_in_place(self, policy):
        from deeplearning4j_tpu.nd import quant
        net = self.lm(policy)
        assert quant.serving_params(net, None) is net.params
        eng = PagedDecodeEngine(net, n_slots=1, n_blocks=4, block_len=BL)
        assert eng._params is net.params
        tree, nbytes = quant.serving_tree(net, None)
        assert tree is net.params
        assert nbytes == quant.weight_bytes(net.params)

    def test_reassigned_params_are_cast_once_more(self, monkeypatch):
        import jax

        from deeplearning4j_tpu.nd import quant
        net = self.lm("mixed_bf16", seed=5)
        calls = []
        cast = quant._cast_leaves
        monkeypatch.setattr(
            quant, "_cast_leaves",
            lambda policy, leaves: calls.append(len(leaves))
            or cast(policy, leaves))
        first = quant.serving_params(net, None)
        assert quant.serving_params(net, None) is first
        n_leaves = len(jax.tree_util.tree_leaves(net.params))
        assert calls == [n_leaves]          # one call, every leaf in it
        net.params = jax.tree_util.tree_map(lambda x: x * 2, net.params)
        second = quant.serving_params(net, None)
        assert second is not first
        assert quant.serving_params(net, None) is second
        assert calls == [n_leaves, n_leaves]
        np.testing.assert_array_equal(
            np.asarray(second["0"]["W"].astype(jnp.float32)),
            np.asarray(net.params["0"]["W"].astype(jnp.bfloat16)
                       .astype(jnp.float32)))
        assert self.dtypes(net.params) == {"float32"}

    def test_a_leaf_already_in_the_compute_dtype_is_not_copied(self):
        """What keeps a tenant's composed tree (bases shared with the
        base net's serving copy) and an int8 tree's `q` at one copy."""
        from deeplearning4j_tpu.nd import dtype as dt, quant
        held = jnp.ones((4, 4), jnp.bfloat16)
        ids = jnp.arange(4)
        tree = {"a": {"W": held, "b": jnp.ones((4,), jnp.float32)},
                "ids": ids}
        out = quant.compute_copy(dt.mixed_bf16(), tree)
        assert out["a"]["W"] is held and out["ids"] is ids
        assert out["a"]["b"].dtype == jnp.bfloat16
        assert tree["a"]["b"].dtype == jnp.float32
        assert quant.compute_copy(dt.bf16_params(), tree) is tree
        assert quant.compute_copy(dt.DataTypePolicy(), tree) is tree

    def test_a_tenants_tree_shares_the_mixed_bases_copy(self, mixed):
        """tenancy/fleet.py composes a tenant's params over the base
        net's serving tree; the tenant's own serving tree (its engine's
        `_params`) holds those base leaves themselves and casts only
        the adapter's factors."""
        from deeplearning4j_tpu.nd import quant
        from deeplearning4j_tpu.tenancy import lora
        from deeplearning4j_tpu.tenancy.fleet import _TenantNetView
        base = quant.serving_params(mixed, None)
        adapter = lora.init_adapter(mixed, rank=2, seed=1)
        view = _TenantNetView(mixed, lora.compose_params(
            base, adapter, rank=2, alpha=4.0))
        served = quant.serving_params(view, None)
        assert self.dtypes(served) == {"bfloat16"}
        n = 0
        for lk, lv in served.items():
            for pk, w in lv.items():
                if isinstance(w, lora.LoRAWeight):
                    assert w.base is base[lk][pk]
                    n += 1
                else:
                    assert w is base[lk][pk]
        assert n > 0

    def test_int8_tree_of_a_mixed_net_is_cast_with_it(self, mixed):
        from deeplearning4j_tpu.nd import quant
        qp = quant.serving_params(mixed, "int8")
        assert quant.serving_params(mixed, "int8") is qp
        assert self.dtypes(qp) == {"int8", "bfloat16"}
        prompts = np.random.default_rng(5).integers(0, V, (2, 3))
        eng = PagedDecodeEngine(mixed, n_slots=2, n_blocks=8,
                                block_len=BL, quantize="int8")
        out = {0: [], 1: []}
        slot2req = {}
        for r in range(2):
            slot, first, _ = eng.admit(prompts[r], 5)
            slot2req[slot] = r
            out[r].append(first)
        drain_engine(eng, slot2req, out)
        ref = generate(mixed, prompts, 5, temperature=0, quantize="int8")
        for r in range(2):
            np.testing.assert_array_equal(out[r], ref[r])

    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "sampled"])
    def test_decode_program_is_bit_equal_on_masters_and_copy(
            self, mixed, prompts, sampled):
        """The decode program (GPT-2 block) given the raw float32 tree
        and given the serving copy: the same tokens, the same pool."""
        import jax
        eng = PagedDecodeEngine(mixed, n_slots=2, n_blocks=8, block_len=BL)
        kw = (dict(temperature=0.8, top_p=0.95,
                   rng=np.asarray([0, 7], np.uint32)) if sampled else {})
        eng.admit(prompts[0], 4, **kw)
        eng.admit(prompts[1], 4)
        step = jax.jit(eng._decode_body(greedy_only=not sampled))
        rest = (mixed.net_state, eng.pool.kv, *eng._decode_args())
        on_masters = step(mixed.params, *rest)
        on_copy = step(eng._params, *rest)
        a, b = (jax.tree_util.tree_leaves(t) for t in (on_masters, on_copy))
        assert len(a) == len(b) > 2
        for x, y in zip(a, b):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(
                np.asarray(x.astype(jnp.float32)),
                np.asarray(y.astype(jnp.float32)))

    def test_server_streams_equal_generate(self, mixed, prompts):
        ref = generate(mixed, prompts, 6, temperature=0)
        srv = GenerationServer(mixed, n_slots=2, n_blocks=16,
                               block_len=BL).start()
        try:
            streams = [srv.generate_async(prompts[r], 6) for r in range(6)]
            for r, s in enumerate(streams):
                np.testing.assert_array_equal(s.result(timeout=120), ref[r])
        finally:
            srv.stop()
        assert self.dtypes(mixed.params) == {"float32"}

    @pytest.mark.parametrize("policy", ["mixed_bf16", "float32"])
    def test_weight_gb_family_reads_the_served_tree(self, policy, prompts):
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        from deeplearning4j_tpu.nd import quant
        net = self.lm(policy)
        reg = monitor.enable(registry=MetricsRegistry())
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=BL).start()
        try:
            for s in [srv.generate_async(prompts[r], 6) for r in range(3)]:
                s.result(timeout=120)
        finally:
            srv.stop()
            monitor.disable()
            monitor._STATE.registry = monitor.GLOBAL_REGISTRY
        snap = reg.snapshot()
        fam = snap["serving_decode_weight_gb"]["values"][0]
        steps = snap["serving_decode_batch_slots"]["values"][0]["count"]
        assert fam["count"] == steps > 0
        master = quant.weight_bytes(net.params)
        want = master / 2 if policy == "mixed_bf16" else master
        assert fam["sum"] / fam["count"] == pytest.approx(want / 1e9)
        assert srv.engine.weight_gb == want / 1e9


class TestSampledDeterminism:
    def test_same_stream_alone_or_batched(self, net, prompts):
        """The serving rng contract: token t of a request derives from
        fold_in(request_key, t) — the stream must not depend on what
        else is in flight (whole-batch generate() cannot offer this;
        the serving tier guarantees it)."""
        key = np.asarray([7, 9], np.uint32)

        def run(extra):
            eng = PagedDecodeEngine(net, n_slots=4, n_blocks=24,
                                    block_len=BL)
            reqs = [dict(prompt_ids=prompts[0], n_tokens=6,
                         temperature=1.0, top_p=0.9, rng=key)]
            for e in range(extra):
                reqs.append(dict(prompt_ids=prompts[e + 1], n_tokens=6,
                                 temperature=0.7,
                                 rng=np.asarray([e, e], np.uint32)))
            admitted = eng.admit_many(reqs)
            out = {r: [admitted[r][1]] for r in range(len(reqs))}
            drain_engine(
                eng, {admitted[r][0]: r for r in range(len(reqs))}, out)
            return out[0]

        alone = run(0)
        batched = run(3)
        assert alone == batched
        assert all(0 <= t < V for t in alone)

    def test_greedy_and_sampled_mix_keeps_greedy_exact(self, net,
                                                       prompts,
                                                       ref_tokens):
        eng = PagedDecodeEngine(net, n_slots=2, n_blocks=16,
                                block_len=BL)
        admitted = eng.admit_many([
            dict(prompt_ids=prompts[0], n_tokens=6),    # greedy
            dict(prompt_ids=prompts[1], n_tokens=6, temperature=1.0,
                 rng=np.asarray([1, 2], np.uint32)),
        ])
        out = {r: [admitted[r][1]] for r in range(2)}
        drain_engine(eng, {admitted[r][0]: r for r in range(2)}, out)
        np.testing.assert_array_equal(np.asarray(out[0]), ref_tokens[0])


def full_matrix_chain(probs, keys, emit_idx, temp, top_p, top_k):
    """The sampling chain over every row of `probs` [S, V], as the
    engine ran it before it gathered the sampled rows: the plain
    reference `_sample_ids` is held to, row for row."""
    import jax
    from deeplearning4j_tpu.zoo.transformer import filter_logits
    greedy_ids = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temp > 0, temp, 1.0)
    logits = jnp.log(jnp.clip(probs, 1e-9, None)) / safe_t[:, None]
    logits = filter_logits(logits, top_k, top_p[:, None])
    skeys = jax.vmap(jax.random.fold_in)(keys, emit_idx)
    sampled = jax.vmap(jax.random.categorical)(skeys, logits)
    return jnp.where(temp > 0, sampled.astype(jnp.int32), greedy_ids)


def sorted_shapes(jaxpr):
    """The operand shapes of every `sort` in `jaxpr`, nested ones too."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "sort":
            out.append(e.invars[0].aval.shape)
        for sub in e.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                out += sorted_shapes(sub)
    return out


class TestSampleRowsAlone:
    """`_sample_ids` runs its chain over the rows that sample, a chunk
    of `_SAMPLE_CHUNK_ROWS` at a time, and every row's id is the one
    the chain over the whole matrix gives."""

    S, VOCAB = 20, 211

    @pytest.fixture(scope="class")
    def rows(self):
        """probs, keys, emit_idx and a temperature and top-p for every
        row (the cases zero the temperatures of the rows that do not
        sample)."""
        import jax
        rng = np.random.default_rng(11)
        probs = jax.nn.softmax(jnp.asarray(
            2.0 * rng.standard_normal((self.S, self.VOCAB)), jnp.float32))
        keys = jnp.asarray(rng.integers(0, 2 ** 31, (self.S, 2)),
                           jnp.uint32)
        emit_idx = jnp.asarray(rng.integers(0, 50, self.S), jnp.int32)
        temp = rng.choice([0.7, 0.8, 1.0, 1.3], self.S).astype(np.float32)
        top_p = rng.choice([1.0, 0.95], self.S).astype(np.float32)
        return probs, keys, emit_idx, temp, jnp.asarray(top_p)

    @staticmethod
    def engine(net, top_k=None):
        return PagedDecodeEngine(net, n_slots=2, n_blocks=8, block_len=BL,
                                 top_k=top_k)

    @staticmethod
    def sampling(rng_seed, n_rows, n_sampled):
        """Which `n_sampled` of the rows sample: scattered, not the
        first ones."""
        mask = np.zeros(n_rows, bool)
        mask[np.random.default_rng(rng_seed).choice(
            n_rows, n_sampled, replace=False)] = True
        return mask

    @pytest.mark.parametrize("top_k", [None, 5])
    @pytest.mark.parametrize("n_sampled", ["0", "1", "R", "R+1", "S"])
    def test_ids_equal_the_full_matrix_chain(self, net, rows, n_sampled,
                                             top_k):
        from deeplearning4j_tpu.serving import engine as engine_mod
        R = engine_mod._SAMPLE_CHUNK_ROWS
        assert R + 1 < self.S
        n = {"0": 0, "1": 1, "R": R, "R+1": R + 1, "S": self.S}[n_sampled]
        probs, keys, emit_idx, temp, top_p = rows
        temp = jnp.asarray(np.where(self.sampling(n, self.S, n), temp, 0.0))
        want = np.asarray(full_matrix_chain(probs, keys, emit_idx, temp,
                                            top_p, top_k))
        got = np.asarray(self.engine(net, top_k)._sample_ids(
            probs, keys, emit_idx, temp, top_p))
        np.testing.assert_array_equal(got, want)
        greedy = np.asarray(jnp.argmax(probs, axis=-1))
        # the case means something: sampled rows left the argmax
        assert (want != greedy).sum() >= n // 2
        np.testing.assert_array_equal(got[np.asarray(temp) == 0],
                                      greedy[np.asarray(temp) == 0])

    def test_one_row_is_one_chunk_of_one(self, net, rows):
        """A one-row admission's `[1, V]`: the chunk is as long as the
        matrix, never longer."""
        probs, keys, emit_idx, temp, top_p = rows
        eng = self.engine(net)
        for r in range(4):
            sl = slice(r, r + 1)
            args = (probs[sl], keys[sl], emit_idx[sl],
                    jnp.asarray(temp[sl]), top_p[sl])
            np.testing.assert_array_equal(
                np.asarray(eng._sample_ids(*args)),
                np.asarray(full_matrix_chain(*args, None)))

    @pytest.mark.parametrize("n_stale", [1, 9])
    def test_a_released_slots_stale_temperature_gets_no_chunk(
            self, net, rows, n_stale):
        """`_release` leaves a slot's temperature where it was: a row
        that is not live is not sampled for, whatever its temperature,
        and the live rows' ids are what they are without it."""
        import jax
        probs, keys, emit_idx, temp, top_p = rows
        live = ~self.sampling(3, self.S, n_stale)
        eng = self.engine(net)
        got = np.asarray(eng._sample_ids(
            probs, keys, emit_idx, jnp.asarray(temp), top_p,
            live=jnp.asarray(live)))
        want = np.asarray(full_matrix_chain(
            probs, keys, emit_idx, jnp.asarray(np.where(live, temp, 0.0)),
            top_p, None))
        np.testing.assert_array_equal(got, want)
        stale_sampled = np.asarray(full_matrix_chain(
            probs, keys, emit_idx, jnp.asarray(temp), top_p, None))
        assert (stale_sampled[~live] != want[~live]).any()
        # and what is sorted is a chunk inside the loop, never the matrix
        from deeplearning4j_tpu.serving import engine as engine_mod
        jaxpr = jax.make_jaxpr(lambda lv: eng._sample_ids(
            probs, keys, emit_idx, jnp.asarray(temp), top_p, live=lv))(
                jnp.asarray(live))
        (loop,) = [e for e in jaxpr.eqns if e.primitive.name == "while"]
        assert sorted_shapes(jaxpr.jaxpr) == sorted_shapes(
            loop.params["body_jaxpr"].jaxpr) == [
                (engine_mod._SAMPLE_CHUNK_ROWS, self.VOCAB)]

    @pytest.mark.parametrize("temperature", [0.0, 0.9])
    def test_the_family_counts_the_rows_of_each_dispatch(
            self, net, prompts, temperature):
        """`serving_sample_rows`, beside `serving_decode_batch_slots`:
        a greedy request's dispatches observe 0 rows, a sampled
        request's, alone in the server, 1 each."""
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        reg = monitor.enable(registry=MetricsRegistry())
        srv = GenerationServer(net, n_slots=4, n_blocks=24,
                               block_len=BL).start()
        try:
            # one after the other: a slot released before the next
            # request keeps its temperature, and must not be counted
            for r in range(2):
                srv.generate_async(
                    prompts[r], 6, temperature=temperature,
                    rng=np.asarray([0, r], np.uint32)).result(timeout=120)
        finally:
            srv.stop()
            monitor.disable()
            monitor._STATE.registry = monitor.GLOBAL_REGISTRY
        snap = reg.snapshot()
        fam = snap["serving_sample_rows"]["values"][0]
        steps = snap["serving_decode_batch_slots"]["values"][0]["count"]
        assert fam["count"] == steps > 0
        assert fam["sum"] == (steps if temperature else 0)


class TestGenerationServer:
    def test_concurrent_streams_greedy_parity(self, net, prompts,
                                              ref_tokens):
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=BL).start()
        try:
            streams = [srv.generate_async(prompts[r], 6)
                       for r in range(6)]
            got = np.stack([s.result(timeout=120) for s in streams])
        finally:
            srv.stop()
        np.testing.assert_array_equal(got, ref_tokens)

    def test_iterator_streams_tokens_incrementally(self, net, prompts,
                                                   ref_tokens):
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=BL).start()
        try:
            toks = list(srv.generate_async(prompts[0], 6))
        finally:
            srv.stop()
        assert toks == list(ref_tokens[0])

    def test_pool_exhaustion_queues_not_corrupts(self, net, prompts,
                                                 ref_tokens):
        """Pool holds ONE sequence: 4 concurrent requests must all
        complete exactly (later ones wait for blocks; nothing reads
        another sequence's pages)."""
        srv = GenerationServer(net, n_slots=4, n_blocks=4,
                               block_len=BL).start()
        try:
            streams = [srv.generate_async(prompts[r], 6)
                       for r in range(4)]
            got = np.stack([s.result(timeout=120) for s in streams])
        finally:
            srv.stop()
        np.testing.assert_array_equal(got, ref_tokens[:4])

    def test_cancel_midstream_and_while_queued(self, net, prompts):
        srv = GenerationServer(net, n_slots=1, n_blocks=5,
                               block_len=BL,
                               steps_per_dispatch=1).start()
        try:
            # A holds the only slot; B is necessarily still queued
            # (pool fits ONE sequence) — cancelling B must retire it
            # without it ever touching a slot
            a = srv.generate_async(prompts[0], 12)
            b = srv.generate_async(prompts[1], 12)
            it = iter(a)
            first = next(it)
            b.cancel()
            a.cancel()                       # mid-stream (best effort)
            got = [first] + list(it)
            assert 1 <= len(got) <= 12
            assert list(a.result(timeout=30)) == got
            assert list(b.result(timeout=30)) == []
            # slot + blocks are free again: a new request runs fully
            s2 = srv.generate_async(prompts[2], 6)
            assert len(s2.result(timeout=120)) == 6
        finally:
            srv.stop()

    def test_shed_under_overload(self, net, prompts):
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        reg = monitor.enable(registry=MetricsRegistry())
        srv = GenerationServer(net, n_slots=1, n_blocks=4,
                               block_len=BL, max_queue=1,
                               slo_ttft_s=1e-3).start()
        try:
            streams = [srv.generate_async(prompts[r % 6], 6)
                       for r in range(8)]
            shed = ok = 0
            for s in streams:
                try:
                    s.result(timeout=120)
                    ok += 1
                except ShedError:
                    shed += 1
        finally:
            srv.stop()
            monitor.disable()
        assert shed >= 1 and ok >= 1
        assert reg.counter("serving_shed_total").value == shed

    def test_serving_metrics_families(self, net, prompts):
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        reg = monitor.enable(registry=MetricsRegistry())
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=BL).start()
        try:
            streams = [srv.generate_async(prompts[r], 6)
                       for r in range(3)]
            for s in streams:
                s.result(timeout=120)
            deadline = time.monotonic() + 5
            while (reg.timer("serving_tpot_seconds").count < 3
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            srv.stop()
            monitor.disable()
        assert reg.counter("serving_requests_total").value == 3
        assert reg.counter("serving_tokens_total").value == 18
        assert reg.timer("serving_ttft_seconds").count == 3
        assert reg.timer("serving_tpot_seconds").count == 3
        assert reg.counter("serving_shed_total").value == 0
        exposition = reg.exposition()
        for fam in ("serving_queue_depth", "serving_active_slots",
                    "serving_free_blocks", "serving_ttft_seconds"):
            assert fam in exposition

    def test_stop_fails_inflight_and_queued(self, net, prompts):
        srv = GenerationServer(net, n_slots=1, n_blocks=4,
                               block_len=BL).start()
        streams = [srv.generate_async(prompts[r % 6], 6)
                   for r in range(4)]
        srv.stop()
        outcomes = []
        for s in streams:
            try:
                s.result(timeout=10)
                outcomes.append("ok")
            except RuntimeError:
                outcomes.append("failed")
        # nothing may HANG; at least the queued tail must have failed
        assert len(outcomes) == 4 and "failed" in outcomes

    def test_validation_eager(self, net, prompts):
        srv = GenerationServer(net, n_slots=1, n_blocks=8, block_len=BL)
        with pytest.raises(RuntimeError, match="start"):
            srv.generate_async(prompts[0], 6)
        srv.start()
        try:
            with pytest.raises(ValueError, match="page budget"):
                srv.generate_async(prompts[0], MAXLEN + 1)
            # within the page budget but needing more blocks than the
            # whole pool owns: must fail at submit, not deadlock queued
            small = GenerationServer(net, n_slots=1, n_blocks=3,
                                     block_len=BL)
            with pytest.raises(ValueError, match="never be admitted"):
                small.engine.check_budget(3, 12)   # 4 blocks > 2 usable
            with pytest.raises(ValueError, match="top_p"):
                srv.generate_async(prompts[0], 4, top_p=0.0)
            with pytest.raises(ValueError, match="non-empty"):
                srv.generate_async(np.zeros((0,), np.int32), 4)
        finally:
            srv.stop()

    def test_warmup_compiles_before_start(self, net, prompts,
                                          ref_tokens):
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=BL)
        srv.warmup(prompts.shape[1], 6)
        # the warmup grid's synthetic grants/preemptions must not leak
        # into the serving-traffic counters (registry deltas + ledger)
        assert srv.engine.block_grants_total == 0
        assert srv.engine.evict_requeue_total == 0
        srv.start()
        try:
            got = srv.generate_async(prompts[0], 6).result(timeout=120)
        finally:
            srv.stop()
        np.testing.assert_array_equal(got, ref_tokens[0])
        assert srv.engine.block_grants_total > 0
        started = GenerationServer(net, n_slots=2, n_blocks=16,
                                   block_len=BL).start()
        try:
            with pytest.raises(RuntimeError, match="before start"):
                started.warmup(3)
        finally:
            # a scheduler left parked would write its `serve/*` spans
            # into whatever tracer a later test of this worker enables
            started.stop()

    def test_warmup_covers_budget_clamped_top_bucket(self, net):
        """A prompt that buckets to the FULL stream budget leaves no
        token headroom at that bucket — warmup must still compile the
        (width, budget-bucket) prefill programs (with a one-shorter
        prompt that pads to the same bucket), or the first budget-edge
        request stalls live streams on a trace."""
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=BL)
        srv.warmup(MAXLEN - 2, 2).start()   # top bucket == MAXLEN
        try:
            got = srv.generate_async(
                np.zeros(MAXLEN - 2, np.int32), 2).result(timeout=120)
            assert len(got) == 2
        finally:
            srv.stop()


class TestServingBenchGate:
    def test_compare_bench_gates_serving_metrics(self):
        from deeplearning4j_tpu.bench import compare_bench

        def rec(tps, speedup):
            return {"platform": "cpu-sandbox", "value": 100.0,
                    "extras": {"serving": {
                        "tokens_per_sec": tps,
                        "speedup_vs_sequential": speedup}}}

        base = rec(5000.0, 1.5)
        assert compare_bench(rec(4900.0, 1.45), base)["status"] == "pass"
        verdict = compare_bench(rec(2000.0, 1.5), base)
        assert verdict["status"] == "regression"
        assert any(r["metric"] == "serving_tokens_per_sec"
                   for r in verdict["regressions"])
        verdict = compare_bench(rec(5000.0, 0.9), base)
        assert verdict["status"] == "regression"
        assert any(r["metric"] == "serving_speedup_vs_sequential"
                   for r in verdict["regressions"])

    def test_compare_bench_gates_quantized_serving(self):
        from deeplearning4j_tpu.bench import compare_bench

        def rec(tps, reduction, ttft):
            return {"platform": "cpu-sandbox", "value": 100.0,
                    "extras": {"serving_mixed_quantized": {
                        "tokens_per_sec": tps,
                        "weight_bytes_reduction": reduction,
                        "p50_ttft_ms": ttft}}}

        base = rec(8000.0, 3.6, 40.0)
        assert compare_bench(rec(7800.0, 3.62, 42.0),
                             base)["status"] == "pass"
        # quantized throughput collapse gates
        v = compare_bench(rec(3000.0, 3.6, 40.0), base)
        assert v["status"] == "regression"
        assert any(r["metric"] == "serving_quantized_tokens_per_sec"
                   for r in v["regressions"])
        # STALE-FALLBACK detection: a run that silently served fp
        # weights reports ~1.0x against the int8 baseline's ~3.6x —
        # the structural 2% band catches it even if throughput held
        v = compare_bench(rec(8000.0, 1.0, 40.0), base)
        assert v["status"] == "regression"
        assert any(
            r["metric"] == "serving_quantized_weight_bytes_reduction"
            for r in v["regressions"])
        # TTFT is lower-is-better: a RISE past tolerance gates...
        v = compare_bench(rec(8000.0, 3.6, 100.0), base)
        assert v["status"] == "regression"
        assert any(r["metric"] == "serving_mixed_p50_ttft_ms"
                   for r in v["regressions"])
        # ...while a big DROP (improvement) passes
        assert compare_bench(rec(8000.0, 3.6, 10.0),
                             base)["status"] == "pass"


class TestServingUI:
    def test_serving_page_renders_registry_state(self, net, prompts):
        import urllib.request

        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor.registry import MetricsRegistry
        from deeplearning4j_tpu.ui import UIServer

        reg = monitor.enable(registry=MetricsRegistry())
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=BL).start()
        try:
            srv.generate_async(prompts[0], 6).result(timeout=120)
        finally:
            srv.stop()
            monitor.disable()
        ui = UIServer(registry=reg).start()
        try:
            base = f"http://127.0.0.1:{ui.port}"
            html = urllib.request.urlopen(base + "/serving",
                                          timeout=10).read().decode()
            assert "requests admitted" in html
            assert "free pool blocks" in html
            assert "pool occupancy" in html
            assert "blocks granted" in html
            mtext = urllib.request.urlopen(base + "/metrics",
                                           timeout=10).read().decode()
            assert "serving_ttft_seconds" in mtext
        finally:
            ui.stop()


class TestReviewHardening:
    def test_midwave_failure_returns_allocated_blocks(self, net,
                                                      prompts):
        """A wave interrupted AFTER earlier requests' blocks were
        allocated (here: a later request failing validation) must
        return them to the pool — no Slot owns them yet, so nothing
        else ever could (the capacity-leak -> silent-starvation
        failure)."""
        eng = PagedDecodeEngine(net, n_slots=4, n_blocks=16,
                                block_len=BL)
        before = eng.free_blocks
        with pytest.raises(ValueError, match="non-empty"):
            eng.admit_many([
                dict(prompt_ids=prompts[0], n_tokens=6),
                dict(prompt_ids=np.zeros((0,), np.int32), n_tokens=6),
            ])
        assert eng.free_blocks == before, "mid-wave failure leaked blocks"
        # pool still fully serviceable
        admitted = eng.admit_many(
            [dict(prompt_ids=prompts[0], n_tokens=6)])
        assert len(admitted) == 1

    def test_output_async_refused_on_generation_server(self, net):
        srv = GenerationServer(net, n_slots=1, n_blocks=8,
                               block_len=BL).start()
        try:
            with pytest.raises(NotImplementedError, match="generate_async"):
                srv.output_async(np.zeros((1, 3), np.float32))
        finally:
            srv.stop()

    def test_warmup_covers_sampled_decode_program(self, net):
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=BL)
        srv.warmup(3)
        assert srv.engine._decode_greedy is not None
        assert srv.engine._decode_full is not None, (
            "warmup left the sampled decode program uncompiled — the "
            "first temperature>0 request would stall live streams")

    def test_default_sampled_requests_draw_distinct_streams(self, net,
                                                            prompts):
        """rng=None + temperature>0 must NOT collapse onto the
        engine's deterministic zero key: two concurrent no-rng sampled
        requests for the SAME prompt get distinct streams (pass rng
        explicitly for reproducibility)."""
        srv = GenerationServer(net, n_slots=2, n_blocks=16,
                               block_len=BL).start()
        try:
            a = srv.generate_async(prompts[0], 8, temperature=1.0)
            b = srv.generate_async(prompts[0], 8, temperature=1.0)
            ta = list(a.result(timeout=120))
            tb = list(b.result(timeout=120))
        finally:
            srv.stop()
        assert ta != tb, "no-rng sampled requests shared one key"

    def test_cancelled_queued_requests_do_not_shed_fresh_ones(self, net,
                                                              prompts):
        """Cancelled entries stranded mid-queue must stop counting
        toward max_queue / the shed projection — phantom load must not
        shed real requests."""
        srv = GenerationServer(net, n_slots=1, n_blocks=5,
                               block_len=BL, max_queue=2,
                               steps_per_dispatch=1).start()
        try:
            a = srv.generate_async(prompts[0], 12)   # holds the slot
            queued = [srv.generate_async(prompts[1], 6)
                      for _ in range(2)]             # fills max_queue
            for s in queued:
                s.cancel()
            # give the scheduler a beat to reap the cancelled entries
            for s in queued:
                s.result(timeout=30)
            fresh = srv.generate_async(prompts[2], 6)
            got = fresh.result(timeout=120)          # must NOT ShedError
            assert len(got) == 6
            a.result(timeout=120)
        finally:
            srv.stop()
