"""Paged continuous-batching decode engine.

The device-program half of the serving tier (the threaded scheduler
lives in serving/server.py): a fixed set of `n_slots` serving slots
advances ONE token per jitted dispatch over the paged KV pool — static
slot count means ONE XLA program no matter which sequences are in
flight; empty slots decode garbage into the reserved block and are
masked out on the host.

Per dispatch:

- `decode_step(params, state, kv, block_tables, token_ids, slot_state)
  -> (kv', next_ids, done_flags)` — embedding -> per-slot positional
  signal -> paged transformer blocks -> per-position softmax, then
  greedy argmax or per-slot sampled next token. The pools stay
  device-resident (donated where the backend supports it), and so does
  the step's carry (last token, position, tokens left, emit index):
  each program hands it to the next, and the host uploads only what it
  changed itself since the last launch (a slot admitted or released, a
  row of the block tables after a grant), so the next step can be
  launched before this one is read back (`step_ahead`).
- admission prefills a WAVE of prompts — heterogeneous lengths
  bucket-padded to one shape (`zoo.transformer.get_prefill_bucketed`,
  per-slot last-position gather) — then scatters the filled monolithic
  carries into each sequence's pool blocks. Prefill numerics are
  `generate()`'s by construction; right padding is sound because the
  blocks are causal and every read past a slot's position is masked.

Block allocation (`allocation="incremental"`, the default): admission
grants only the blocks the PROMPT occupies; `step()` grows a slot's
block table lazily as its position crosses block boundaries. Under
pool pressure the lowest-progress slot is evicted and handed back to
the scheduler for requeue (`drain_preempted`) instead of deadlocking —
effective concurrency rises ~budget/actual_length for short
generations at the same pool size. `allocation="upfront"` restores the
PR-9 grant-everything-at-admission behavior (the A/B baseline the
concurrency tests compare against).

Weights (`quantize="int8"`): the decode/prefill/admission programs
read per-output-channel int8 matmul weights (nd/quant.py) from HBM and
compute in the policy's compute dtype — autoregressive decode is
bandwidth-bound, so the ~4x weight-byte cut is the serving throughput
lever. `net.params` (the training master) is untouched. A net whose
policy is mixed (float32 masters, bfloat16 compute) is served from ONE
copy of its weights in the compute dtype, cast when the engine is
built and again only when `net.params` is reassigned
(`quant.serving_tree`): a decode step reads every weight, and reads it
at the width it computes on. A net that is not mixed is read in place.

Decode-parity contract (docs/SERVING.md): for the same prompt and
sampling config, the token stream is identical to whole-batch
`generate()` — greedy is exact (test-enforced bit-equality; with
`quantize=` the reference is `generate(quantize=...)`); sampled mode
derives token t's key as `fold_in(request_key, t)`, which makes a
request's stream deterministic REGARDLESS of what else is in flight —
including across a preempt-and-requeue, whose continuation re-admits
at the same emit offset.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.nd import quant
from deeplearning4j_tpu.nd.donation import donate_argnums
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.nn.layers.transformer import PositionalEncodingLayer
from deeplearning4j_tpu.serving.paged import (
    GARBAGE_BLOCK,
    PagedKVPool,
    RadixPrefixCache,
    blocks_needed,
    plan_table,
)

# Rows of one chunk of the sampling chain (`_sample_ids`): the most that
# cost the v5e no more than twice a chunk of one row. At 50,257 ids the
# chain takes 553 us for one row, 666 for 8, 1,159 for 16 and 2,218 for
# 32 (`scripts/sample_chain_cost.py`, PR 34; PERF.md 5 has the table).
_SAMPLE_CHUNK_ROWS = 8


def bucket_len(n: int, cap: int) -> int:
    """Pad length for mixed-length prefill: the next power of two >= n,
    clamped to `cap` (the stream budget). Quantized lengths bound the
    prefill program grid exactly like power-of-two wave widths bound
    the admission programs."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _moe_means(stats: dict):
    """What the routed expert layers of one traced forward reported
    (`stats`, filled by `paged_step` / `forward_prefill`) as the two
    means over those layers: (rows routed to held experts, fullest held
    expert over the mean); () for a net with no such layer."""
    n = stats.get("moe_layers", 0)
    if not n:
        return ()
    return (stats["moe_rows"] / n, stats["moe_load_max_over_mean"] / n)


class Slot:
    """Host mirror of one serving slot's in-flight sequence."""

    __slots__ = ("request_id", "blocks", "window_blocks", "prompt_len",
                 "n_tokens", "emitted", "pos", "emit_base", "history")

    def __init__(self, request_id, blocks, prompt_len, n_tokens,
                 emit_base=0, history=None, window_blocks=None):
        self.request_id = request_id
        self.blocks = blocks
        # the ring of blocks the window layers keep for the slot (their
        # own pool's ids); None for a net with no window layer
        self.window_blocks = window_blocks
        self.prompt_len = prompt_len
        self.n_tokens = n_tokens
        self.emitted = 0
        self.pos = prompt_len
        # tokens the request emitted in EARLIER admissions (a requeued
        # continuation) — progress ordering and the sampled-rng emit
        # offset both count from here
        self.emit_base = emit_base
        # full token history (prompt + every emitted token): the
        # self-drafting proposer's n-gram suffix cache reads it
        self.history: List[int] = history if history is not None else []

    @property
    def progress(self) -> int:
        """Total tokens this REQUEST has emitted (across preemptions)
        — the eviction policy's ordering key."""
        return self.emit_base + self.emitted


class PagedDecodeEngine:
    """Continuous-batching decode over a `PagedKVPool`.

    Synchronous and single-threaded by design — every method must be
    called from one scheduler thread (serving/server.py owns that
    thread; tests drive the engine directly for determinism).

    `top_k` is engine-static (lax.top_k needs a static k — same
    constraint `generate()` documents); temperature and top_p are
    per-request traced values, so mixed greedy/sampled batches share
    the one decode program.
    """

    def __init__(self, net, *, n_slots: int = 8, n_blocks: int = 64,
                 block_len: int = 16, top_k: Optional[int] = None,
                 steps_per_dispatch: int = 1,
                 quantize: Optional[str] = None,
                 allocation: str = "incremental",
                 speculative: Optional[int] = None,
                 spec_max_ngram: int = 3,
                 spec_sampled: bool = False,
                 spec_draft_layers: Optional[int] = None,
                 prefix_cache: str = "registered",
                 max_positions: Optional[int] = None,
                 max_prefill_tokens: Optional[int] = None,
                 min_prefill_bucket: int = 1,
                 window_blocks: Optional[int] = None):
        if not getattr(net, "_initialized", False):
            net.init()
        self.net = net
        self.n_slots = int(n_slots)
        self.steps_per_dispatch = int(steps_per_dispatch)
        if self.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1; got {steps_per_dispatch}")
        self.top_k = None if top_k is None else int(top_k)
        if self.n_slots < 1:
            raise ValueError(f"n_slots must be >= 1; got {n_slots}")
        if allocation not in ("incremental", "upfront"):
            raise ValueError(
                f"allocation must be 'incremental' or 'upfront'; "
                f"got {allocation!r}")
        self.allocation = allocation
        self.quantize = quantize
        if speculative is not None:
            speculative = int(speculative)
            if speculative < 2:
                raise ValueError(
                    f"speculative (the draft depth k) must be >= 2 — "
                    f"k=1 is ordinary decode; got {speculative}")
        self.spec_k = speculative
        self.spec_max_ngram = int(spec_max_ngram)
        # sampled speculation (rejection sampling over delta drafts —
        # zoo.transformer.rejection_sample_drafts): OPT-IN because it
        # trades the sampled bit-parity contract for a distributional
        # one (docs/SERVING.md acceptance-oracle table); greedy slots
        # keep the bit-exact argmax oracle either way
        self.spec_sampled = bool(spec_sampled)
        if self.spec_sampled and self.spec_k is None:
            raise ValueError(
                "spec_sampled=True without speculative=k — there is "
                "no draft depth to rejection-sample over")
        # truncated-layer drafter: the SECOND _propose backend — the
        # first `spec_draft_layers` transformer blocks of the SAME
        # weights greedily draft k-1 tokens when the n-gram suffix
        # cache has nothing (non-repetitive text)
        if spec_draft_layers is not None:
            spec_draft_layers = int(spec_draft_layers)
            if self.spec_k is None:
                raise ValueError(
                    "spec_draft_layers without speculative=k — the "
                    "drafter only feeds speculative dispatches")
        self.spec_draft_layers = spec_draft_layers
        if prefix_cache not in ("registered", "radix"):
            raise ValueError(
                f"prefix_cache must be 'registered' or 'radix'; "
                f"got {prefix_cache!r}")
        self.prefix_cache_mode = prefix_cache
        # pay the pass that makes the serving tree (quantization, a
        # mixed net's compute-dtype copy) NOW, not inside the first
        # live dispatch (the tree itself is resolved per dispatch — see
        # the _params property)
        quant.serving_tree(net, quantize)
        # the per-sequence budget: the smallest length a layer of the
        # net bounds the paged path to (a positional table's `max_len`,
        # the `cache_len` a block's prefill carry has) and the server's
        # own `max_positions` — which a rotary model, with no table and
        # no length of its own, needs
        limits = [l.paged_stream_limit for l in net.layers
                  if getattr(l, "paged_cache", False)]
        limits += [l.max_len for l in net.layers
                   if isinstance(l, PositionalEncodingLayer)]
        limits = [int(x) for x in limits if x is not None]
        if max_positions is not None:
            if limits and int(max_positions) > min(limits):
                raise ValueError(
                    f"max_positions {max_positions} exceeds what the net "
                    f"itself can hold ({min(limits)}: KV cache_len / "
                    f"positional max_len)")
            limits.append(int(max_positions))
        if not limits:
            raise ValueError(
                "net has no bounded stream budget (no positional table, "
                "no cache length of its own): give the server "
                "max_positions, the positions a slot may hold")
        budget = min(limits)
        if budget % block_len != 0:
            raise ValueError(
                f"block_len {block_len} must divide the stream budget "
                f"{budget} (KV cache_len / positional max_len): the "
                f"gathered page view must have the same length as the "
                f"monolithic cache for decode parity")
        vocab = getattr(net.layers[-1], "n_out", None)
        if self.top_k is not None and not (1 <= self.top_k <=
                                           (vocab or self.top_k)):
            raise ValueError(f"top_k must be in [1, vocab={vocab}]; "
                             f"got {top_k}")
        self.max_blocks = budget // int(block_len)
        self.max_total_tokens = budget
        if self.spec_k is not None and self.spec_k > budget:
            raise ValueError(
                f"speculative depth {self.spec_k} exceeds the stream "
                f"budget {budget} — no slot could ever take a full-"
                f"depth dispatch")
        self.pool = PagedKVPool(net, n_blocks, block_len, window_blocks,
                                n_slots=self.n_slots)
        self.block_len = int(block_len)
        # a THIRD kind of cache: layers that keep a state of fixed size
        # a slot beside the pages (`pool.kv[n_paged:]`). What would need
        # the state at a position other than a slot's last (a prefix
        # hit, a rejected draft) or a place for it on the wire refuses
        # such a net
        self.state_layers = len(self.pool.state_indices)
        if self.state_layers:
            if prefix_cache == "radix":
                self._refuse_state("the radix prefix cache")
            if self.spec_k is not None:
                self._refuse_state("speculative decoding (the K-wide "
                                   "score program)")
        # two kinds of cache: the window layers' ring of blocks a slot
        # (`window_ring` table columns, their own pool and allocator);
        # 0 for a net with no window layer, whose programs and tables
        # are then what they always were
        self.window_ring = (0 if self.pool.window is None
                            else self.pool.ring_blocks(self.max_blocks))
        if self.window_ring:
            if prefix_cache == "radix":
                self._refuse_two_pools("the radix prefix cache")
            if self.spec_k is not None and self.spec_k > self.block_len + 1:
                raise ValueError(
                    f"speculative depth {self.spec_k} exceeds block_len + 1 "
                    f"= {self.block_len + 1}: the k writes of one score "
                    f"dispatch would reach back into a block of a window "
                    f"layer's ring that its first query still reads")
        # per transformer block: do the single-token programs attend
        # over the pool in place (`dl4tpu_paged_decode`) or gather it?
        # The layer decides when a program is traced, from the kernels'
        # shared switch and the pool's shape; asked once here, it keys
        # those programs' cache entries and is what `kv_read_pct`
        # counts from
        self._in_place = tuple(
            net.layers[i].paged_in_place(arrays)
            for i, arrays in zip(self.pool.layer_indices,
                                 self.pool.kv[:self.pool.n_paged]))
        # prefill: a layer that implements `forward_prefill` hands back
        # the rows its pages are cut from, prompt-long, and the wave's
        # last positions alone go through the layers after the last
        # paged one; otherwise the prompt runs through the net's
        # monolithic carries (`get_prefill_bucketed`), budget-long
        self._paged_prefill = all(
            hasattr(net.layers[i], "forward_prefill")
            for i in self.pool.layer_indices + self.pool.state_indices)
        if self.state_layers and not self._paged_prefill:
            raise ValueError(
                "a net with per-slot state layers is prefilled through "
                "`forward_prefill` (the state each row reached at its last "
                "real token); a paged or state layer of this net lacks it")
        # admission bounded in tokens: no prefill program is wider than
        # `max_prefill_tokens` (wave width x prompt bucket); prompts
        # pad to at least `min_prefill_bucket` (a grid no traffic uses
        # is set-up time for nothing)
        self.max_prefill_tokens = (None if max_prefill_tokens is None
                                   else int(max_prefill_tokens))
        self.min_prefill_bucket = max(1, int(min_prefill_bucket))
        if self.min_prefill_bucket & (self.min_prefill_bucket - 1):
            raise ValueError(
                f"min_prefill_bucket must be a power of two; got "
                f"{min_prefill_bucket}")
        # a serving "plan": how each layer participates in the paged
        # decode walk. Input preprocessors would silently change the
        # math mid-walk — reject loudly (the zoo LMs have none).
        if net.conf.input_preprocessors:
            raise ValueError(
                "paged decode does not support input preprocessors "
                f"(found at {sorted(net.conf.input_preprocessors)})")
        self._plan: List[Tuple] = []
        pool_j, state_j = 0, self.pool.n_paged
        for i, layer in enumerate(net.layers):
            if getattr(layer, "paged_cache", False):
                # a two-pool net's entries also say which table the
                # layer reads: 0 the full one, 1 the window layers' ring
                self._plan.append(
                    ("block", i, pool_j,
                     int(self.pool.window_layers[pool_j]))
                    if self.window_ring else ("block", i, pool_j))
                pool_j += 1
            elif isinstance(layer, PositionalEncodingLayer):
                self._plan.append(("pos", i))
            elif getattr(layer, "slot_state", False):
                # the layer's per-slot state: entry `state_j` of the
                # pools, no table
                self._plan.append(("state", i, state_j))
                state_j += 1
            elif isinstance(layer, BaseRecurrentLayer):
                raise ValueError(
                    f"layer {i} ({type(layer).__name__}) carries "
                    "recurrent state but declares no way to serve it: "
                    "either the paged protocol (`paged_cache`, "
                    "`paged_pool_arrays`, `paged_step`) or a per-slot "
                    "state (`slot_state`, `slot_state_arrays(n_slots, "
                    "dtype)`, `state_step`, `forward_prefill`); "
                    "docs/SERVING.md")
            else:
                self._plan.append(("plain", i))
        # truncated-drafter plan: the SAME walk minus the deep blocks —
        # embedding/positional/unembedding layers all kept, only the
        # first `spec_draft_layers` ("block", i, j) entries survive.
        # Layer-i K/V depends only on layers < i, so the slot's real
        # pages double as the draft model's cache for committed tokens
        # with NO extra state
        self._draft_plan: Optional[List[Tuple]] = None
        if self.spec_draft_layers is not None:
            n_layers = sum(1 for e in self._plan if e[0] == "block")
            if not (1 <= self.spec_draft_layers < n_layers):
                raise ValueError(
                    f"spec_draft_layers must be in [1, {n_layers - 1}] "
                    f"(a strict truncation of the {n_layers}-block "
                    f"target); got {self.spec_draft_layers}")
            kept = 0
            self._draft_plan = []
            for e in self._plan:
                if e[0] == "block":
                    if kept >= self.spec_draft_layers:
                        continue
                    kept += 1
                self._draft_plan.append(e)
        # host slot state (uploaded per step; a few [S] vectors)
        S = self.n_slots
        self.block_tables = np.zeros((S, self.max_blocks), np.int32)
        self.window_tables = (np.zeros((S, self.window_ring), np.int32)
                              if self.window_ring else None)
        self.pos = np.zeros(S, np.int32)
        self.active = np.zeros(S, bool)
        self.remaining = np.zeros(S, np.int32)
        self.emit_idx = np.zeros(S, np.int32)
        self.last_token = np.zeros(S, np.int32)
        self.keys = np.zeros((S, 2), np.uint32)
        self.temp = np.zeros(S, np.float32)
        self.top_p = np.ones(S, np.float32)
        self.slots: List[Optional[Slot]] = [None] * S
        # what the device holds of the above. The decode program hands
        # `_carry` (last token, pos, remaining, emit index: [4, S]) to
        # the next one; `_dev_ints` is the host's reckoning of its last
        # three rows (written in place at each launch), and `_tok_host`
        # marks the slots whose last token the host wrote (an admission,
        # a speculative step) and the carry lacks. `_uploaded` keeps, by
        # name, the host copy and the device copy of each input the
        # program does not advance: a launch uploads one anew only where
        # its mirror has changed (`_decode_args`). Device constants come
        # from numpy: no program
        self._carry = jnp.asarray(np.zeros((4, S), np.int32))
        self._no_fresh = jnp.asarray(np.zeros((5, S), np.int32))
        self._dev_ints = np.zeros((3, S), np.int32)
        self._tok_host = np.zeros(S, bool)
        self._uploaded: Dict[str, tuple] = {}
        # the decode step launched and not read back (one, where the
        # caller runs ahead; none after `step` or `drain`), and what has
        # been read back but not yet returned to the caller
        self._flight: Optional[dict] = None
        self._ready: Optional[Tuple[Dict[int, List[int]], List[int]]] = None
        self._decode_full = None      # greedy + sampling chain
        self._decode_greedy = None    # argmax only (no sort/rng ops)
        self._admit_finish = {}       # k -> fused write-pages+first-token
        # K-position score programs (speculative decode + CoW suffix
        # extension), keyed (K, greedy_only — K is baked into the
        # array shapes, but the variants differ in OPS); the fork copy
        # and the first-token samplers (exact prefix-match admission,
        # keyed greedy_only) are shape-polymorphic single jits — jit's
        # own per-shape cache covers every pow2 width
        self._score = {}
        self._fork = None
        self._first_token = {}
        self._draft_fn = None         # truncated-layer draft scan
        # copy-on-write shared-prefix registry: key (token-id tuple) ->
        # {tokens, len, blocks, probs}; the cache itself holds one
        # allocator reference per block so registered prefixes survive
        # every slot release
        self._prefixes: Dict[tuple, dict] = {}
        self.prefix_pinned_blocks = 0
        # radix prefix cache (prefix_cache="radix"): automatic
        # block-aligned mid-prompt dedup across all admissions — the
        # registered-prefix registry above keeps working alongside it
        # (exact registered matches win; the tree catches everything
        # else). Radix-held blocks are NOT pinned capacity: eviction
        # reclaims them on demand (LRU leaves first, live slots never)
        self._radix: Optional[RadixPrefixCache] = (
            RadixPrefixCache(self.pool.allocator, self.block_len)
            if prefix_cache == "radix" else None)
        self.radix_hit_tokens_total = 0
        self.radix_evictions_total = 0
        # allocator observability (host ints — the scheduler mirrors
        # them onto the metrics registry) + preemption notices the
        # scheduler drains for requeue
        self.block_grants_total = 0
        self.evict_requeue_total = 0
        # speculative-decoding accounting (host ints; the scheduler's
        # accept-rate EWMA and the serving_spec_* gauges read them)
        self.spec_dispatches_total = 0
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        self.spec_emitted_total = 0
        # per-proposer split of the same accounting (the scheduler's
        # per-proposer EWMAs and the serving_spec_*{proposer=} label
        # families read these; the global counters above are the sum
        # over proposers and keep their exact PR-14 semantics)
        self.spec_proposed_by: Dict[str, int] = {"ngram": 0,
                                                 "truncated": 0}
        self.spec_accepted_by: Dict[str, int] = {"ngram": 0,
                                                 "truncated": 0}
        self.spec_draft_dispatches_total = 0
        # shared-prefix accounting
        self.prefix_hits_total = 0
        self.prefix_tokens_saved_total = 0
        self.prefix_forks_total = 0
        # token-goodput ledger: every dispatch site classifies the
        # token-positions of the program it launches (host ints; the
        # scheduler mirrors the classes onto the registry) — sum of
        # classes == dispatched_total by construction
        from deeplearning4j_tpu.monitor.goodput import GoodputLedger
        self.goodput = GoodputLedger()
        self._preempted: List[dict] = []
        # per-slot attribution for the LAST admit_many wave (host-side
        # bookkeeping only — what request tracing reads to say whether
        # an admission rode a shared prefix / forked CoW blocks)
        self.admit_info: dict = {}
        # shared with the scheduler for its spans and timers: the
        # scheduler's iteration number, which it advances and every
        # span of the iteration carries as `it`; the padded prompt
        # length of the last wave; and the seconds the last
        # `admit_many` / `step` call's `*/wait` spans spent blocked on
        # readbacks (0.0 with monitoring off: nothing is timed then)
        self.loop_it = 0
        self.admit_bucket = 0
        self.admit_tokens = 0     # prompt tokens of the last admit_many
        self.wait_s = 0.0
        # of the decode step the last `step` / `step_ahead` / `drain`
        # call read back: the share of the slots' tables its attention
        # read, and whether an earlier step was still unread when it
        # was launched; `launched`: did that call dispatch a step itself;
        # `weight_gb`: the bytes / 1e9 of the params tree its program
        # was given (reckoned when the tree was made, not a step)
        self.kv_read_pct = 0.0
        self.weight_gb = 0.0
        self.overlapped = False
        self.launched = False
        # rows the sampling chain ran over in that step (the live slots
        # with a temperature; 0 for a step of the greedy twin)
        self.sample_rows = 0
        # positions the last decode dispatch's attention read (summed
        # over its paged layers), and what the routed expert layers of
        # the last dispatch (decode or admission) report: rows routed
        # to held experts and the fullest held expert over the mean,
        # each a mean over those layers; None where the net has none.
        # Read back with the tokens: no transfer of their own
        self.positions_read = 0
        self.moe_stats: Optional[Tuple[float, float]] = None
        # of the same dispatch, for a two-pool net: 100 x the positions
        # the window layers hold for the decoding slots over the
        # positions those slots have reached (None: no window layer)
        self.window_held_pct: Optional[float] = None
        # for a net with per-slot state layers: the bytes / 1e9 of the
        # state arrays the last decode dispatch read back was handed, in
        # and out, each micro-step (structural: the arrays' own size,
        # whatever the slots that decode);
        # and of the last admission wave, 100 x the positions of its
        # prefill (width x bucket) past their row's last token, which
        # the layers' scan is dispatched over all the same
        self.state_gb = 0.0
        self.scan_pad_pct: Optional[float] = None

    def _refuse_two_pools(self, what: str):
        """What cannot take a second kind of pool yet refuses such a net
        loudly: it names ONE block list a slot."""
        if self.window_ring:
            raise NotImplementedError(
                f"{what} keeps one list of blocks a sequence; this net's "
                f"window layers keep a ring of {self.window_ring} blocks "
                f"of their own pool beside it (two kinds of cache in one "
                f"manager): not supported yet")

    def _refuse_state(self, what: str):
        """What cannot serve a per-slot state yet refuses such a net
        loudly."""
        if self.state_layers:
            raise NotImplementedError(
                f"{what} needs a layer's state at a position other than "
                f"the slot's last, or a place for it beside the pages; "
                f"this net has {self.state_layers} layers that keep a "
                f"recurrent state of fixed size a slot, of which the "
                f"manager holds the newest alone (no snapshot): not "
                f"supported yet")

    # ------------------------------------------------------------ queries
    @property
    def _params(self):
        """The params tree every serving program reads
        (`quant.serving_tree`): the net's own tree, or under a mixed
        policy its one copy in the compute dtype, with int8-quantized
        matmul weights under quantize="int8" — resolved PER DISPATCH,
        so a fit()/restore between dispatches serves the fresh weights
        (the identity-keyed cache makes this a dict lookup; the copy is
        made again only when net.params was reassigned)."""
        return quant.serving_params(self.net, self.quantize)

    @property
    def free_slots(self) -> int:
        return sum(1 for s in self.slots if s is None)

    @property
    def active_slots(self) -> int:
        return int(self.active.sum())

    @property
    def free_blocks(self) -> int:
        return self.pool.free_blocks

    def _bucket(self, n: int) -> int:
        """Padded length of an `n`-token prompt: the next power of two,
        at least `min_prefill_bucket`, at most the budget."""
        return min(max(bucket_len(n, self.max_total_tokens),
                       self.min_prefill_bucket), self.max_total_tokens)

    def _admit_blocks(self, prompt_len: int, n_tokens: int) -> int:
        """Blocks an admission grants NOW: the prompt's footprint under
        incremental allocation (decode growth is lazy), the request's
        whole budget under the PR-9 upfront policy."""
        if self.allocation == "incremental":
            return blocks_needed(prompt_len, self.block_len)
        return blocks_needed(prompt_len + n_tokens, self.block_len)

    def _ring_need(self, n_blocks: int) -> int:
        """Blocks of the window layers' pool a slot holds when the
        others hold `n_blocks`: as many, up to the ring."""
        return min(int(n_blocks), self.window_ring)

    @property
    def has_prefixes(self) -> bool:
        return bool(self._prefixes)

    def _match_prefix(self, prompt) -> Optional[dict]:
        """The LONGEST registered prefix that prefixes `prompt`, or
        None. O(#prefixes x prefix_len) numpy compares — the registry
        holds a handful of warmed system prompts, not a trie."""
        if not self._prefixes:
            return None
        best = None
        # list() snapshot: submitter threads run this through
        # check_budget while the scheduler thread applies register/
        # release control requests — iterating the live dict would
        # raise "changed size during iteration" in an innocent submit
        for e in list(self._prefixes.values()):
            P = e["len"]
            if P > prompt.shape[0]:
                continue
            if best is not None and P <= best["len"]:
                continue
            if np.array_equal(np.asarray(prompt[:P], np.int64),
                              e["tokens"]):
                best = e
        return best

    def _cow_fresh_blocks(self, entry: dict, map_tokens: int) -> int:
        """Fresh (non-shared) blocks a CoW admission mapping
        `map_tokens` positions must allocate: the full map minus the
        shared prefix blocks, plus one for the forked tail when the
        prefix ends mid-block (copy-on-first-write — the fork target
        is a fresh block; the slot's reference on the shared source is
        dropped at fork time)."""
        nb_sh = blocks_needed(entry["len"], self.block_len)
        fork = 0 if entry["len"] % self.block_len == 0 else 1
        return blocks_needed(map_tokens, self.block_len) - nb_sh + fork

    def _reclaimable_blocks(self) -> int:
        """Blocks an admission could obtain right now: the free list
        plus whatever evicting the whole unpinned radix tree would
        return (cache-only references — `_alloc_admit` realizes them
        LRU-first on demand)."""
        extra = (self._radix.evictable_blocks
                 if self._radix is not None else 0)
        return self.pool.free_blocks + extra

    def _match_radix(self, prompt) -> Optional[dict]:
        """Longest block-aligned radix-cached prefix of `prompt` as a
        synthetic CoW entry (the same dict shape `_match_prefix`
        returns, minus cached probs — a radix match is always capped
        BELOW the full prompt, so the suffix-extension score path
        computes the first token and no cached distribution is ever
        needed; block alignment means the mid-block fork never
        fires)."""
        if self._radix is None:
            return None
        P = int(prompt.shape[0])
        matched, blocks = self._radix.match(prompt)
        if matched >= P:
            matched -= self.block_len
            blocks = blocks[:-1]
        if matched <= 0:
            return None
        return dict(tokens=np.asarray(prompt[:matched], np.int64),
                    len=matched, blocks=blocks, probs=None, radix=True)

    def _alloc_admit(self, n: int) -> Optional[List[int]]:
        """Admission-path allocation: on pool exhaustion, evict radix
        LRU leaves (cache-only references — never a live slot) until
        the grant fits or nothing evictable remains."""
        got = self.pool.allocator.allocate(n)
        while got is None and self._radix is not None:
            if not self._radix.evict_lru():
                break
            self.radix_evictions_total += 1
            got = self.pool.allocator.allocate(n)
        return got

    def can_admit(self, prompt_len: int, n_tokens: int,
                  prompt_ids=None) -> bool:
        if not any(s is None for s in self.slots):
            return False
        if prompt_ids is not None and (self._prefixes
                                       or self._radix is not None):
            prompt = np.asarray(prompt_ids)
            entry = self._match_prefix(prompt)
            if entry is None:
                entry = self._match_radix(prompt)
            if entry is not None:
                map_tokens = (prompt_len if self.allocation == "incremental"
                              else prompt_len + n_tokens)
                return (self._cow_fresh_blocks(entry, map_tokens)
                        <= self._reclaimable_blocks())
        need = self._admit_blocks(prompt_len, n_tokens)
        if self.window_ring and (self._ring_need(need)
                                 > self.pool.window_allocator.free_blocks):
            return False
        return need <= self._reclaimable_blocks()

    def check_budget(self, prompt_len: int, n_tokens: int,
                     prompt_ids=None):
        """Reject requests that can NEVER be admitted — distinct from
        `can_admit` (not right now): over the per-sequence page budget,
        or needing more blocks AT THE END than the pool can ever free
        up (under incremental allocation a request must still be able
        to finish alone in the pool — pool-pressure preemption can
        evict every OTHER slot, never conjure capacity, and blocks
        pinned by the shared-prefix cache never free). With
        `prompt_ids`, a request that RIDES a registered prefix is
        charged only its fresh blocks — sharing is exactly what makes
        an otherwise-oversized request admittable."""
        total = prompt_len + n_tokens
        if n_tokens < 1:
            raise ValueError(f"n_tokens must be >= 1; got {n_tokens}")
        if total > self.max_total_tokens:
            raise ValueError(
                f"prompt ({prompt_len}) + n_tokens ({n_tokens}) = {total} "
                f"exceeds the per-sequence page budget "
                f"{self.max_total_tokens} (max_blocks {self.max_blocks} x "
                f"block_len {self.block_len}); this request can never be "
                f"admitted — rebuild the model with a larger max_len")
        if (self.max_prefill_tokens is not None
                and self._bucket(prompt_len) > self.max_prefill_tokens):
            raise ValueError(
                f"a prompt of {prompt_len} tokens pads to "
                f"{self._bucket(prompt_len)}, over the server's "
                f"max_prefill_tokens {self.max_prefill_tokens}; it can "
                f"never be admitted")
        # id 0 is the garbage block; prefix-cache pins never free
        usable = self.pool.n_blocks - 1 - self.prefix_pinned_blocks
        needed = blocks_needed(total, self.block_len)
        if prompt_ids is not None and self._prefixes:
            entry = self._match_prefix(np.asarray(prompt_ids))
            if entry is not None:
                needed = self._cow_fresh_blocks(entry, total)
        if self.window_ring and (self._ring_need(needed)
                                 > self.pool.window_blocks - 1):
            raise ValueError(
                f"request needs {self._ring_need(needed)} blocks of the "
                f"window layers' pool but it only has "
                f"{self.pool.window_blocks - 1} usable (window_blocks "
                f"{self.pool.window_blocks} incl. the reserved garbage "
                f"block); it can never be admitted — grow window_blocks")
        if needed > usable:
            raise ValueError(
                f"request needs {needed} "
                f"pool blocks but the pool only has {usable} usable "
                f"(n_blocks {self.pool.n_blocks} incl. the reserved "
                f"garbage block and {self.prefix_pinned_blocks} pinned "
                f"by registered prefixes); it can never be admitted — "
                f"grow n_blocks or shorten the request")

    # ----------------------------------------------------------- sampling
    def _sample_ids(self, probs, keys, emit_idx, temp, top_p,
                    greedy_only: bool = False, live=None):
        """Next token per row of `probs` [S, V]: greedy argmax where
        temp == 0 (bit-identical to `generate(temperature=0)`), else
        the same log/clip/filter/categorical chain `generate` runs —
        with a PER-SLOT key folded by emit index, the serving rng
        contract. `greedy_only=True` (a STATIC program variant the
        scheduler picks when no sampled request is in flight) has no
        sort or threefry operation in it at all: on the v5e
        `gpt2-medium`'s 32-slot decode step takes 1.18 ms so, 1.22 as
        the full variant with no row to sample for and 1.92 with one
        (3.51 when the chain ran over all 32 rows; PERF.md 5, PR 34).

        The chain runs over the rows that sample and no others: the
        rows with `temp > 0` that are `live` (the decode step's mask: a
        released slot keeps its temperature; None where every row is a
        request's) are gathered `_SAMPLE_CHUNK_ROWS` at a time by a
        loop whose trip count the device computes, so a step with no
        sampled row sorts nothing and a step with one sorts one chunk.
        A row's arithmetic does not depend on the rows beside it: its
        token is the one the chain over the whole matrix gives."""
        greedy_ids = jnp.argmax(probs, axis=-1).astype(jnp.int32)
        if greedy_only:
            return greedy_ids
        from deeplearning4j_tpu.zoo.transformer import filter_logits
        R = min(_SAMPLE_CHUNK_ROWS, probs.shape[0])
        wanted = temp > 0 if live is None else (temp > 0) & live
        place = jnp.cumsum(wanted) - 1     # a wanted row's rank among them

        def chunk(c, ids):
            # hit[r, s]: row s is the chunk's r-th. A chunk's unfilled
            # places read row 0, whose result no row takes
            hit = wanted & (place == (c * R + jnp.arange(R))[:, None])
            rows = jnp.argmax(hit, axis=1)
            t = temp[rows]
            safe_t = jnp.where(t > 0, t, 1.0)
            logits = (jnp.log(jnp.clip(probs[rows], 1e-9, None))
                      / safe_t[:, None])
            # generate()'s own filter body, with per-slot traced p
            # (p=1.0 keeps everything)
            logits = filter_logits(logits, self.top_k,
                                   top_p[rows][:, None])
            skeys = jax.vmap(jax.random.fold_in)(keys[rows],
                                                 emit_idx[rows])
            sampled = jax.vmap(jax.random.categorical)(skeys, logits)
            return jnp.where(
                hit.any(axis=0),
                jnp.where(hit, sampled.astype(jnp.int32)[:, None],
                          0).sum(axis=0),
                ids)

        return jax.lax.fori_loop(0, (place[-1] + R) // R, chunk,
                                 greedy_ids)

    # ------------------------------------------------------ jit builders
    def _shared_jit(self, key, builder):
        """Jitted-program cache shared ACROSS engines of the same net —
        anchored on `net.__dict__` (the `get_prefill_bucketed` idiom).
        A per-engine `jax.jit(closure)` is a fresh callable every
        construction, so every hot-swap successor and every tenant of a
        shared base used to pay the full ~10s+ decode/admit compile
        again; a `tenancy._TenantNetView` pre-seeds this attribute with
        the base net's dict, so N tenant servers and every adapter
        swap reuse ONE compile (params are arguments, never baked in).
        Keys carry every non-shape static the closure bakes into the
        trace (plan, scan length, greedy variant, top_k, block_len) —
        shape specialization is jit's own per-shape cache."""
        cache = self.net.__dict__.setdefault("_serving_jit_cache", {})
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = builder()
        return fn

    def _tables_arg(self, full=None, window=None):
        """Fresh device copies of the host's tables (or of the ones
        given: a draft's masked tables, the row tables an admission
        scatters by) as the programs take them: the one table, or the
        (full, window) pair of a two-pool net."""
        full = self.block_tables if full is None else full
        if not self.window_ring:
            return jnp.asarray(full)
        return (jnp.asarray(full), jnp.asarray(
            self.window_tables if window is None else window))

    def _decode_body(self, greedy_only: bool):
        """The decode-chunk python body (jitted by `_build_decode`;
        traced directly by `decode_cost_report` for the byte-table
        evidence)."""
        net, layers, plan = self.net, self.net.layers, self._plan
        J = self.steps_per_dispatch

        def one_token(params, state, kv, block_tables, token_ids, pos,
                      live, keys, emit_idx, temp, top_p):
            h = token_ids[:, None]            # [S, 1] int ids
            kv = list(kv)
            stats = {}
            for entry in plan:
                kind, i = entry[0], entry[1]
                layer = layers[i]
                lp = params.get(str(i), {})
                ls = state.get(str(i), {})
                if kind == "plain":
                    h, _ = layer.forward(lp, ls, h, train=False, rng=None)
                elif kind == "pos":
                    h, _ = layer.forward_at_positions(lp, ls, h, pos)
                elif kind == "state":
                    j = entry[2]
                    h, kv[j] = layer.state_step(lp, h, kv[j], live)
                else:
                    j = entry[2]
                    h, kv[j] = layer.paged_step(
                        lp, h, kv[j], plan_table(block_tables, entry),
                        pos, live, stats=stats)
            probs = h[:, -1]                   # [S, V]
            return (tuple(kv), self._sample_ids(probs, keys, emit_idx,
                                                temp, top_p,
                                                greedy_only=greedy_only,
                                                live=live),
                    _moe_means(stats))

        def decode_step(params, state, kv, block_tables, carry, fresh,
                        keys, temp, top_p):
            """`steps_per_dispatch` micro-steps fused into ONE program
            via lax.scan: host round-trip and dispatch overhead
            amortize over J tokens x S slots (the continuous-batching
            counterpart of `generate()`'s fused decode scan). J=1 is the
            admit-every-token schedule the scheduler defaults to.

            `carry` [4, S] is what the previous decode program left on
            the device: each slot's last token, position, tokens still
            to emit and emit index. `fresh` [5, S] is what the host
            changed since (`_fresh_rows`: a mask, then the four
            values): a slot admitted, released or rewritten by another
            program takes the host's values, every other slot goes on
            from the carry, so the host need not have read the previous
            step's tokens to launch this one.

            `remaining > 0` is each micro-step's `live` mask, and only a
            live slot advances: a slot that finishes mid-chunk, like a
            freed one, keeps its `pos` (its writes land in its own
            pages or the garbage block, never another slot's, and the
            in-place attention kernel reads no page for it), so the
            carry out equals the host's mirrors, which advance by the
            same rule (`taken = min(J, remaining)`)."""
            params = net.dtype.cast_params(params)
            tok, pos, rem, emit = jnp.where(fresh[0] > 0, fresh[1:], carry)

            def micro(carry, _):
                kv, tok, pos, rem, emit = carry
                live = rem > 0
                kv, nxt, moe = one_token(params, state, kv, block_tables,
                                         tok, pos, live, keys, emit,
                                         temp, top_p)
                adv = live.astype(pos.dtype)
                return ((kv, jnp.where(live, nxt, tok), pos + adv,
                         rem - adv, emit + adv), (nxt, moe))

            (kv, tok, pos, rem, emit), (toks, moe) = jax.lax.scan(
                micro, (kv, tok, pos, rem, emit), None, length=J)
            # toks [J, S]: micro-step j's is real where `remaining > j`,
            # which the host knows; `moe` is () for a net with no routed
            # experts (the program then has no such output), else two
            # [J] rows
            return kv, toks, moe, jnp.stack([tok, pos, rem, emit])

        return decode_step

    def _build_decode(self, greedy_only: bool):
        return self._shared_jit(
            ("decode", greedy_only, self.steps_per_dispatch,
             tuple(self._plan), self.top_k, self._in_place),
            lambda: jax.jit(self._decode_body(greedy_only),
                            donate_argnums=donate_argnums(2)))

    def decode_cost_report(self) -> dict:
        """Byte accounting of the REAL decode program (greedy variant)
        via the hlo_cost per-op tables — the quantization ledger's
        evidence seam: weight HBM bytes of the params tree the program
        reads, split matmul-weights vs total, plus the per-op
        operand+result byte totals of one traced decode chunk."""
        from benchtools import hlo_cost

        S = self.n_slots
        args = (self._params, self.net.net_state, self.pool.kv,
                *self._decode_args())
        jaxpr = jax.make_jaxpr(self._decode_body(greedy_only=True))(*args)
        table = hlo_cost.per_op_table(jaxpr,
                                      fused_steps=self.steps_per_dispatch)
        mm_keys = quant.quantized_weight_keys(self.net)
        mm_bytes = quant.weight_bytes(
            {lk: {pk: self._params[lk][pk] for pk in pks}
             for lk, pks in mm_keys.items()})
        return {
            "quantize": self.quantize,
            "weight_bytes": quant.weight_bytes(self._params),
            "matmul_weight_bytes": mm_bytes,
            "decode_bytes_per_step": table["total_bytes_per_step"],
            "decode_flops_per_step": table["total_flops_per_step"],
            "n_slots": S,
        }

    def _prefill_paged_body(self):
        """Prefill through the paged protocol: each paged layer's
        `forward_prefill` returns the rows its pages are cut from (as
        long as the prompt bucket, padded to whole blocks), each state
        layer's the state every row reached at its last real token, and only
        each prompt's LAST position goes through the layers after the
        last paged one (the final norm, the vocabulary head: logits of
        every position of an 8k-token prompt would be gigabytes)."""
        net, layers, plan = self.net, self.net.layers, self._plan
        bl = self.block_len
        last_cached = max(n for n, e in enumerate(plan)
                          if e[0] in ("block", "state"))

        def prefill(params, state, x, last_idx):
            params = net.dtype.cast_params(params)
            h = x
            lengths = last_idx + 1
            rows_out, states_out = [], []
            stats = {}
            for n, entry in enumerate(plan):
                kind, i = entry[0], entry[1]
                layer = layers[i]
                lp = params.get(str(i), {})
                ls = state.get(str(i), {})
                if kind == "plain":
                    h, _ = layer.forward(lp, ls, h, train=False, rng=None)
                elif kind == "pos":
                    h, _ = layer.forward_at_positions(
                        lp, ls, h, jnp.broadcast_to(
                            jnp.arange(h.shape[1]), h.shape[:2]))
                elif kind == "state":
                    h, reached = layer.forward_prefill(lp, h, lengths,
                                                       stats=stats)
                    states_out.append(tuple(reached))
                else:
                    h, rows = layer.forward_prefill(lp, h, lengths,
                                                    stats=stats)
                    pad = -h.shape[1] % bl
                    rows_out.append(tuple(
                        jnp.pad(r, ((0, 0), (0, pad), (0, 0)))
                        for r in rows))
                if n == last_cached:
                    h = h[jnp.arange(h.shape[0]), last_idx][:, None]
            # in the pools' own order: the paged layers' rows, then the
            # state layers' states
            return (h[:, -1], tuple(rows_out) + tuple(states_out),
                    _moe_means(stats))

        return prefill

    def _run_prefill(self, prompts, last_idx):
        """One prefill dispatch over right-padded `prompts` [k, Pb] ->
        (probs [k, V] at each row's `last_idx`, per paged layer the
        `[k, C, width]` arrays its pages are cut from, the routed
        expert layers' device scalars for `_take_moe`)."""
        net = self.net
        if self._paged_prefill:
            fn = self._shared_jit(
                ("prefill_paged", tuple(self._plan), self.block_len),
                lambda: jax.jit(self._prefill_paged_body()))
            return fn(self._params, net.net_state, jnp.asarray(prompts),
                      jnp.asarray(last_idx))
        from deeplearning4j_tpu.zoo.transformer import get_prefill_bucketed
        k = prompts.shape[0]
        carries = {str(i): layer.init_carry(k, net.dtype.compute_dtype)
                   for i, layer in enumerate(net.layers)
                   if isinstance(layer, BaseRecurrentLayer)}
        probs, carries = get_prefill_bucketed(net)(
            self._params, net.net_state, jnp.asarray(prompts), carries,
            jnp.asarray(last_idx))
        return probs, tuple(net.layers[i].carry_pages(carries[str(i)])
                            for i in self.pool.layer_indices), ()

    def _take_moe(self, moe):
        """Device scalars from a program's routed expert layers -> the
        host pair `moe_stats` (the mean over a fused chunk's steps), at
        a point where the caller has already blocked on that program's
        tokens."""
        self.moe_stats = (tuple(float(np.mean(np.asarray(m))) for m in moe)
                          if moe else None)

    def _build_admit_finish(self, k: int, greedy_only: bool):
        """One fused dispatch completing a k-wide admission wave:
        scatter every sequence's monolithic prefill K/V into its pool
        pages AND sample the wave's first tokens from the prefill
        probs. Separate per-request dispatches here were measured to
        cost as much as a whole `generate()` call each on the CPU
        sandbox — admission overhead is exactly what the sequential
        baseline pays, so it must be amortized for continuous batching
        to win."""
        bl = self.block_len
        two, kinds = bool(self.window_ring), self.pool.window_layers
        n_paged, n_state = self.pool.n_paged, self.state_layers

        def admit_finish(kv, rows, block_carries, probs, keys, emit0,
                         temp, top_p):
            # rows [k, max_rows]; block_carries: per paged layer the
            # arrays its pool's pages are cut from (`carry_pages` /
            # `forward_prefill`), leading dim k; probs [k, V]; emit0 [k] is
            # the sampled-rng emit offset (nonzero for a requeued
            # continuation — its stream keeps the fold_in(key, t)
            # indices it would have had uninterrupted)
            # a two-pool net's `rows` is the (full, window) pair; a net
            # with state layers pairs that with the wave's slots [k]
            # (`n_slots`, out of range, for a dummy row: dropped)
            if n_state:
                rows, slots = rows
            out = []
            for pools, caches, ring in zip(kv[:n_paged], block_carries,
                                           kinds):
                C = caches[0].shape[1]     # [k, C, ...] -> pages
                flat_rows = (rows[ring] if two else rows)[
                    :, :C // bl].reshape(-1)
                out.append(tuple(
                    pool.at[flat_rows].set(cache.reshape(
                        (k * (C // bl), bl, pool.shape[-1])
                    ).astype(pool.dtype))
                    for pool, cache in zip(pools, caches)))
            for arrays, reached, axes in zip(kv[n_paged:],
                                             block_carries[n_paged:],
                                             self.pool.state_axes):
                # a slot's row is overwritten whole: whatever the slot's
                # last request left is gone
                out.append(tuple(
                    a.at[(slice(None),) * ax + (slots,)].set(
                        r.astype(a.dtype), mode="drop")
                    for a, r, ax in zip(arrays, reached, axes)))
            firsts = self._sample_ids(probs, keys, emit0, temp, top_p,
                                      greedy_only=greedy_only)
            return tuple(out), firsts

        return self._shared_jit(
            ("admit", int(k), greedy_only, self.block_len, self.top_k)
            + ((kinds,) if two else ())
            + ((("state", n_state),) if n_state else ()),
            lambda: jax.jit(admit_finish,
                            donate_argnums=donate_argnums(0)))

    def _score_body(self, greedy_only: bool):
        """The K-position score program (zoo.transformer.
        paged_score_forward): ONE target-model dispatch scores K
        proposed tokens per slot — speculative decoding's target half
        — or extends a shared prefix by a K-bucketed suffix (CoW
        admission). Returns (kv', greedy_mat [S, K] — the target's
        argmax after each position, the acceptance oracle — and
        chosen [S], the sampled/greedy token at each slot's LAST valid
        position, which is the first emitted token on the suffix
        path and the sampled-slot token on the speculative path)."""
        net, plan = self.net, self._plan
        from deeplearning4j_tpu.zoo.transformer import paged_score_forward

        def score(params, state, kv, block_tables, token_mat, pos,
                  n_valid, keys, emit_idx, temp, top_p):
            params = net.dtype.cast_params(params)
            kv, probs = paged_score_forward(
                net, plan, params, state, kv, block_tables, token_mat,
                pos, n_valid)
            greedy_mat = jnp.argmax(probs, axis=-1).astype(jnp.int32)
            last = jnp.take_along_axis(
                probs, jnp.maximum(n_valid - 1, 0)[:, None, None],
                axis=1)[:, 0]                              # [S, V]
            chosen = self._sample_ids(last, keys, emit_idx, temp, top_p,
                                      greedy_only=greedy_only)
            return kv, greedy_mat, chosen

        return score

    def _score_rs_body(self):
        """The sampled-speculation score variant (`spec_sampled=True`
        dispatches with sampled slots in flight): same target forward,
        but the sampling tail is the rejection-sampling chain
        (zoo.transformer.rejection_sample_drafts) — per slot it
        returns how many leading drafts survived (`n_acc`) and the
        residual/bonus token at the first divergence (`final`).
        Greedy slots in the same dispatch keep the bit-exact argmax
        oracle: the host reads their rows from `greedy_mat` and
        ignores the sampled outputs."""
        net, plan = self.net, self._plan
        from deeplearning4j_tpu.zoo.transformer import (
            paged_score_forward, rejection_sample_drafts)

        def score(params, state, kv, block_tables, token_mat, pos,
                  n_valid, keys, emit_idx, temp, top_p):
            params = net.dtype.cast_params(params)
            kv, probs = paged_score_forward(
                net, plan, params, state, kv, block_tables, token_mat,
                pos, n_valid)
            greedy_mat = jnp.argmax(probs, axis=-1).astype(jnp.int32)
            n_acc, final = rejection_sample_drafts(
                probs, token_mat, n_valid, keys, emit_idx, temp,
                top_p, self.top_k)
            return kv, greedy_mat, n_acc, final

        return score

    def _get_score(self, K: int, variant):
        """`variant`: True/False = the greedy_only split, "rs" = the
        rejection-sampling tail (sampled speculation)."""
        key = (int(K), variant)
        fn = self._score.get(key)
        if fn is None:
            def build():
                body = (self._score_rs_body() if variant == "rs"
                        else self._score_body(variant))
                return jax.jit(body, donate_argnums=donate_argnums(2))
            fn = self._score[key] = self._shared_jit(
                ("score", int(K), variant, tuple(self._plan),
                 self.top_k), build)
        return fn

    def _build_fork(self):
        """Copy-on-write block fork: one dispatch copies a vector of
        pool blocks src -> dst across every layer's K and V pool.
        Unused lanes point both ids at the garbage block (a garbage-
        to-garbage self-copy — the one block whose content is never
        read). One jit; each pow2 pair-vector width is its own
        shape-keyed executable."""

        def fork(kv, src, dst):
            return tuple(tuple(pool.at[dst].set(pool[src])
                               for pool in pools) for pools in kv)

        return self._shared_jit(
            ("fork",),
            lambda: jax.jit(fork, donate_argnums=donate_argnums(0)))

    def _run_fork(self, pairs):
        w = 1
        while w < len(pairs):
            w *= 2
        src = np.full(w, GARBAGE_BLOCK, np.int32)
        dst = np.full(w, GARBAGE_BLOCK, np.int32)
        for i, (s, d) in enumerate(pairs):
            src[i], dst[i] = s, d
        if self._fork is None:
            self._fork = self._build_fork()
        self.pool.kv = self._fork(self.pool.kv, jnp.asarray(src),
                                  jnp.asarray(dst))
        self.prefix_forks_total += len(pairs)

    def _build_first_token(self, greedy_only: bool):
        """Sampling tail alone (no forward): first tokens of exact-
        prefix-match admissions, whose next-token distribution was
        cached at registration. The same `_sample_ids` chain the
        admit/decode programs run — same math, same bits."""

        def first(probs, keys, emit0, temp, top_p):
            return self._sample_ids(probs, keys, emit0, temp, top_p,
                                    greedy_only=greedy_only)

        return self._shared_jit(("first", greedy_only, self.top_k),
                                lambda: jax.jit(first))

    def _draft_body(self):
        """The truncated-layer draft scan: k-1 greedy micro-steps of
        the FIRST `spec_draft_layers` transformer blocks (same
        weights, same embedding/positional/unembedding — the plan
        minus its deep blocks) fused into one program. The slot's real
        pages are the draft model's KV cache for free: layer-i K/V
        depends only on layers < i, so the full model's committed
        pages ARE the truncated model's. Draft K/V writes land in the
        slot's not-yet-committed write window — every one of those
        positions is rewritten with full-model K/V by the verify
        dispatch in the same `_spec_step` (write-before-read, the same
        discipline rejected speculative lanes ride). Non-drafting
        slots' table rows point at the garbage block."""
        net, layers = self.net, self.net.layers
        dplan = self._draft_plan

        def draft(params, state, kv, block_tables, token_ids, pos,
                  live):
            params = net.dtype.cast_params(params)

            def micro(carry, _):
                kv, tok, pos = carry
                h = tok[:, None]            # [S, 1] int ids
                kv = list(kv)
                for entry in dplan:
                    kind, i = entry[0], entry[1]
                    layer = layers[i]
                    lp = params.get(str(i), {})
                    ls = state.get(str(i), {})
                    if kind == "plain":
                        h, _ = layer.forward(lp, ls, h, train=False,
                                             rng=None)
                    elif kind == "pos":
                        h, _ = layer.forward_at_positions(lp, ls, h, pos)
                    else:
                        j = entry[2]
                        h, kv[j] = layer.paged_step(
                            lp, h, kv[j],
                            plan_table(block_tables, entry), pos, live)
                nxt = jnp.argmax(h[:, -1], axis=-1).astype(jnp.int32)
                return (tuple(kv), nxt, pos + 1), nxt

            carry = (kv, token_ids, pos)
            (kv, _, _), drafts = jax.lax.scan(micro, carry, None,
                                              length=self.spec_k - 1)
            return kv, drafts               # [k-1, S]

        return draft

    def _run_draft(self, trunc_slots):
        """One truncated-layer draft dispatch over `trunc_slots`
        ([(slot, depth)] — write windows already granted/forked).
        Returns the [k-1, S] draft matrix; rows of non-participating
        slots are garbage and never read. Ledger: draft positions
        never emit directly (the verify dispatch emits), so the real
        lanes are speculation overhead — spec_rejected — and the
        masked lanes padding."""
        S, K = self.n_slots, self.spec_k
        mask = np.zeros(S, bool)
        for s, _ in trunc_slots:
            mask[s] = True
        tables = self._tables_arg(
            np.where(mask[:, None], self.block_tables,
                     GARBAGE_BLOCK).astype(np.int32),
            np.where(mask[:, None], self.window_tables,
                     GARBAGE_BLOCK).astype(np.int32)
            if self.window_ring else None)
        if self._draft_fn is None:
            self._draft_fn = self._shared_jit(
                ("draft", self.spec_k, tuple(self._draft_plan or ()),
                 self._in_place),
                lambda: jax.jit(self._draft_body(),
                                donate_argnums=donate_argnums(2)))
        kv, drafts = self._draft_fn(
            self._params, self.net.net_state, self.pool.kv,
            tables, jnp.asarray(self.last_token),
            jnp.asarray(self.pos), jnp.asarray(mask))
        self.pool.kv = kv
        self.spec_draft_dispatches_total += 1
        real = sum(d - 1 for _, d in trunc_slots)
        self.goodput.account(spec_rejected=real,
                             pad_waste=(K - 1) * S - real)
        return np.asarray(drafts)

    # ------------------------------------------------- shared prefixes
    def register_prefix(self, token_ids) -> tuple:
        """Warm a shared prompt prefix into the pool ONCE: prefill it
        (the same bucketed-prefill program family admission waves
        run), scatter its K/V into dedicated pool blocks, and pin
        those blocks under a cache-held allocator reference. Every
        later admission whose prompt starts with these ids maps the
        blocks instead of re-prefilling them (`serving_prefix_hits_
        total` / `serving_prefix_blocks_shared`). Idempotent per id
        sequence; returns the registry key. Raises when the pool
        cannot host the prefix right now — registration is a capacity
        commitment, not a best-effort hint."""
        self._refuse_two_pools("a registered prefix")
        self._refuse_state("a registered prefix")
        prompt = np.asarray(token_ids)
        if prompt.ndim == 2 and prompt.shape[0] == 1:
            prompt = prompt[0]
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"prefix must be a non-empty 1-D id sequence; got "
                f"shape {prompt.shape}")
        P = int(prompt.shape[0])
        if P >= self.max_total_tokens:
            raise ValueError(
                f"prefix of {P} tokens leaves no room to generate "
                f"under the {self.max_total_tokens}-token page budget")
        key = tuple(int(t) for t in prompt)
        if key in self._prefixes:
            return key
        nb = blocks_needed(P, self.block_len)
        blocks = self.pool.allocator.allocate(nb)
        if blocks is None:
            raise ValueError(
                f"pool cannot host a {nb}-block prefix right now "
                f"({self.pool.free_blocks} free) — register prefixes "
                f"before admitting traffic, or grow n_blocks")
        try:
            Pb = self._bucket(P)
            prompts = np.zeros((1, Pb), np.int32)
            prompts[0, :P] = prompt
            probs, block_carries, _ = self._run_prefill(
                prompts, np.asarray([P - 1], np.int32))
            max_rows = max(c[0].shape[1] // self.block_len
                           for c in block_carries)
            rows = np.full((1, max_rows), GARBAGE_BLOCK, np.int32)
            rows[0, :nb] = blocks
            # the admit_finish program scatters the pages; its sampled
            # first token is discarded (registration emits nothing) —
            # but the LAST-position probs are kept: an exact-match
            # admission samples its first token from them with no
            # forward pass at all
            fin = self._admit_finish.get((1, True))
            if fin is None:
                fin = self._admit_finish[(1, True)] = \
                    self._build_admit_finish(1, True)
            self.pool.kv, _ = fin(
                self.pool.kv, jnp.asarray(rows), block_carries, probs,
                jnp.zeros((1, 2), np.uint32), jnp.zeros(1, np.int32),
                jnp.zeros(1, np.float32), jnp.ones(1, np.float32))
        except Exception:
            self.pool.allocator.free(blocks)
            raise
        self._prefixes[key] = dict(
            tokens=np.asarray(prompt, np.int64), len=P, blocks=blocks,
            probs=np.asarray(probs[0]))
        self.prefix_pinned_blocks += nb
        self.block_grants_total += nb
        # registration prefills once so later admissions don't: the P
        # real positions are useful, the bucket padding is waste
        self.goodput.account(useful=P, pad_waste=Pb - P)
        return key

    def release_prefix(self, key: tuple):
        """Unpin a registered prefix: the cache's block references
        drop; blocks still mapped by in-flight slots stay granted
        until those slots release (the refcount contract)."""
        entry = self._prefixes.pop(tuple(key))
        self.pool.allocator.free(entry["blocks"])
        self.prefix_pinned_blocks -= len(entry["blocks"])

    # ---------------------------------------------------------- admission
    def admit(self, prompt_ids, n_tokens: int, *, request_id=None,
              temperature: float = 0.0, top_p: Optional[float] = None,
              rng=None):
        """Single-request admission (a k=1 `admit_many` wave). Returns
        (slot index, first emitted token, done) or None when capacity
        can't take the request right now."""
        out = self.admit_many([dict(prompt_ids=prompt_ids,
                                    n_tokens=n_tokens,
                                    request_id=request_id,
                                    temperature=temperature,
                                    top_p=top_p, rng=rng)])
        return out[0] if out else None

    def admit_many(self, requests: List[dict]):
        """Admission wave: prefill up to len(requests) prompts — of
        HETEROGENEOUS lengths, right-padded to one power-of-two bucket
        — as one batch through the cached bucketed-prefill jit
        (zoo/transformer.get_prefill_bucketed: `generate()`'s forward
        with a per-slot last-position gather, so prefill numerics are
        its by construction), then one fused dispatch writes all their
        pool pages and samples all their first tokens. Requests beyond
        the wave's slot/block capacity are left unadmitted (the
        returned list is a PREFIX of the input — FIFO order
        preserved).

        Each request dict: prompt_ids, n_tokens, and optionally
        request_id, temperature, top_p, rng, emit_start (a requeued
        continuation's already-emitted token count — offsets the
        sampled-rng fold and the progress ordering). Returns
        [(slot, first_token, done), ...] for the admitted prefix."""
        if not requests:
            return []
        self.admit_info = {}
        self.wait_s = 0.0
        self.admit_tokens = 0
        it = self.loop_it
        wave = []
        try:
            with monitor.span("serve/admit/plan", it=it):
                for r in requests:
                    prompt = np.asarray(r["prompt_ids"])
                    if prompt.ndim == 2 and prompt.shape[0] == 1:
                        prompt = prompt[0]
                    if prompt.ndim != 1 or prompt.size == 0:
                        raise ValueError(
                            f"prompt must be a non-empty 1-D id sequence; "
                            f"got shape {prompt.shape}")
                    P = int(prompt.shape[0])
                    n_tokens = int(r["n_tokens"])
                    self.check_budget(P, n_tokens, prompt_ids=prompt)
                    slot = next((i for i, s in enumerate(self.slots)
                                 if s is None
                                 and all(i != w["slot"] for w in wave)),
                                None)
                    if slot is None:
                        break
                    entry = self._match_prefix(prompt)
                    if entry is None:
                        # no registered exact match — the radix tree
                        # catches block-aligned mid-prompt sharing across
                        # ALL prior admissions (prefix_cache="radix")
                        entry = self._match_radix(prompt)
                    if entry is None:
                        if not self._wave_fits(wave, P):
                            break
                        nb = self._admit_blocks(P, n_tokens)
                        blocks = self._alloc_admit(nb)
                        if blocks is None:
                            break
                        ring = None
                        if self.window_ring:
                            # all-or-nothing across both kinds of pool
                            ring = self.pool.window_allocator.allocate(
                                self._ring_need(nb))
                            if ring is None:
                                self.pool.allocator.free(blocks)
                                break
                        w = dict(blocks=blocks, grants=nb, entry=None,
                                 fork=None, window_blocks=ring)
                    else:
                        w = self._cow_admit_blocks(entry, P, n_tokens)
                        if w is None:
                            break
                    w.update(slot=slot, prompt=prompt, n_tokens=n_tokens,
                             r=r)
                    wave.append(w)
            if not wave:
                return []
            self.admit_tokens = sum(int(w["prompt"].shape[0]) for w in wave)
            out = self._admit_dispatch(wave)
            if self._radix is not None:
                # every admission's fully-written prompt blocks feed
                # the tree on the way in (automatic dedup — no manual
                # register/release); the partial tail block, which the
                # slot will keep writing, never enters
                with monitor.span("serve/admit/post", it=it):
                    for w in wave:
                        slot = self.slots[w["slot"]]
                        if slot is None:  # n_tokens == 1: already done
                            continue
                        n_full = len(w["prompt"]) // self.block_len
                        if n_full:
                            self._radix.insert(w["prompt"],
                                               slot.blocks[:n_full])
            return out
        except Exception:
            # a mid-wave failure (validation of a later request, a
            # prefill/admit dispatch error) must return the wave's
            # already-allocated blocks — no Slot owns them yet, so
            # _release could never recover them and the pool would
            # shrink permanently (capacity leak -> eventual silent
            # starvation of every later admission). Entries a Slot DID
            # take ownership of (partial bookkeeping) keep theirs —
            # the normal release path frees those. A CoW entry's list
            # mixes fresh blocks and shared-prefix references; `free`
            # handles both uniformly (fresh return to the free list,
            # shares decrement back to the cache's own reference).
            for w in wave:
                s = self.slots[w["slot"]]
                if s is None or s.blocks is not w["blocks"]:
                    try:
                        self.pool.allocator.free(w["blocks"])
                        if w.get("window_blocks"):
                            self.pool.window_allocator.free(
                                w["window_blocks"])
                    except ValueError:
                        pass   # already back in the pool
            raise

    def _wave_fits(self, wave, prompt_len: int) -> bool:
        """Would one more fresh prompt of `prompt_len` keep the wave's
        prefill program (pow2 width x prompt bucket) within
        `max_prefill_tokens`?  A wave's first prompt always fits
        (`check_budget` refused the ones that never can)."""
        if self.max_prefill_tokens is None:
            return True
        fresh = [int(w["prompt"].shape[0]) for w in wave
                 if w["entry"] is None]
        k2 = 1
        while k2 < len(fresh) + 1:
            k2 *= 2
        return k2 * self._bucket(max(fresh + [prompt_len])) \
            <= self.max_prefill_tokens

    def _cow_admit_blocks(self, entry: dict, prompt_len: int,
                          n_tokens: int) -> Optional[dict]:
        """Block grants for a shared-prefix admission: take one
        allocator reference per shared prefix block, allocate the fresh
        remainder, and — when the prefix ends mid-block — fork the
        partially-filled tail NOW (copy-on-first-write realized at
        admission: the very next write, suffix prefill or first decode
        token, lands in that block, and a write into a block someone
        else still maps would corrupt every other reader). The fork
        drops this slot's just-taken reference on the shared source
        (the refcount-decrement half of the CoW contract); the cache's
        own reference keeps the source alive for the next admission.
        Returns the wave-entry dict, or None when the pool can't cover
        the fresh blocks right now."""
        alloc = self.pool.allocator
        bl = self.block_len
        P = entry["len"]
        nb_sh = blocks_needed(P, bl)
        map_tokens = (prompt_len if self.allocation == "incremental"
                      else prompt_len + n_tokens)
        n_fresh = self._cow_fresh_blocks(entry, map_tokens)
        # take the shared references BEFORE allocating fresh blocks:
        # covering the fresh grant may evict radix LRU nodes, and an
        # unshared match could be evicted out from under us — the
        # share pins the matched blocks regardless of what the tree
        # does
        alloc.share(entry["blocks"][:nb_sh])
        fresh = [] if n_fresh == 0 else self._alloc_admit(n_fresh)
        if fresh is None:
            alloc.free(entry["blocks"][:nb_sh])
            return None
        if P % bl == 0:
            blocks = list(entry["blocks"][:nb_sh]) + fresh
            fork = None
        else:
            src, dst = entry["blocks"][nb_sh - 1], fresh[0]
            alloc.free([src])                # drop OUR tail reference
            fork = (src, dst)
            blocks = list(entry["blocks"][:nb_sh - 1]) + [dst] + fresh[1:]
        return dict(blocks=blocks, grants=n_fresh, entry=entry, fork=fork)

    def _admit_dispatch(self, wave):
        """Route one capacity-granted admission wave through its
        dispatch paths — full prefill for fresh prompts, fork + suffix
        extension for shared-prefix hits — and return results in the
        wave's (FIFO) input order."""
        results = {}
        norm = [w for w in wave if w["entry"] is None]
        cow = [w for w in wave if w["entry"] is not None]
        if norm:
            self._admit_wave(norm, results)
        if cow:
            self._admit_wave_shared(cow, results)
        return [results[w["slot"]] for w in wave]

    def _admit_wave(self, wave, results):
        k = len(wave)
        # pad the wave WIDTH to the next power of two: every distinct
        # batch width costs a prefill + admit_finish COMPILE, and
        # free-slot counts vary chunk to chunk — unquantized widths
        # were measured as a compile storm that dwarfed the serving
        # itself. Dummy rows repeat the last prompt, scatter only into
        # the garbage block, and their sampled firsts are discarded.
        k2 = 1
        while k2 < k:
            k2 *= 2
        # pad the prompt LENGTHS to one power-of-two bucket (mixed-
        # length waves — the same-length restriction serialized
        # admissions under realistic traffic): right padding is sound
        # because the blocks are causal and the padding rows' K/V land
        # past each slot's position, where every later read masks them
        Pb = self._bucket(max(int(w["prompt"].shape[0]) for w in wave))
        self.admit_bucket = Pb
        it = self.loop_it

        with monitor.span("serve/admit/dispatch", it=it, width=k2,
                          bucket=Pb):
            prompts = np.zeros((k2, Pb), np.int32)
            last_idx = np.zeros(k2, np.int32)
            for j, w in enumerate(wave):
                prompts[j, :w["prompt"].shape[0]] = w["prompt"]
                last_idx[j] = w["prompt"].shape[0] - 1
            for j in range(k, k2):            # dummy width-padding rows
                prompts[j] = prompts[k - 1]
                last_idx[j] = last_idx[k - 1]
            probs, block_carries, moe = self._run_prefill(prompts,
                                                          last_idx)
            max_rows = max(c[0].shape[1] // self.block_len
                           for c in block_carries[:self.pool.n_paged])
            rows = np.full((k2, max_rows), GARBAGE_BLOCK, np.int32)
            ring_rows = (np.full((k2, max_rows), GARBAGE_BLOCK, np.int32)
                         if self.window_ring else None)
            keys = np.zeros((k2, 2), np.uint32)
            emit0 = np.zeros(k2, np.int32)
            temps = np.zeros(k2, np.float32)
            top_ps = np.ones(k2, np.float32)
            for j, w in enumerate(wave):
                # an upfront grant may pass the rows the prefill wrote
                n = min(len(w["blocks"]), max_rows)
                rows[j, :n] = w["blocks"][:n]
                if ring_rows is not None:
                    # of the prompt's logical blocks the ring keeps the
                    # last `window_ring`, block b at column b % ring;
                    # the earlier ones go to the garbage block
                    nb = blocks_needed(len(w["prompt"]), self.block_len)
                    b = np.arange(max(0, nb - self.window_ring), nb)
                    ring_rows[j, b] = np.asarray(
                        w["window_blocks"])[b % self.window_ring]
                r = w["r"]
                if r.get("rng") is not None:
                    keys[j] = np.asarray(r["rng"], np.uint32).reshape(2)
                emit0[j] = int(r.get("emit_start") or 0)
                temps[j] = r.get("temperature") or 0.0
                p = r.get("top_p")
                top_ps[j] = 1.0 if p is None else p
            # all-greedy waves skip the sampling chain (sort + threefry)
            # on the TTFT-critical path — same static-variant split the
            # decode program uses
            greedy = not bool((temps > 0).any())
            fin = self._admit_finish.get((k2, greedy))
            if fin is None:
                fin = self._admit_finish[(k2, greedy)] = \
                    self._build_admit_finish(k2, greedy)
            tables = self._tables_arg(rows, ring_rows)
            if self.state_layers:
                # each row's slot, whose state rows the wave's are
                # written to; a dummy row's is out of range: dropped
                slots = np.full(k2, self.n_slots, np.int32)
                slots[:k] = [w["slot"] for w in wave]
                tables = (tables, jnp.asarray(slots))
            self.pool.kv, firsts = fin(
                self.pool.kv, tables, block_carries, probs,
                jnp.asarray(keys), jnp.asarray(emit0), jnp.asarray(temps),
                jnp.asarray(top_ps))
        with monitor.span("serve/admit/wait", it=it) as sp:
            firsts = np.asarray(firsts)
            self._take_moe(moe)
        self.wait_s += sp.duration_s

        with monitor.span("serve/admit/post", it=it):
            # ledger: the prefill program touched k2*Pb token-positions
            # — live prompt positions are useful, a requeued
            # continuation's re-prefill is preempt_discard (that work
            # was already done once), width/length padding is pad_waste
            fresh = sum(int(w["prompt"].shape[0]) for w in wave
                        if not int(w["r"].get("emit_start") or 0))
            redone = sum(int(w["prompt"].shape[0]) for w in wave
                         if int(w["r"].get("emit_start") or 0))
            self.goodput.account(useful=fresh, preempt_discard=redone,
                                 pad_waste=k2 * Pb - fresh - redone)
            if self.state_layers:
                self.scan_pad_pct = 100.0 * (
                    k2 * Pb - fresh - redone) / (k2 * Pb)

            for j, w in enumerate(wave):
                self._finish_admission(w, int(firsts[j]), keys[j], results)

    def _finish_admission(self, w, first, key, results):
        """Slot bookkeeping shared by the fresh-prefill and shared-
        prefix admission paths (one body — the two must not drift)."""
        slot, prompt, blocks = w["slot"], w["prompt"], w["blocks"]
        n_tokens, r = w["n_tokens"], w["r"]
        emit0 = int(r.get("emit_start") or 0)
        done = n_tokens == 1
        # token history feeds the self-drafting proposer only — a
        # non-speculative server skips the per-admission O(prompt)
        # copy and the per-dispatch extends entirely
        s = Slot(r.get("request_id"), blocks, len(prompt), n_tokens,
                 emit_base=emit0,
                 history=([int(t) for t in prompt] + [first]
                          if self.spec_k else []),
                 window_blocks=w.get("window_blocks"))
        s.emitted = 1
        self.slots[slot] = s
        self.block_tables[slot] = GARBAGE_BLOCK
        self.block_tables[slot, :len(blocks)] = blocks
        if s.window_blocks is not None:
            self.window_tables[slot] = GARBAGE_BLOCK
            self.window_tables[slot, :len(s.window_blocks)] = s.window_blocks
        self.pos[slot] = len(prompt)
        self.remaining[slot] = n_tokens - 1
        self.emit_idx[slot] = emit0 + 1
        self.last_token[slot] = first
        self._tok_host[slot] = True
        self.keys[slot] = key
        self.temp[slot] = r.get("temperature") or 0.0
        p = r.get("top_p")
        self.top_p[slot] = 1.0 if p is None else p
        self.active[slot] = not done
        self.block_grants_total += w["grants"]
        self.admit_info[slot] = {
            "grants": int(w["grants"]),
            "prefix_hit": w["entry"] is not None,
            "tokens_saved": (int(w["entry"]["len"])
                             if w["entry"] is not None else 0),
            "cow_fork": w.get("fork") is not None,
        }
        if w["entry"] is not None:
            self.prefix_hits_total += 1
            self.prefix_tokens_saved_total += w["entry"]["len"]
            if w["entry"].get("radix"):
                self.radix_hit_tokens_total += w["entry"]["len"]
        if done:
            self._release(slot)
        results[slot] = (slot, first, done)

    def _admit_wave_shared(self, wave, results):
        """Shared-prefix (CoW) admission: the prefix blocks are already
        in the pool — fork any mid-block tails, run the K-position
        score program over the suffixes (ONE dispatch extends every
        hit past its shared region, attending the shared blocks
        through the slot's table), and sample first tokens — from the
        suffix scores, or from the prefix's cached last-position probs
        when the prompt IS the prefix. No monolithic prefill runs at
        all: that is the `serving_prefix_prefill_reduction` lever."""
        it = self.loop_it
        S = self.n_slots
        with monitor.span("serve/admit/dispatch", it=it, width=len(wave)):
            # fork copies must land BEFORE any suffix/decode write
            # reaches a block another holder still maps
            pairs = [w["fork"] for w in wave if w["fork"] is not None]
            if pairs:
                self._run_fork(pairs)
            for w in wave:
                slot = w["slot"]
                self.block_tables[slot] = GARBAGE_BLOCK
                self.block_tables[slot, :len(w["blocks"])] = w["blocks"]
                w["suffix"] = w["prompt"][w["entry"]["len"]:]
            keys_by_slot = {}
            firsts = {}
            ext = [w for w in wave if w["suffix"].shape[0] > 0]
            if ext:
                K = bucket_len(max(int(w["suffix"].shape[0]) for w in ext),
                               self.max_total_tokens)
                self.admit_bucket = K
                token_mat = np.zeros((S, K), np.int32)
                n_valid = np.zeros(S, np.int32)
                pos = np.zeros(S, np.int32)
                keys = np.zeros((S, 2), np.uint32)
                emit0 = np.zeros(S, np.int32)
                temps = np.zeros(S, np.float32)
                top_ps = np.ones(S, np.float32)
                for w in ext:
                    s, r = w["slot"], w["r"]
                    Ts = int(w["suffix"].shape[0])
                    token_mat[s, :Ts] = w["suffix"]
                    n_valid[s] = Ts
                    pos[s] = w["entry"]["len"]
                    if r.get("rng") is not None:
                        keys[s] = np.asarray(r["rng"],
                                             np.uint32).reshape(2)
                    emit0[s] = int(r.get("emit_start") or 0)
                    temps[s] = r.get("temperature") or 0.0
                    p = r.get("top_p")
                    top_ps[s] = 1.0 if p is None else p
                    keys_by_slot[s] = keys[s].copy()
                greedy = not bool((temps > 0).any())
                score = self._get_score(K, greedy)
                kv, _, chosen = score(
                    self._params, self.net.net_state, self.pool.kv,
                    jnp.asarray(self.block_tables), jnp.asarray(token_mat),
                    jnp.asarray(pos), jnp.asarray(n_valid),
                    jnp.asarray(keys), jnp.asarray(emit0),
                    jnp.asarray(temps), jnp.asarray(top_ps))
                self.pool.kv = kv
        if ext:
            with monitor.span("serve/admit/wait", it=it) as sp:
                chosen = np.asarray(chosen)
            self.wait_s += sp.duration_s
            for w in ext:
                firsts[w["slot"]] = int(chosen[w["slot"]])
            # ledger: the suffix-extension score program touched S*K
            # positions — live suffix positions are useful (the shared
            # prefix itself was accounted at registration), requeued
            # continuations are preempt_discard, the rest is padding
            fresh = sum(int(w["suffix"].shape[0]) for w in ext
                        if not int(w["r"].get("emit_start") or 0))
            redone = sum(int(w["suffix"].shape[0]) for w in ext
                         if int(w["r"].get("emit_start") or 0))
            self.goodput.account(useful=fresh, preempt_discard=redone,
                                 pad_waste=S * K - fresh - redone)
        # exact-match admissions (prompt == prefix): next-token probs
        # were computed ONCE at registration — nothing to prefill,
        # just run the sampling tail on the cached distribution
        empt = [w for w in wave if w["suffix"].shape[0] == 0]
        if empt:
            with monitor.span("serve/admit/dispatch", it=it,
                              width=len(empt)):
                width = 1
                while width < len(empt):
                    width *= 2
                probs0 = empt[0]["entry"]["probs"]
                probs = np.zeros((width,) + probs0.shape, probs0.dtype)
                keys = np.zeros((width, 2), np.uint32)
                emit0 = np.zeros(width, np.int32)
                temps = np.zeros(width, np.float32)
                top_ps = np.ones(width, np.float32)
                for j, w in enumerate(empt):
                    r = w["r"]
                    probs[j] = w["entry"]["probs"]
                    if r.get("rng") is not None:
                        keys[j] = np.asarray(r["rng"],
                                             np.uint32).reshape(2)
                    emit0[j] = int(r.get("emit_start") or 0)
                    temps[j] = r.get("temperature") or 0.0
                    p = r.get("top_p")
                    top_ps[j] = 1.0 if p is None else p
                    keys_by_slot[w["slot"]] = keys[j].copy()
                greedy = not bool((temps > 0).any())
                fn = self._first_token.get(greedy)
                if fn is None:
                    fn = self._first_token[greedy] = \
                        self._build_first_token(greedy)
                ids = fn(jnp.asarray(probs), jnp.asarray(keys),
                         jnp.asarray(emit0), jnp.asarray(temps),
                         jnp.asarray(top_ps))
            with monitor.span("serve/admit/wait", it=it) as sp:
                ids = np.asarray(ids)
            self.wait_s += sp.duration_s
            for j, w in enumerate(empt):
                firsts[w["slot"]] = int(ids[j])
        with monitor.span("serve/admit/post", it=it):
            for w in wave:
                self._finish_admission(w, firsts[w["slot"]],
                                       keys_by_slot[w["slot"]], results)

    # -------------------------------------------- incremental block grants
    def _lowest_progress_active(self) -> int:
        """The pool-pressure eviction victim: the active slot whose
        REQUEST has emitted the fewest tokens (requeue costs it the
        least re-prefill work). Ties break toward the higher slot
        INDEX — an arbitrary but deterministic order (slot index is
        not admission order once retired slots are reused)."""
        best, best_p = -1, None
        for i in np.flatnonzero(self.active):
            i = int(i)
            p = self.slots[i].progress
            if best_p is None or p <= best_p:
                best, best_p = i, p
        return best

    def _preempt(self, slot: int):
        # the requeued continuation is the prompt and every token
        # emitted, those of the step in flight too: read it first
        self._settle()
        s = self.slots[slot]
        self._preempted.append({
            "slot": slot, "request_id": s.request_id,
            "emitted": s.progress,
        })
        self.evict_requeue_total += 1
        self._release(slot)

    def drain_preempted(self) -> List[dict]:
        """Preemption notices since the last drain: [{slot, request_id,
        emitted}] — the scheduler requeues each request as a
        continuation (prompt + its emitted tokens, emit_start set) at
        the head of the admission queue."""
        out, self._preempted = self._preempted, []
        return out

    def _allocate_under_pressure(self, s: int, n: int, allocator=None):
        """Allocate `n` blocks for slot `s` (from `allocator`; by
        default the pool of the layers that keep every position),
        preempting the lowest-progress slot under pool pressure
        (requeue, not deadlock); returns None when `s` itself lost the
        pool race (it has been preempted and released)."""
        allocator = allocator or self.pool.allocator
        got = allocator.allocate(n)
        while got is None:
            # radix LRU leaves go first — cache-only references, no
            # re-prefill cost — before any live slot is preempted
            if self._radix is not None and self._radix.evict_lru():
                self.radix_evictions_total += 1
                got = allocator.allocate(n)
                continue
            victim = self._lowest_progress_active()
            self._preempt(victim)
            if victim == s:
                return None            # s itself lost the pool race
            got = allocator.allocate(n)
        return got

    def _grow_block_tables(self, tokens_by_slot=None):
        """Pre-dispatch block grants: every active slot gets the blocks
        its write window `[pos, pos + tokens)` will cross into (lazy
        growth, incremental allocation), and any window block the slot
        does NOT own exclusively — refcount > 1: still mapped by the
        shared-prefix cache or another slot — is FORKED first
        (copy-on-first-write: fresh block, device copy, the slot's
        reference on the shared source dropped). Admission forks the
        common case eagerly; this pass is the invariant's enforcement
        point — no dispatch may ever write a block another holder
        reads. Under pool pressure the lowest-progress slot is evicted
        (requeue, not deadlock); check_budget guarantees a slot left
        alone in the pool can always finish — prefix-pinned blocks
        excluded — so this terminates with every surviving slot fully
        granted and exclusively owning its window."""
        J = self.steps_per_dispatch
        fork_pairs = []
        for s in range(self.n_slots):
            if not self.active[s] or self.slots[s] is None:
                continue
            slot = self.slots[s]
            if tokens_by_slot is None:
                tokens = min(J, int(self.remaining[s]))
            else:
                tokens = int(tokens_by_slot.get(s, 0))
            if tokens < 1:
                continue
            needed = blocks_needed(int(self.pos[s]) + tokens,
                                   self.block_len)
            have = len(slot.blocks)
            if needed > have:
                got = self._allocate_under_pressure(s, needed - have)
                if got is None or self.slots[s] is None:
                    continue
                slot.blocks.extend(got)
                self.block_tables[s, have:needed] = got
                self.block_grants_total += len(got)
            if slot.window_blocks is not None:
                # the window layers' ring grows with the slot up to its
                # width and is then written round
                have = len(slot.window_blocks)
                ring = self._ring_need(needed)
                if ring > have:
                    got = self._allocate_under_pressure(
                        s, ring - have, self.pool.window_allocator)
                    if got is None or self.slots[s] is None:
                        continue
                    slot.window_blocks.extend(got)
                    self.window_tables[s, have:ring] = got
                    self.block_grants_total += len(got)
            # copy-on-first-write fork of shared write-window blocks
            first_b = int(self.pos[s]) // self.block_len
            last_b = (int(self.pos[s]) + tokens - 1) // self.block_len
            for bi in range(first_b, min(last_b + 1, len(slot.blocks))):
                src = slot.blocks[bi]
                if self.pool.allocator.refcount(src) <= 1:
                    continue
                got = self._allocate_under_pressure(s, 1)
                if got is None or self.slots[s] is None:
                    break              # s lost the pool race mid-fork
                dst = got[0]
                fork_pairs.append((s, src, dst))
                slot.blocks[bi] = dst
                self.block_tables[s, bi] = dst
                self.pool.allocator.free([src])   # drop OUR reference
                self.block_grants_total += 1
        # a slot preempted AFTER recording a fork has already freed its
        # dst block (maybe even re-granted to a later slot this pass) —
        # copying into it now would corrupt the new owner; only live
        # slots' forks dispatch
        fork_pairs = [(src, dst) for s, src, dst in fork_pairs
                      if self.slots[s] is not None]
        if fork_pairs:
            self._run_fork(fork_pairs)

    # ------------------------------------------------------------- decode
    def step(self, *, speculate: Optional[bool] = None,
             proposers: Optional[tuple] = None
             ) -> Tuple[Dict[int, List[int]], List[int]]:
        """One continuous-batching dispatch, launched and read back:
        every active slot advances up to `steps_per_dispatch` tokens —
        or, with `speculative=k` configured (and `speculate` not
        overridden to False by the scheduler's accept-rate policy), up
        to k tokens through ONE k-position score dispatch
        (`_spec_step`). Returns ({slot: [tokens emitted]}, [slots that
        finished and were released]). Under incremental allocation,
        slots whose next writes cross a block boundary are granted
        blocks first — and pool pressure preempts the lowest-progress
        slot into `drain_preempted()` instead of deadlocking.

        This is `step_ahead` with nothing left in flight: the form for
        callers that want a step's tokens from the call that launched
        it (`while eng.active.any(): eng.step()` loses none)."""
        return self._step(False, speculate, proposers)

    def step_ahead(self, *, speculate: Optional[bool] = None,
                   proposers: Optional[tuple] = None
                   ) -> Tuple[Dict[int, List[int]], List[int]]:
        """Launch the next decode step, THEN read the one launched by
        the call before: the device runs step n+1 while the host reads
        step n, does its bookkeeping and hands its tokens on. What the
        next launch needs of the step in flight stays on the device
        (`_carry`); the rest the host knows without its tokens, because
        a request ends by length alone: positions, emit indices, block
        grants, and which slots finish — a slot whose `remaining`
        reaches 0 in the step just launched is released at the launch
        (the device runs programs in order, so a later program that
        writes its freed blocks cannot overtake the step that still
        does), and named in `finished` by the call that returns its
        last token.

        Every token comes back exactly once, in order; the last step's
        by a call made when no slot is active any more (it launches
        nothing), or by `drain()`. Whatever needs the tokens on the host
        or rewrites a slot reads the step in flight first, by itself
        (`_spec_step`, `_preempt`, `evict`, `export_handoff`), and what
        it read is returned by the next call here. A caller that maps
        slots to requests calls `drain()` before it admits: a slot that
        finished in the step in flight is free already, and its last
        tokens would come back under the slot's next request."""
        return self._step(True, speculate, proposers)

    def drain(self) -> Tuple[Dict[int, List[int]], List[int]]:
        """Read back whatever decode step is in flight -> everything
        emitted and finished that no call has returned yet."""
        self._begin_call()
        self._settle()
        return self._take_ready()

    @property
    def in_flight(self) -> bool:
        """Is a decode step launched whose tokens no call has returned
        (or one read back on the quiet, by `_settle`)?"""
        return self._flight is not None or self._ready is not None

    def _begin_call(self):
        self.wait_s = 0.0
        self.kv_read_pct = 100.0     # the K-wide score path gathers
        self.positions_read = 0
        self.window_held_pct = None
        self.moe_stats = None
        self.overlapped = False
        self.launched = False
        self.sample_rows = 0

    def _step(self, ahead: bool, speculate, proposers):
        if speculate is None:
            speculate = self.spec_k is not None
        self._begin_call()
        if speculate and self.spec_k:
            # the proposer reads each slot's history on the host
            self._settle()
            self.launched = True
            self._hold(*self._spec_step(proposers=proposers))
            return self._take_ready()
        flight = self._launch()
        self._settle()               # the step launched before this one
        self._flight = flight
        if not ahead:
            self._settle()
        return self._take_ready()

    def _settle(self):
        """Read the step in flight, if any, and hold what it emitted for
        the next `step` / `step_ahead` / `drain` to return: afterwards
        the host's mirrors, `last_token` and the slots' histories
        included, are the whole truth again."""
        if self._flight is not None:
            self._collect()

    def _hold(self, emitted, finished):
        if self._ready is None:
            self._ready = (emitted, finished)
            return
        held, done = self._ready
        for slot, toks in emitted.items():
            held.setdefault(slot, []).extend(toks)
        done.extend(finished)

    def _take_ready(self):
        out, self._ready = self._ready, None
        return out if out is not None else ({}, [])

    def _device(self, name: str, host):
        """The device copy of the host mirror `name`, uploaded anew
        only if the mirror has changed since the copy was made. The
        copy is of a snapshot, never of the mirror itself: the host
        goes on writing its mirrors while the step that reads the
        upload is in flight."""
        held = self._uploaded.get(name)
        if held is None or not np.array_equal(held[0], host):
            snap = np.array(host)
            held = self._uploaded[name] = (snap, jnp.asarray(snap))
        return held[1]

    def _fresh_rows(self):
        """What the host changed since the last launch, for the decode
        program's merge: [changed, last_token, pos, remaining,
        emit_idx] as one [5, S] upload — or the all-zero constant when
        the carry on the device already says it all, which is every
        step between an admission, a grant of the host's own making and
        a release. A slot is changed where the host wrote its token or
        where a mirror differs from what the last launch left on the
        device. Every writer in this file does both or leaves the slot
        dead (a release: the token no longer matters); should a live
        slot be changed with its token still the device's, the step in
        flight is read first, so that `last_token` is the truth."""
        pos, rem, emit = self._dev_ints
        changed = (self._tok_host | (self.pos != pos)
                   | (self.remaining != rem) | (self.emit_idx != emit))
        if not changed.any():
            return self._no_fresh
        if (changed & ~self._tok_host & (self.remaining > 0)).any():
            self._settle()
        return jnp.asarray(np.stack(
            [changed, self.last_token, self.pos, self.remaining,
             self.emit_idx]).astype(np.int32, copy=False))

    def _decode_args(self):
        """The decode program's arguments after the pool."""
        tables = self._device("block_tables", self.block_tables)
        if self.window_ring:
            tables = (tables, self._device("window_tables",
                                           self.window_tables))
        return (tables, self._carry, self._fresh_rows(),
                self._device("keys", self.keys),
                self._device("temp", self.temp),
                self._device("top_p", self.top_p))

    def _launch(self) -> Optional[dict]:
        """Grant blocks, then dispatch one plain decode step over the
        active slots and advance the host's mirrors by the program's
        own rule; nothing is read back. -> the step's record for
        `_collect` (None: no slot was active)."""
        it = self.loop_it
        with monitor.span("serve/decode/grow", it=it):
            if (self.allocation == "incremental" or self._prefixes
                    or self._radix is not None):
                # upfront allocation never grows, but the CoW fork pass
                # (shared write-window blocks) must still run
                self._grow_block_tables()
        if not self.active.any():
            return None
        with monitor.span("serve/decode/dispatch", it=it):
            # two static program variants: the greedy-only decode skips
            # the sampling chain (sort + threefry) — picked whenever no
            # sampled request is in flight, the common serving case
            sample_rows = int(((self.temp > 0) & self.active).sum())
            if sample_rows:
                if self._decode_full is None:
                    self._decode_full = self._build_decode(
                        greedy_only=False)
                decode = self._decode_full
            else:
                if self._decode_greedy is None:
                    self._decode_greedy = self._build_decode(
                        greedy_only=True)
                decode = self._decode_greedy
            kv_read_pct, positions_read = self._kv_read()
            window_held_pct = self._window_held()
            # the state arrays this dispatch's program is handed go
            # through each micro-step whole, read and written: reckoned
            # from the arrays themselves, so a program that is handed
            # fewer rows moves the number
            state_gb = (2 * self.steps_per_dispatch
                        * self.pool.state_bytes() / 1e9)
            # was the step before this one still unread at the launch?
            overlapped = self._flight is not None
            params, weight_bytes = quant.serving_tree(self.net,
                                                      self.quantize)
            kv, toks, moe, self._carry = decode(
                params, self.net.net_state, self.pool.kv,
                *self._decode_args())
            self.pool.kv = kv
            self.launched = True
            for out in (toks, *moe):
                out.copy_to_host_async()
            # the host's half of the step, from what it knows already:
            # an active slot emits `taken = min(J, remaining)` tokens
            J = self.steps_per_dispatch
            idx = np.flatnonzero(self.active).tolist()
            slots = [self.slots[i] for i in idx]
            taken = np.where(self.active,
                             np.minimum(J, self.remaining), 0
                             ).astype(np.int32)
            self.pos = self.pos + taken
            self.emit_idx = self.emit_idx + taken
            self.remaining = self.remaining - taken
            # ledger: the decode chunk touches J*S token-positions;
            # emitted tokens on live lanes are useful, idle/finished
            # lanes and the tail past each lane's budget are pad_waste
            n_useful = int(taken.sum())
            self.goodput.account(useful=n_useful,
                                 pad_waste=J * self.n_slots - n_useful)
            finished = []
            for i, slot in zip(idx, slots):
                slot.emitted += int(taken[i])
                slot.pos = int(self.pos[i])
                if self.remaining[i] <= 0:
                    finished.append(i)
                    self._release(i)
            self._dev_ints[0] = self.pos
            self._dev_ints[1] = self.remaining
            self._dev_ints[2] = self.emit_idx
            self._tok_host[:] = False
            return dict(toks=toks, moe=moe, idx=idx, slots=slots,
                        taken=taken, finished=finished,
                        kv_read_pct=kv_read_pct,
                        sample_rows=sample_rows,
                        positions_read=positions_read,
                        window_held_pct=window_held_pct,
                        weight_gb=weight_bytes / 1e9, state_gb=state_gb,
                        overlapped=overlapped)

    def _collect(self):
        """Read the step in flight back and do the bookkeeping that
        needs its tokens; what it emitted is held for the caller
        (`_take_ready`)."""
        flight, self._flight = self._flight, None
        it = self.loop_it
        with monitor.span("serve/decode/wait", it=it) as sp:
            toks = np.asarray(flight["toks"])               # [J, S]
            self._take_moe(flight["moe"])
        self.wait_s += sp.duration_s
        with monitor.span("serve/decode/post", it=it):
            self.kv_read_pct = flight["kv_read_pct"]
            self.sample_rows = flight["sample_rows"]
            self.positions_read = flight["positions_read"]
            self.window_held_pct = flight["window_held_pct"]
            self.weight_gb = flight["weight_gb"]
            self.state_gb = flight["state_gb"]
            self.overlapped = flight["overlapped"]
            taken = flight["taken"]
            emitted: Dict[int, List[int]] = {}
            for i, slot in zip(flight["idx"], flight["slots"]):
                out = emitted[i] = toks[:taken[i], i].tolist()
                if self.slots[i] is slot:
                    # not a slot released at the launch (and maybe
                    # admitted to since, with a first token of its own)
                    self.last_token[i] = out[-1]
                if self.spec_k:
                    slot.history.extend(out)
            self._hold(emitted, flight["finished"])

    def _kv_read(self) -> Tuple[float, int]:
        """Of the decode dispatch about to launch: (100 x the pool
        blocks of K (and as many of V) it reads, over the table entries
        its layers have, `steps_per_dispatch x n_slots x` the columns of
        each layer's table, which is what a gather of every slot's whole
        table moves; the positions its attention reads, summed over the
        paged layers). An in-place layer reads `ceil((pos+1)/block_len)`
        blocks for each slot whose `remaining > 0` at that micro-step
        (the program's own validity) and none for the others — a window
        layer those from its window's first position on, counted in
        whole blocks; a gathering layer reads everything."""
        per_slot = self.steps_per_dispatch * self.n_slots
        j = np.arange(self.steps_per_dispatch)[:, None]      # [J, 1]
        live = self.remaining[None, :] > j
        # a slot's position at micro-step j: it advances while live
        at = self.pos[None, :] + j
        blocks = np.where(live, -(-(at + 1) // self.block_len), 0)
        reads = {False: (int(blocks.sum()),
                         int(np.where(live, at + 1, 0).sum()))}
        if self.window_ring:
            first = np.maximum(at + 1 - self.pool.window, 0) \
                // self.block_len
            ring = int(np.where(live, blocks - first, 0).sum())
            reads[True] = (ring, ring * self.block_len)
        read = whole = positions = 0
        for in_place, ring in zip(self._in_place, self.pool.window_layers):
            cols = per_slot * (self.window_ring if ring else self.max_blocks)
            whole += cols
            got = reads[ring] if in_place else (cols, cols * self.block_len)
            read += got[0]
            positions += got[1]
        return 100.0 * read / whole, positions

    def _window_held(self) -> Optional[float]:
        """Of the decode dispatch about to launch, for a net with window
        layers: 100 x the positions those layers hold for the decoding
        slots (a slot's ring, or all it has reached while that is less)
        over the positions the slots have reached."""
        if not self.window_ring:
            return None
        reached = held = 0
        for s in np.flatnonzero(self.active & (self.remaining > 0)):
            n = int(self.pos[s]) + 1
            reached += n
            held += min(n, len(self.slots[s].window_blocks) * self.block_len)
        return 100.0 * held / reached if reached else None

    # ------------------------------------------------- speculative decode
    def _propose(self, s: int, max_draft: int) -> List[int]:
        """Self-drafting proposer: an n-gram suffix cache over the
        slot's own token history (prompt + emitted). The continuation
        that followed the MOST RECENT earlier occurrence of the
        current suffix n-gram is the draft — longest n first
        (`spec_max_ngram`), nothing matched proposes nothing (the slot
        decodes one verified token, exactly vanilla). Free of model
        cost by construction: the 'draft model' is a numpy substring
        search, and the acceptance oracle (the target's own argmax)
        makes any bad draft cost only its rejected lanes.

        Host cost per call is a full-history windowed scan —
        O(len(history) x spec_max_ngram) numpy compares — which the
        page budget bounds at max_total_tokens per slot per dispatch;
        an incremental ngram -> last-occurrence map updated at
        history.extend would make it O(spec_max_ngram) if budgets
        grow past the point where this scan shows up in TPOT."""
        if max_draft <= 0:
            return []
        hist = self.slots[s].history
        L = len(hist)
        if L < 2:
            return []
        h = np.asarray(hist, np.int64)
        for n in range(min(self.spec_max_ngram, L - 1), 0, -1):
            suffix = h[L - n:]
            # candidate occurrences must end before the history's last
            # token so at least one continuation token exists
            win = np.lib.stride_tricks.sliding_window_view(h[:L - 1], n)
            hits = np.flatnonzero((win == suffix).all(axis=1))
            if hits.size:
                # most recent occurrence WITH a full-depth continuation
                # wins; matches hugging the end of history only offer a
                # one-or-two-token draft (on a converged cycle — the
                # common serving tail — that recency bias was measured
                # to cap acceptance near 0.4 where a full-depth draft
                # of the same cycle scores near 1.0)
                full = hits[hits + n + max_draft <= L]
                i = int(full[-1]) if full.size else int(hits[-1])
                cont = h[i + n:i + n + max_draft]
                if cont.size:
                    return [int(t) for t in cont]
        return []

    def _spec_step(self, proposers: Optional[tuple] = None
                   ) -> Tuple[Dict[int, List[int]], List[int]]:
        """One speculative dispatch: the proposer drafts up to k-1
        tokens per greedy slot, ONE k-position score dispatch
        (`_get_score`) runs the target over [last_token, d1..d_{k-1}],
        and the host accepts the longest draft prefix the target's own
        argmax agrees with — the first disagreement truncates and
        emits the TARGET's token, so the emitted stream is the
        target's greedy stream bit-for-bit no matter what the drafts
        were (rejected lanes' K/V writes sit beyond the advanced `pos`
        and are overwritten by the dispatch that reaches them, the
        same write-before-read discipline the garbage block rests on).
        Sampled slots: with `spec_sampled=False` (the default) they
        ride the same dispatch at depth 1 — their token comes from the
        `chosen` sampling tail, untouched by speculation and bit-equal
        to the spec-free engine. With `spec_sampled=True` they take
        drafts too and the acceptance oracle is REJECTION SAMPLING
        (`rejection_sample_drafts`): each emitted token is marginally
        a vanilla sample from the target's filtered distribution — a
        distributional contract, not a bit one. `proposers` (the
        scheduler's per-proposer arbitration) restricts which draft
        backends may run this dispatch; None allows all configured.
        Emits 1..k tokens per slot per dispatch."""
        if not self.active.any():
            return {}, []
        it = self.loop_it
        with monitor.span("serve/decode/propose", it=it):
            K = self.spec_k
            S = self.n_slots
            allow_ngram = proposers is None or "ngram" in proposers
            allow_trunc = (self._draft_plan is not None
                           and (proposers is None or "truncated" in proposers))
            token_mat = np.zeros((S, K), np.int32)
            n_valid = np.zeros(S, np.int32)
            by_proposer: Dict[int, str] = {}
            trunc_slots: List[Tuple[int, int]] = []
            for s in np.flatnonzero(self.active):
                s = int(s)
                token_mat[s, 0] = self.last_token[s]
                if self.temp[s] > 0 and not self.spec_sampled:
                    n_valid[s] = 1          # sampling has no greedy oracle
                    continue
                depth = int(min(K, self.remaining[s]))
                draft = self._propose(s, depth - 1) if allow_ngram else []
                if draft:
                    by_proposer[s] = "ngram"
                    n_valid[s] = 1 + len(draft)
                    token_mat[s, 1:1 + len(draft)] = draft
                elif allow_trunc and depth >= 2:
                    # n-gram came up empty — the truncated-layer drafter
                    # takes the slot (drafts filled in below, after its
                    # write window is granted)
                    trunc_slots.append((s, depth))
                    n_valid[s] = depth
                else:
                    n_valid[s] = 1
            if trunc_slots:
                # grant (and CoW-fork) the drafting slots' FULL windows
                # first: the truncated pass writes draft K/V into the
                # slot's own not-yet-committed positions [pos, pos+d-2],
                # all of which the verify dispatch below rewrites with
                # full-model K/V (write-before-read)
                self._grow_block_tables(dict(trunc_slots))
                trunc_slots = [(s, d) for s, d in trunc_slots
                               if self.slots[s] is not None
                               and self.active[s]]
            if trunc_slots:
                drafts = self._run_draft(trunc_slots)
                for s, d in trunc_slots:
                    by_proposer[s] = "truncated"
                    token_mat[s, 1:d] = drafts[:d - 1, s]
        with monitor.span("serve/decode/grow", it=it):
            # grant (and CoW-fork) each slot's write window [pos,
            # pos+n_valid) — pool pressure preempts exactly like the
            # chunked path
            self._grow_block_tables(
                {int(s): int(n_valid[s]) for s in np.flatnonzero(self.active)})
            n_valid = np.where(self.active, n_valid, 0).astype(np.int32)
        if not self.active.any():
            return {}, []
        with monitor.span("serve/decode/dispatch", it=it):
            greedy_only = not bool((self.temp[self.active] > 0).any())
            use_rs = self.spec_sampled and not greedy_only
            score = self._get_score(K, "rs" if use_rs else greedy_only)
            params, weight_bytes = quant.serving_tree(self.net,
                                                      self.quantize)
            self.weight_gb = weight_bytes / 1e9
            # the score program is given no live mask: every row with a
            # temperature goes through its chain (the rs tail has its own)
            self.sample_rows = (0 if greedy_only or use_rs
                                else int((self.temp > 0).sum()))
            out = score(
                params, self.net.net_state, self.pool.kv,
                self._tables_arg(), jnp.asarray(token_mat),
                jnp.asarray(self.pos), jnp.asarray(n_valid),
                jnp.asarray(self.keys), jnp.asarray(self.emit_idx),
                jnp.asarray(self.temp), jnp.asarray(self.top_p))
        with monitor.span("serve/decode/wait", it=it) as sp:
            if use_rs:
                kv, greedy_mat, n_acc, final = out
                n_acc, final = np.asarray(n_acc), np.asarray(final)
                chosen = None
            else:
                kv, greedy_mat, chosen = out
                chosen = np.asarray(chosen)
            self.pool.kv = kv
            greedy_mat = np.asarray(greedy_mat)
        self.wait_s += sp.duration_s
        with monitor.span("serve/decode/post", it=it):
            self.spec_dispatches_total += 1
            # ledger: the score program touched S*K token-positions; per
            # slot, emitted tokens are useful, valid-but-rejected draft
            # lanes are spec_rejected, positions past n_valid (and whole
            # inactive rows) are pad_waste — tallied in the accept loop
            gp_useful = 0
            gp_rejected = 0
            emitted: Dict[int, List[int]] = {}
            finished = []
            for s in np.flatnonzero(self.active):
                s = int(s)
                v = int(n_valid[s])
                prop = by_proposer.get(s)
                if self.temp[s] > 0:
                    if use_rs:
                        # rejection sampling: the first n_acc drafts
                        # survived their u < q_t(d) tests; `final` is the
                        # residual resample at the divergence (or the
                        # bonus token when every draft survived)
                        acc = min(int(n_acc[s]), v - 1)
                        toks = [int(token_mat[s, j])
                                for j in range(1, 1 + acc)] + [int(final[s])]
                    else:
                        toks = [int(chosen[s])]
                    if v > 1:
                        self.spec_proposed_total += v - 1
                        self.spec_accepted_total += len(toks) - 1
                else:
                    # acceptance: draft j survives iff it EQUALS the
                    # target's argmax after position j-1; the first miss
                    # truncates and the target's token takes its place
                    row = greedy_mat[s]
                    toks = [int(row[0])]
                    for j in range(1, v):
                        if int(token_mat[s, j]) != toks[-1]:
                            break
                        toks.append(int(row[j]))
                    self.spec_proposed_total += v - 1
                    self.spec_accepted_total += len(toks) - 1
                if prop is not None and v > 1:
                    self.spec_proposed_by[prop] += v - 1
                    self.spec_accepted_by[prop] += len(toks) - 1
                n = len(toks)
                gp_useful += n
                gp_rejected += v - n
                self.spec_emitted_total += n
                self.pos[s] += n
                self.emit_idx[s] += n
                self.remaining[s] -= n
                self.last_token[s] = toks[-1]
                self._tok_host[s] = True
                slot = self.slots[s]
                slot.emitted += n
                slot.pos = int(self.pos[s])
                slot.history.extend(toks)
                emitted[s] = toks
                if self.remaining[s] <= 0:
                    finished.append(s)
                    self._release(s)
            self.goodput.account(
                useful=gp_useful, spec_rejected=gp_rejected,
                pad_waste=S * K - gp_useful - gp_rejected)
            return emitted, finished

    # ------------------------------------------------------------ evict
    def evict(self, slot: int):
        """Mid-stream eviction (cancel/timeout): free the slot and its
        blocks immediately; the pool pages become garbage the moment
        the table row is retired (no device work — the next gather by
        a reusing sequence overwrites them via its own prefill)."""
        self._settle()
        if self.slots[slot] is None:
            raise ValueError(f"slot {slot} is not in use")
        self._release(slot)

    def _release(self, slot: int):
        s = self.slots[slot]
        self.pool.allocator.free(s.blocks)
        if s.window_blocks is not None:
            self.pool.window_allocator.free(s.window_blocks)
            self.window_tables[slot] = GARBAGE_BLOCK
        self.slots[slot] = None
        self.active[slot] = False
        self.remaining[slot] = 0
        self.block_tables[slot] = GARBAGE_BLOCK

    # --------------------------------------- disaggregation handoff
    def export_handoff(self, slot: int) -> Tuple[dict, np.ndarray]:
        """Serialize one LIVE slot for a prefill→decode handoff: the
        paged block table is the handoff format — the returned header
        is the slot's full host state, the array its granted K/V
        blocks gathered from the pool and stacked
        ``[n_layers, 2, n_blocks, block_len, heads, head_dim]`` in the
        pool's compute dtype. `wire.encode_handoff` puts both on the
        ND4T wire; a decode engine's `adopt_handoff` rebuilds the slot
        bit-identically (shared/CoW source blocks are gathered by
        VALUE, so the adopting pool always gets private copies).

        The exporting engine is left untouched — the caller releases
        the slot with `evict()` once the handoff is safely delivered
        (at-least-once: a failed send keeps the slot decodable here)."""
        self._check_handoff_wire()
        self._settle()      # the header carries `last_token`
        s = self.slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} is not in use")
        if not self.active[slot]:
            raise ValueError(
                f"slot {slot} already finished — nothing to hand off")
        idx = np.asarray(s.blocks, np.int64)
        per_layer = []
        for i, (k, v) in zip(self.pool.layer_indices, self.pool.kv):
            # the wire keeps heads and head_dim apart; the pool keeps a
            # page's heads side by side
            page = (self.block_len,
                    self.net.layers[i].paged_handoff_heads, -1)
            per_layer.append(np.stack(
                [np.asarray(k)[idx].reshape(len(idx), *page),
                 np.asarray(v)[idx].reshape(len(idx), *page)]))
        kv = np.stack(per_layer)
        header = {
            "request_id": s.request_id,
            "prompt_len": int(s.prompt_len),
            "n_tokens": int(s.n_tokens),
            "pos": int(self.pos[slot]),
            "remaining": int(self.remaining[slot]),
            "emitted": int(s.emitted),
            "emit_base": int(s.emit_base),
            "emit_idx": int(self.emit_idx[slot]),
            "last_token": int(self.last_token[slot]),
            "history": [int(t) for t in s.history],
            "keys": [int(x) for x in self.keys[slot]],
            "temperature": float(self.temp[slot]),
            "top_p": float(self.top_p[slot]),
            "block_len": int(self.block_len),
            "n_layers": len(self.pool.kv),
        }
        return header, kv

    def _check_handoff_wire(self):
        """The handoff wire (`wire.encode_handoff`) is `[n_layers, 2,
        n_blocks, block_len, heads, head_dim]`: (K, V) pages of heads.
        A pool whose layers declare other arrays (a latent pool: one
        array, no head axis) has no place on it."""
        self._refuse_two_pools("the prefill->decode handoff wire")
        self._refuse_state("the prefill->decode handoff wire")
        for i, arrays in zip(self.pool.layer_indices, self.pool.kv):
            layer = self.net.layers[i]
            if (len(arrays) != 2
                    or getattr(layer, "paged_handoff_heads", None) is None):
                raise NotImplementedError(
                    f"prefill->decode handoff carries (K, V) pages of "
                    f"heads; layer {i} ({type(layer).__name__}) keeps "
                    f"{len(arrays)} pool array(s) of width "
                    f"{arrays[0].shape[-1]} with no head axis — the wire "
                    f"format has no such payload yet")

    def adopt_handoff(self, header: dict, kv) -> int:
        """Adopt a handed-off slot: allocate private blocks, scatter
        the K/V payload into the pool, and rebuild the host slot state
        so the next `step()` continues the stream bit-identically to
        the exporting engine having kept it (the PR-9 parity contract
        extended across the wire). Raises ValueError on a pool-shape/
        dtype mismatch, RuntimeError when no slot or blocks are free
        (the caller's backpressure signal — nothing is mutated)."""
        self._check_handoff_wire()
        kv = np.asarray(kv)
        L = len(self.pool.kv)
        k0 = self.pool.kv[0][0]
        if kv.ndim != 6 or kv.shape[0] != L or kv.shape[1] != 2:
            raise ValueError(
                f"handoff K/V shape {kv.shape} does not match this "
                f"pool's {L} layers")
        if int(header["block_len"]) != self.block_len:
            raise ValueError(
                f"handoff block_len {header['block_len']} != engine "
                f"block_len {self.block_len}")
        n_heads = self.net.layers[
            self.pool.layer_indices[0]].paged_handoff_heads
        page = (self.block_len, n_heads, k0.shape[2] // n_heads)
        if tuple(kv.shape[3:]) != page:
            raise ValueError(
                f"handoff block shape {kv.shape[3:]} != pool block "
                f"shape {page}")
        if np.dtype(kv.dtype) != np.dtype(k0.dtype):
            raise ValueError(
                f"handoff dtype {kv.dtype} != pool compute dtype "
                f"{k0.dtype} — a silent cast would break bit-parity")
        slot = next((i for i, s in enumerate(self.slots) if s is None),
                    None)
        if slot is None:
            raise RuntimeError("no free slot to adopt the handoff")
        n_blocks = int(kv.shape[2])
        blocks = self._alloc_admit(n_blocks)
        if blocks is None:
            raise RuntimeError(
                f"pool cannot grant {n_blocks} blocks for the handoff "
                f"({self.pool.free_blocks} free)")
        bidx = jnp.asarray(np.asarray(blocks, np.int32))
        new_kv = []
        flat = kv.reshape(kv.shape[:4] + (-1,))    # heads side by side
        for l, (k, v) in enumerate(self.pool.kv):
            new_kv.append((k.at[bidx].set(jnp.asarray(flat[l, 0])),
                           v.at[bidx].set(jnp.asarray(flat[l, 1]))))
        self.pool.kv = tuple(new_kv)
        s = Slot(header.get("request_id"), blocks,
                 int(header["prompt_len"]), int(header["n_tokens"]),
                 emit_base=int(header.get("emit_base") or 0),
                 history=[int(t) for t in (header.get("history") or [])])
        s.emitted = int(header["emitted"])
        s.pos = int(header["pos"])
        self.slots[slot] = s
        self.block_tables[slot] = GARBAGE_BLOCK
        self.block_tables[slot, :len(blocks)] = blocks
        self.pos[slot] = int(header["pos"])
        self.remaining[slot] = int(header["remaining"])
        self.emit_idx[slot] = int(
            header.get("emit_idx", s.emit_base + s.emitted))
        self.last_token[slot] = int(header["last_token"])
        self._tok_host[slot] = True
        self.keys[slot] = np.asarray(header.get("keys") or [0, 0],
                                     np.uint32)
        self.temp[slot] = float(header.get("temperature") or 0.0)
        tp = header.get("top_p")
        self.top_p[slot] = 1.0 if tp is None else float(tp)
        self.active[slot] = int(header["remaining"]) > 0
        self.block_grants_total += n_blocks
        return slot
