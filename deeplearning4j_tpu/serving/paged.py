"""Paged KV-cache pool: fixed-size blocks + host-side accounting.

The serving tier's memory plane (ROADMAP "production inference tier";
the design TF-Serving layered over the TF runtime, PAPERS.md §serving):
instead of one monolithic `[cache_len, H, Dh]` buffer pinned per
sequence for its whole lifetime, K/V live in a shared pool of
fixed-size blocks `[n_blocks, block_len, H*Dh]` per transformer
layer (a page keeps all heads side by side: one contiguous,
lane-dense slab the decode kernel reads with one DMA). A sequence
owns `ceil((prompt + n_tokens) / block_len)` blocks, addressed
through a per-slot block table — so `stream_budget` becomes
a POOL-capacity question (how many sequences fit at once) instead of a
per-sequence clamp, and a finished sequence's blocks immediately serve
the next admission.

Split of responsibilities:

- device: the block pools (whatever arrays each paged layer declares:
  a (K, V) pair per transformer block, one latent array per latent
  attention block; all dtype = the net's compute dtype), the per-slot
  states of the layers that declare one, and the layers' own cached
  steps (`paged_step`, `state_step`: docs/SERVING.md, the paged
  protocol);
- host: free/used accounting (`BlockAllocator`) and the block tables,
  which ride h2d once per scheduler step.

Block id 0 is RESERVED as the garbage block: inactive slots and block-
table padding point at it, so masked scatter lanes always have a legal
target and freed blocks can be retired from a table without reshaping
anything. The allocator never hands it out.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

GARBAGE_BLOCK = 0


def blocks_needed(total_tokens: int, block_len: int) -> int:
    """Blocks a sequence of `total_tokens` (prompt + generated) owns."""
    return -(-int(total_tokens) // int(block_len))


def plan_table(block_tables, entry):
    """The table a block entry of the engine's plan reads: entries are
    ("block", layer, pool), or ("block", layer, pool, kind) in a net
    with two kinds of pool, whose programs take the (full, window) pair
    of tables and read the one `kind` names."""
    return block_tables[entry[3]] if len(entry) > 3 else block_tables


class BlockAllocator:
    """Host-side free-list over pool block ids 1..n_blocks-1 (id 0 is
    the reserved garbage block). Allocation is all-or-nothing: a
    request either gets its full block set or stays queued — partial
    grants would deadlock two half-admitted sequences against each
    other. LIFO reuse keeps freshly-freed blocks hot.

    Grants are REFCOUNTED (the copy-on-write shared-prefix plane,
    docs/SERVING.md): `allocate` hands out blocks at refcount 1;
    `share` takes an additional reference on already-granted blocks
    (multiple slots — and the server's prefix cache — mapping the same
    physical prefix block); `free` drops one reference and only
    returns the block to the free list when the last holder lets go.
    The double-free guard generalizes: dropping a reference a block
    does not carry is the same bug class as the PR-9 free-list
    double-append, and raises the same way."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError(
                f"need at least 2 pool blocks (1 usable + the reserved "
                f"garbage block); got {n_blocks}")
        self.n_blocks = int(n_blocks)
        # pop() order: 1, 2, 3, ... for a fresh pool
        self._free: List[int] = list(range(self.n_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}      # granted block -> refcount

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return (self.n_blocks - 1) - len(self._free)

    @property
    def shared_blocks(self) -> int:
        """Physical blocks currently mapped by more than one holder
        (refcount > 1) — the `serving_prefix_blocks_shared` gauge."""
        return sum(1 for r in self._refs.values() if r > 1)

    def refcount(self, block: int) -> int:
        return self._refs.get(int(block), 0)

    def allocate(self, n: int) -> Optional[List[int]]:
        """`n` block ids (each at refcount 1), or None if the pool
        can't cover the request right now (caller keeps it queued)."""
        if n <= 0:
            raise ValueError(f"allocate(n={n})")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def share(self, blocks: List[int]):
        """Take one more reference on each of `blocks` — they must be
        granted already (a share of a free block would alias whatever
        sequence the free list hands it to next)."""
        for b in blocks:
            b = int(b)
            if self._refs.get(b, 0) < 1:
                raise ValueError(
                    f"share of block {b} which is not granted (free or "
                    f"out of range) — a stale grant reference")
        for b in blocks:
            self._refs[int(b)] += 1

    def free(self, blocks: List[int]):
        # validate the WHOLE batch before mutating anything: a double-
        # free halfway through a list must not leave the allocator in a
        # half-freed state (the PR-9 guard, extended to refcounts —
        # a list naming one block more times than it holds references
        # is the same bug)
        need: Dict[int, int] = {}
        for b in blocks:
            b = int(b)
            if not (0 < b < self.n_blocks):
                raise ValueError(f"freeing invalid block id {b}")
            need[b] = need.get(b, 0) + 1
        for b, n in need.items():
            if self._refs.get(b, 0) < n:
                raise ValueError(f"double-free of block {b}")
        for b in blocks:
            b = int(b)
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)


class _RadixNode:
    """One edge of the radix tree: a run of BLOCK-ALIGNED token chunks
    and the pool blocks holding their K/V. `tokens` is always a
    multiple of `block_len` long and `blocks[j]` holds tokens
    `tokens[j*bl:(j+1)*bl]`; children are keyed by the first block's
    token tuple (unique among siblings — any two edges sharing a full
    first block get factored by a split, and edges differing within
    the first block differ in the key)."""

    __slots__ = ("tokens", "blocks", "children", "parent", "last_used",
                 "pinned")

    def __init__(self, tokens, blocks, parent):
        self.tokens = tokens
        self.blocks = blocks
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.parent = parent
        self.last_used = 0
        self.pinned = False


class RadixPrefixCache:
    """Radix tree over block-aligned token chunks — automatic
    mid-prompt K/V dedup across ALL admissions (the `prefix_cache=
    "radix"` engine mode, docs/SERVING.md), replacing the manual
    exact-match-from-token-0 `register_prefix` contract.

    Every admission's prompt is `match()`ed against the tree (longest
    block-aligned shared prefix → those blocks are `share()`d to the
    new slot, copy-on-write discipline unchanged) and `insert()`ed on
    the way in (the slot's fully-written prompt blocks become tree
    edges, with the cache holding its OWN allocator reference on each
    — a finished slot's release leaves the prefix resident). Matching
    and splitting happen only at block boundaries, so a radix hit
    never needs a mid-block fork or cached next-token probs: the
    engine caps the match below the full prompt and runs its ordinary
    suffix-extension prefill for the remainder.

    Eviction is LRU over UNPINNED LEAVES (`evict_lru()`): the engine
    calls it under pool pressure BEFORE preempting live slots, and the
    cache drops its reference — a block still mapped by an active slot
    survives at the slot's refcount (the same last-holder-frees rule
    every other release rides). Nothing here is pinned capacity:
    `check_budget` ignores radix-held blocks because they are
    reclaimable on demand."""

    def __init__(self, allocator: BlockAllocator, block_len: int):
        self.alloc = allocator
        self.block_len = int(block_len)
        self.root = _RadixNode((), [], None)
        self._n_nodes = 0
        self._clock = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    @property
    def nodes(self) -> int:
        """Edge count (root excluded) — the `serving_radix_nodes`
        gauge."""
        return self._n_nodes

    def _iter_nodes(self):
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            yield n

    @property
    def held_blocks(self) -> int:
        return sum(len(n.blocks) for n in self._iter_nodes())

    @property
    def evictable_blocks(self) -> int:
        """Blocks that would return to the free list if the whole
        unpinned tree were evicted (cache is the only holder)."""
        return sum(1 for n in self._iter_nodes() if not n.pinned
                   for b in n.blocks if self.alloc.refcount(b) == 1)

    def match(self, tokens) -> Tuple[int, List[int]]:
        """Longest block-aligned cached prefix of `tokens`: returns
        `(n_matched_tokens, blocks)` — the caller `share()`s the
        blocks onto the admitted slot. Touches the path for LRU."""
        t = tuple(int(x) for x in tokens)
        bl = self.block_len
        now = self._tick()
        node, i, out = self.root, 0, []
        while len(t) - i >= bl:
            child = node.children.get(t[i:i + bl])
            if child is None:
                break
            m = 0
            while (m < len(child.blocks) and i + (m + 1) * bl <= len(t)
                   and child.tokens[m * bl:(m + 1) * bl]
                   == t[i + m * bl:i + (m + 1) * bl]):
                m += 1
            child.last_used = now
            out.extend(child.blocks[:m])
            i += m * bl
            if m < len(child.blocks):
                break
            node = child
        return i, out

    def insert(self, tokens, blocks) -> int:
        """Insert the fully-written prompt blocks of a just-admitted
        slot. `tokens[:len(blocks)*block_len]` must be the tokens those
        blocks hold. Shared portions already in the tree are skipped
        (the tree keeps ITS blocks); the diverging suffix becomes a new
        edge the cache takes its own references on. Returns the number
        of newly referenced blocks."""
        bl = self.block_len
        t = tuple(int(x) for x in tokens)
        nb = min(len(t) // bl, len(blocks))
        t = t[:nb * bl]
        now = self._tick()
        node, i, bi = self.root, 0, 0
        while bi < nb:
            key = t[i:i + bl]
            child = node.children.get(key)
            if child is None:
                new_blocks = [int(b) for b in blocks[bi:nb]]
                self.alloc.share(new_blocks)
                leaf = _RadixNode(t[i:], new_blocks, node)
                leaf.last_used = now
                node.children[key] = leaf
                self._n_nodes += 1
                return len(new_blocks)
            m = 0
            while (m < len(child.blocks) and bi + m < nb
                   and child.tokens[m * bl:(m + 1) * bl]
                   == t[i + m * bl:i + (m + 1) * bl]):
                m += 1
            child.last_used = now
            if m == len(child.blocks):
                node, i, bi = child, i + m * bl, bi + m
                continue
            if bi + m == nb:
                return 0          # prompt ends inside the edge: cached
            node = self._split(child, m)
            i, bi = i + m * bl, bi + m
        return 0

    def _split(self, child: "_RadixNode", m: int) -> "_RadixNode":
        """Split `child` at block boundary `m` (0 < m < blocks): the
        upper part becomes a new interior node, `child` keeps the
        tail."""
        bl = self.block_len
        parent = child.parent
        upper = _RadixNode(child.tokens[:m * bl], child.blocks[:m], parent)
        upper.last_used = child.last_used
        upper.pinned = child.pinned
        parent.children[child.tokens[:bl]] = upper
        child.tokens = child.tokens[m * bl:]
        child.blocks = child.blocks[m:]
        child.parent = upper
        upper.children[child.tokens[:bl]] = child
        self._n_nodes += 1
        return upper

    def evict_lru(self) -> int:
        """Drop the cache's references on the least-recently-used
        unpinned LEAF. Returns how many block references were released
        (0 = nothing evictable). Blocks still mapped by a live slot
        stay granted at the slot's refcount."""
        best = None
        for n in self._iter_nodes():
            if n.children or n.pinned:
                continue
            if best is None or n.last_used < best.last_used:
                best = n
        if best is None:
            return 0
        del best.parent.children[best.tokens[:self.block_len]]
        self.alloc.free(best.blocks)
        self._n_nodes -= 1
        return len(best.blocks)

    def clear(self) -> int:
        """Release every cache-held reference (drain/evict-all). The
        tree rebuilds from traffic — fleet swap successors start here."""
        dropped = 0
        for n in list(self._iter_nodes()):
            self.alloc.free(n.blocks)
            dropped += len(n.blocks)
        self.root.children.clear()
        self._n_nodes = 0
        return dropped


class PagedKVPool:
    """The per-layer block pools for one model + their allocators.

    The arrays are whatever each paged layer DECLARES
    (`layer.paged_pool_arrays(n_blocks, block_len, dtype)`, the paged
    protocol of docs/SERVING.md): `kv` is a flat tuple with one entry a
    paged layer, in layer order, each a tuple of
    `[n_blocks, block_len, width]` arrays in the net's compute dtype —
    (K, V) of `n_heads * head_dim` for a `TransformerEncoderBlock`, one
    latent array for a `LatentAttentionBlock`. It is a plain pytree:
    jitted programs take it as an argument and return the updated
    pools.

    Two kinds of cache in one manager: a layer that declares
    `paged_window` (the positions back from a query it ever reads)
    keeps a slot's pages in a RING of `ceil(window / block_len) + 1`
    blocks, logical block b at table column `b % ring`, whatever the
    slot's length; every other layer keeps a block for every
    `block_len` positions. The window layers' arrays hold
    `window_blocks` blocks and are granted by `window_allocator`, the
    others `n_blocks` by `allocator`: one block id names the same page
    in every layer of its kind. `window` is None, and there is one
    allocator, for a net with no window layer.

    A THIRD kind: a layer that declares `slot_state` (a recurrent or
    state-space layer, `layer.slot_state_arrays(n_slots, dtype)`) keeps
    a state of FIXED size a serving slot, whatever the slot's length:
    arrays with a row a slot, which has no block table and needs no
    allocator (a slot's row is its allocation; an admission overwrites
    it whole).  Their entries follow the paged layers' in `kv`, in layer
    order (`kv[n_paged:]`, layers `state_indices`), so that every
    program that is handed the pools carries, donates and returns the
    states with them."""

    def __init__(self, net, n_blocks: int, block_len: int,
                 window_blocks: Optional[int] = None, n_slots: int = 0):
        if block_len < 1:
            raise ValueError(f"block_len must be >= 1; got {block_len}")
        self.block_len = int(block_len)
        self.n_blocks = int(n_blocks)
        self.layer_indices = [i for i, l in enumerate(net.layers)
                              if getattr(l, "paged_cache", False)]
        if not self.layer_indices:
            raise ValueError(
                "PagedKVPool needs at least one layer that implements the "
                "paged protocol (TransformerEncoderBlock, "
                "LatentAttentionBlock, an attention layer of "
                "HybridStateSpaceBlock: a layer that keeps a per-slot "
                "state alone gives the manager no page to keep a slot's "
                "length by); got "
                f"{[type(l).__name__ for l in net.layers]}")
        windows = [getattr(net.layers[i], "paged_window", None)
                   for i in self.layer_indices]
        # per paged layer: does it keep a ring (True) or every block?
        self.window_layers: Tuple[bool, ...] = tuple(
            w is not None for w in windows)
        distinct = sorted({int(w) for w in windows if w is not None})
        if len(distinct) > 1:
            raise ValueError(
                f"paged layers declare windows {distinct}: one pool of "
                f"rings serves one window length")
        if distinct and all(self.window_layers):
            raise ValueError(
                "every paged layer declares a window: the pool manager "
                "keeps the slots' lengths by the layers that hold every "
                "position, and this net has none")
        self.window: Optional[int] = distinct[0] if distinct else None
        if self.window is None and window_blocks is not None:
            raise ValueError(
                "window_blocks given for a net with no window layer")
        self.window_blocks = (None if self.window is None else
                              int(window_blocks or n_blocks))
        dtype = net.dtype.compute_dtype
        self.kv: Tuple = tuple(
            tuple(net.layers[i].paged_pool_arrays(
                self.window_blocks if ring else self.n_blocks,
                self.block_len, dtype))
            for i, ring in zip(self.layer_indices, self.window_layers))
        self.n_paged = len(self.kv)
        self.state_indices = [i for i, l in enumerate(net.layers)
                              if getattr(l, "slot_state", False)]
        if self.state_indices and n_slots < 1:
            raise ValueError(
                "a net whose layers keep a per-slot state needs n_slots, "
                "the rows of each state array")
        self.kv += tuple(
            tuple(net.layers[i].slot_state_arrays(int(n_slots), dtype))
            for i in self.state_indices)
        # which axis of each state array is the slot's
        self.state_axes: Tuple = tuple(
            tuple(net.layers[i].slot_state_axes) for i in self.state_indices)
        self.allocator = BlockAllocator(self.n_blocks)
        self.window_allocator = (None if self.window is None else
                                 BlockAllocator(self.window_blocks))

    def state_bytes(self) -> int:
        """Bytes of the per-slot state arrays as they stand in `kv`: what
        a program that is handed the pools takes, and returns, of them
        (0 for a net with no state layer)."""
        return sum(a.nbytes for arrays in self.kv[self.n_paged:]
                   for a in arrays)

    def ring_blocks(self, max_blocks: int) -> int:
        """Columns of a window layer's block table: the blocks the
        window can touch at once, and never more than the budget's."""
        return min(int(max_blocks),
                   blocks_needed(self.window, self.block_len) + 1)

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    @property
    def used_blocks(self) -> int:
        return self.allocator.used_blocks

    def device_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize
                   for arrays in self.kv for a in arrays)
