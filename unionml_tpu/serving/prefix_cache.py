"""Host-side radix tree over token-id blocks for KV prefix caching.

Prompt-heavy serving traffic is dominated by shared prefixes — system prompts,
few-shot templates, chat history — and recomputing their prefill per request
burns the FLOPs that bound throughput. The serving engine keeps computed KV for
prompt prefixes in a device-side block pool; THIS module is the host-side index
over that pool: a radix tree whose edges are fixed-size blocks of token ids,
mapping a prompt's longest cached prefix to the pool block ids holding its KV.

Design (the vLLM/SGLang radix-cache discipline, block-granular):

- **Block granularity.** A node caches exactly ``block_size`` tokens' KV in one
  pool block; matching walks whole blocks, so a prompt sharing 10 tokens of a
  cached prefix at ``block_size=4`` restores 8 (a partial-block hit) and
  prefills the rest.
- **Refcounts.** Every matched/inserted path is acquired until the using slot
  retires; referenced nodes are never evicted, so a block can always be trusted
  while a restore or a multi-turn follow-up depends on it.
- **LRU eviction.** Allocation prefers the free list, then evicts the
  least-recently-used *leaf* with zero references (leaves only: an interior
  evict would orphan descendants whose match path runs through it).

The tree is pure host Python (no jax import): the engine owns the device pool
and performs the gather/scatter copies; this index only decides WHICH blocks
hold WHAT tokens and WHEN a block may be reused.

Paged serving (PR 13) widened this class from *index* to *allocator*: live
decode slots now draw their working blocks from the same pool through
:meth:`alloc_blocks`/:meth:`free_blocks`, and a retiring slot's full blocks are
indexed copy-free by :meth:`adopt` — the tree node takes ownership of the
slot's block instead of allocating a fresh one and device-copying KV into it.
Every pool block is therefore owned by exactly one of: the free list, a tree
node, or a live slot (``slot_blocks`` counts the last), which is what makes
"zero leaked or double-freed blocks" a teardown counter check.
"""

from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["PrefixCache", "block_key", "prefix_digests"]

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_FNV_MASK = 0xFFFFFFFFFFFFFFFF


def block_key(tokens: Sequence[int], block_index: int, block_size: int) -> Tuple[int, ...]:
    """The radix key of block ``block_index`` of ``tokens``: the tuple of that
    block's token ids. This is THE prefix-cache hashing — the tree's node keys
    (:meth:`PrefixCache._key_at`) and the fleet router's affinity digests
    (:func:`prefix_digests`) both derive from it, so the two can never disagree
    about which prompts share a cached block."""
    start = block_index * block_size
    return tuple(int(t) for t in tokens[start : start + block_size])


def prefix_digests(
    tokens: Sequence[int], block_size: int, max_blocks: Optional[int] = None
) -> List[int]:
    """Chained 64-bit FNV-1a digests of ``tokens``' block-aligned prefixes.

    ``digests[i]`` summarizes blocks ``0..i`` (each via :func:`block_key`), and
    each digest folds in its predecessor, so equal digests mean equal whole
    *prefixes* — exactly the property a router needs to guess which replica's
    radix tree holds a prompt's longest cached chain without shipping token
    ids around. Deterministic across processes (unlike ``hash()``), cheap
    (pure host integer math), and block-granular like the tree itself: a
    prompt shorter than one block has no digest and no affinity.
    """
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    total = len(tokens) // block_size
    if max_blocks is not None:
        total = min(total, max_blocks)
    digests: List[int] = []
    acc = _FNV_OFFSET
    for index in range(total):
        for tok in block_key(tokens, index, block_size):
            # mix each token id byte-wise so nearby ids diverge fully
            val = int(tok) & _FNV_MASK
            for _ in range(8):
                acc = ((acc ^ (val & 0xFF)) * _FNV_PRIME) & _FNV_MASK
                val >>= 8
        digests.append(acc)
    return digests


class _Node:
    """One cached block: ``key`` (the block's token ids) under ``parent``."""

    __slots__ = ("key", "block_id", "parent", "children", "refcount", "last_used")

    def __init__(self, key: Tuple[int, ...], block_id: int, parent: Optional["_Node"]) -> None:
        self.key = key
        self.block_id = block_id
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.refcount = 0
        self.last_used = 0


class PrefixCache:
    """Block-granular radix index mapping token-id prefixes to pool block ids.

    :param num_blocks: capacity of the device block pool this index manages.
    :param block_size: tokens cached per block (match/insert granularity).

    Protocol (driven by :class:`~unionml_tpu.serving.continuous.DecodeEngine`):
    :meth:`match` walks the longest cached chain of full blocks for a prompt and
    acquires a reference on every matched node; after the uncovered suffix
    prefills, :meth:`extend` indexes the prompt's remaining full blocks
    (allocating pool blocks, evicting LRU unreferenced leaves as needed) and the
    caller device-copies KV into the NEW blocks it returns. :meth:`release`
    drops the path's references when the slot retires. Counters
    (:meth:`stats`) make the hit rate and eviction churn observable.
    """

    def __init__(self, num_blocks: int, block_size: int, *, telemetry=None) -> None:
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        #: optional Telemetry mirror for hit-rate counters (``is not None`` guarded)
        self.telemetry = telemetry
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._root = _Node((), -1, None)
        # pop() takes from the tail: keep ids ascending for readable tests/logs
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._tick = 0
        self.lookups = 0
        self.hits = 0
        self.hit_tokens = 0
        self.inserted_blocks = 0
        self.evicted_blocks = 0
        self.pinned_blocks = 0
        #: blocks currently owned by live decode slots (paged serving); the
        #: engine acquires them via alloc_blocks and returns them via
        #: free_blocks or adopt — teardown asserts this is back to zero
        self.slot_blocks = 0
        self.adopted_blocks = 0

    @property
    def cached_blocks(self) -> int:
        """Pool blocks currently holding indexed KV (tree-owned: excludes both
        the free list and live slots' working blocks)."""
        return self.num_blocks - len(self._free) - self.slot_blocks

    def _key_at(self, tokens: Sequence[int], block_index: int) -> Tuple[int, ...]:
        return block_key(tokens, block_index, self.block_size)

    def match(self, tokens: Sequence[int], max_blocks: int) -> List[_Node]:
        """Longest cached chain of full blocks covering ``tokens``, up to
        ``max_blocks``. Bumps recency and ACQUIRES a reference on every matched
        node — callers must :meth:`release` the returned path when done."""
        self._tick += 1
        self.lookups += 1
        if self.telemetry is not None:
            self.telemetry.prefix_lookups_total.inc()
        node, path = self._root, []  # type: ignore[var-annotated]
        while len(path) < max_blocks:
            child = node.children.get(self._key_at(tokens, len(path)))
            if child is None:
                break
            child.last_used = self._tick
            child.refcount += 1
            path.append(child)
            node = child
        return path

    def probe(self, tokens: Sequence[int], max_blocks: int) -> int:
        """Length (in blocks) :meth:`match` would return — WITHOUT acquiring
        references or touching recency/counters. Used by admission scheduling
        to compare a live match against what a same-batch sibling will insert."""
        node, depth = self._root, 0
        while depth < max_blocks:
            child = node.children.get(self._key_at(tokens, depth))
            if child is None:
                break
            depth += 1
            node = child
        return depth

    def record_hit(self, matched_tokens: int) -> None:
        """Count one served hit of ``matched_tokens`` restored-prefix tokens
        (called by the engine with the FINAL matched length, after any
        capacity-driven shrink, so counters reflect KV actually reused)."""
        if matched_tokens > 0:
            self.hits += 1
            self.hit_tokens += int(matched_tokens)
            if self.telemetry is not None:
                self.telemetry.prefix_hits_total.inc()
                self.telemetry.prefix_hit_tokens_total.inc(float(matched_tokens))

    def extend(
        self, path: List[_Node], tokens: Sequence[int], max_blocks: int
    ) -> Tuple[List[_Node], List[_Node]]:
        """Index ``tokens``' full blocks beyond ``path``, up to ``max_blocks``.

        Existing nodes (a sibling indexed them first) are acquired in place; a
        missing node allocates a pool block — evicting the LRU unreferenced
        leaf when the free list is empty — and is returned in ``new`` for the
        caller to device-copy KV into. Stops early (keeping the indexed chain a
        true prefix) when every pool block is referenced. Returns
        ``(full_path, new_nodes)``; ``new_nodes`` is always the tail of
        ``full_path``, and every node of ``full_path`` holds a reference the
        caller must eventually :meth:`release`.
        """
        self._tick += 1
        node = path[-1] if path else self._root
        full, new = list(path), []  # type: ignore[var-annotated]
        while len(full) < max_blocks:
            key = self._key_at(tokens, len(full))
            child = node.children.get(key)
            if child is None:
                block_id = self._alloc()
                if block_id is None:  # every block referenced: cannot evict
                    break
                child = _Node(key, block_id, node)
                node.children[key] = child
                new.append(child)
                self.inserted_blocks += 1
            child.last_used = self._tick
            child.refcount += 1
            full.append(child)
            node = child
        return full, new

    def alloc_blocks(self, n: int) -> Optional[List[int]]:
        """Acquire ``n`` pool blocks for a live slot's working set (paged
        admission), evicting LRU unreferenced leaves as needed. All-or-nothing:
        returns ``None`` — with nothing allocated — if fewer than ``n`` blocks
        can be freed, so a failed admission never strands a partial grant.
        The caller owns the returned ids until :meth:`free_blocks` or
        :meth:`adopt` hands each one back."""
        ids: List[int] = []
        for _ in range(n):
            block_id = self._alloc()
            if block_id is None:
                self._free.extend(reversed(ids))  # rollback, preserving order
                return None
            ids.append(block_id)
        self.slot_blocks += n
        return ids

    def free_blocks(self, ids: Sequence[int]) -> None:
        """Return slot-owned blocks (from :meth:`alloc_blocks`) to the free
        list — the paged engine calls this when a slot retires with blocks the
        radix index did not :meth:`adopt` (partial tail, unused budget)."""
        self._free.extend(int(b) for b in ids)
        self.slot_blocks -= len(ids)
        assert self.slot_blocks >= 0, "freed more slot blocks than were allocated"

    def available_blocks(self) -> int:
        """Blocks an :meth:`alloc_blocks` call could acquire right now: the
        free list plus every evictable (transitively unreferenced) tree chain.
        Admission gates block demand on this without mutating the tree."""
        def reclaim(node: _Node) -> Tuple[int, bool]:
            # (reclaimable blocks in the subtree, whole subtree evictable?):
            # leaves-only eviction frees a node iff all its descendants go
            # first, but a referenced parent doesn't shield evictable leaf
            # chains below it. Depth is bounded by max_len/block_size.
            count, fully = 0, True
            for child in node.children.values():
                sub, sub_fully = reclaim(child)
                count += sub
                fully = fully and sub_fully
            if fully and node.refcount <= 0:
                return count + 1, True
            return count, False

        total = 0
        for child in self._root.children.values():
            total += reclaim(child)[0]
        return len(self._free) + total

    def adopt(
        self,
        path: List[_Node],
        tokens: Sequence[int],
        max_blocks: int,
        block_map: Dict[int, int],
    ) -> Tuple[List[_Node], int]:
        """Copy-free :meth:`extend`: index ``tokens``' full blocks beyond
        ``path`` by transferring ownership of the caller's own pool blocks.

        ``block_map`` maps block index -> the slot-owned block id already
        holding that block's KV (the slot's table wrote it there during
        decode). A missing tree node ADOPTS the mapped block — the id is popped
        from ``block_map`` and ownership moves slot -> tree, no device copy.
        Where a sibling indexed the same block first, the existing node is
        acquired and the slot keeps (and later frees) its duplicate. Returns
        ``(full_path, adopted)``; every node of ``full_path`` holds a reference
        the caller must eventually :meth:`release`.
        """
        self._tick += 1
        node = path[-1] if path else self._root
        full = list(path)
        adopted = 0
        while len(full) < max_blocks:
            key = self._key_at(tokens, len(full))
            child = node.children.get(key)
            if child is None:
                block_id = block_map.pop(len(full), None)
                if block_id is None:  # caller has no block for this index
                    break
                child = _Node(key, block_id, node)
                node.children[key] = child
                adopted += 1
                self.inserted_blocks += 1
                self.adopted_blocks += 1
                self.slot_blocks -= 1  # ownership: slot -> tree
            child.last_used = self._tick
            child.refcount += 1
            full.append(child)
            node = child
        return full, adopted

    def release(self, path: Sequence[_Node]) -> None:
        """Drop one reference from every node of ``path`` (slot retirement)."""
        for node in path:
            node.refcount -= 1

    def pin(self, path: Sequence[_Node]) -> None:
        """Acquire an eviction-proof reference on every node of ``path``.

        A PREEMPTED request's checkpoint lives only in these blocks: evicting
        one before the resume re-admits would silently turn the resume into a
        full re-prefill (or corrupt a partially-matched chain), so the pin
        holds a reference across the whole queued gap — the engine's slot
        references come and go with slots, this one belongs to the scheduler's
        ticket. ``pinned_blocks`` (see :meth:`stats`) makes leak detection a
        counter read: it must return to zero once every preempted request has
        resumed or been cancelled.
        """
        for node in path:
            node.refcount += 1
        self.pinned_blocks += len(path)

    def unpin(self, path: Sequence[_Node]) -> None:
        """Drop a :meth:`pin`'s references (resume re-admitted, or the
        preempted request was cancelled while re-queued)."""
        for node in path:
            node.refcount -= 1
        # clear() may have reset the counter while paths were still pinned
        # (engine reset drops the whole tree); never let it go negative
        self.pinned_blocks = max(0, self.pinned_blocks - len(path))

    def clear(self) -> None:
        """Forget every cached block (engine reset: the pool is reallocated).
        Slot-owned blocks are reclaimed too — the paged engine only calls this
        when every slot's device state is being rebuilt with it."""
        self._root = _Node((), -1, None)
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self.pinned_blocks = 0
        self.slot_blocks = 0

    def _alloc(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        victim = self._lru_leaf()
        if victim is None:
            return None
        self._evict(victim)
        return self._free.pop()

    def _lru_leaf(self) -> Optional[_Node]:
        best: Optional[_Node] = None
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            elif node.refcount <= 0 and (best is None or node.last_used < best.last_used):
                best = node
        return best

    def _evict(self, node: _Node) -> None:
        assert node.parent is not None and not node.children
        del node.parent.children[node.key]
        self._free.append(node.block_id)
        self.evicted_blocks += 1

    def stats(self) -> Dict[str, int]:
        """Counters for /stats."""
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "cached_blocks": self.cached_blocks,
            "lookups": self.lookups,
            "hits": self.hits,
            "hit_tokens": self.hit_tokens,
            "inserted_blocks": self.inserted_blocks,
            "evicted_blocks": self.evicted_blocks,
            "pinned_blocks": self.pinned_blocks,
            # paged-pool occupancy: live working blocks, free headroom, and
            # copy-free index adoptions (all zero on a dense-mode engine)
            "slot_blocks": self.slot_blocks,
            "free_blocks": len(self._free),
            "adopted_blocks": self.adopted_blocks,
        }
