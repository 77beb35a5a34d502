"""Prefix KV-cache reuse: a host radix trie over the engine's block pool.

Production traffic is dominated by shared prefixes — system prompts, few-shot
templates, multi-turn history — and a prompt's keys and values for a prefix
do not depend on what follows it. This module lets admission skip the shared
part (SGLang-style RadixAttention, adapted to this stack's static-shape
discipline):

  - the engine's KV store is already carved into fixed-size **blocks** of
    ``PagedKVConfig.block_tokens`` tokens (`serving/engine.py`); this class
    owns no device state, only block ids of that pool, shared with the
    engine's `models.kv_cache.BlockAllocator`;
  - a host-side **radix trie** maps token-id prefixes to blocks at block
    granularity: one trie node per block, keyed by that block's token tuple.
    Nodes are ref-counted while an admitted request uses them and evicted in
    deterministic LRU order (a monotonic touch counter, never wall clock)
    when admission needs the blocks (`reclaim`) — only unpinned leaves are
    evictable, so a pinned long prefix keeps its whole chain resident;
  - **admission** does a longest-prefix match (`acquire`, which pins); the
    matched blocks are aliased into the slot's block table (zero-copy) and
    only the uncached suffix is prefilled through the bucketed prefill;
  - **retire** hands the finished slot's full prompt blocks to the trie
    (`adopt`, a host-side ownership move of blocks the prefill already
    wrote). Poisoned (`FINISH_ERROR`) slots never donate.

Because prefix blocks always sit at the same absolute positions (a prefix
starts at token 0) the cached KV — position embeddings baked in — is valid
for every request sharing those tokens, and because decode only ever writes
at or past a slot's prompt end, an aliased block is never written again.
Correctness bar: cached-vs-cold output is token-identical
(tests/test_prefix_cache.py proves the matrix, including under eviction
pressure and watchdog re-prefill).

Shape discipline (the GSPMD lesson): matching, pinning, and eviction are
host-side; the only device program is the per-``(suffix_bucket,
batch_bucket)`` cached admission (bounded like plain admission) — block ids
ride as data (out-of-range ids drop), never as shape.
"""

from __future__ import annotations

import dataclasses
from typing import Any


class _TrieNode:
    """One cached block: a radix-trie edge keyed by the block's token tuple."""

    __slots__ = ("key", "parent", "children", "block_id", "ref", "last_used")

    def __init__(self, key: tuple[int, ...], parent: "_TrieNode | None",
                 block_id: int):
        self.key = key
        self.parent = parent
        self.children: dict[tuple[int, ...], _TrieNode] = {}
        self.block_id = block_id
        self.ref = 0
        self.last_used = 0


@dataclasses.dataclass(frozen=True)
class PrefixMatch:
    """A pinned longest-prefix match: ``tokens`` cached tokens held in
    ``block_ids`` pool blocks. Every node in ``nodes`` carries one reference
    until `PrefixCache.release` (the engine releases on slot retirement)."""

    tokens: int
    block_ids: tuple[int, ...] = ()
    nodes: tuple[Any, ...] = ()


NO_MATCH = PrefixMatch(0)


class PrefixCache:
    """Block-granular prefix KV cache for `serving.ServingEngine`.

    ``allocator`` is the engine's `models.kv_cache.BlockAllocator`: the
    engine's block pool IS the cache, so the trie and all policy live on the
    host and this class owns no device state at all — donation is `adopt` (a
    host-side ownership move of blocks the slot already wrote), hits are
    zero-copy block-table aliases, and eviction returns blocks to the shared
    free list via `reclaim`.
    """

    def __init__(self, allocator: Any, max_len: int, block_tokens: int,
                 metrics: Any = None):
        block_tokens = int(block_tokens)
        if block_tokens < 1 or block_tokens & (block_tokens - 1):
            raise ValueError(f"block_tokens must be a power of two, got {block_tokens}")
        if max_len % block_tokens:
            raise ValueError(
                f"block_tokens {block_tokens} must divide n_positions {max_len}"
            )
        self.block_tokens = block_tokens
        self.max_len = int(max_len)
        self.blocks_per_row = self.max_len // block_tokens
        self.metrics = metrics
        self._root = _TrieNode((), None, -1)
        self._tick = 0
        # host-RAM tier hook (`serving/kv_tier.py`): when set, spilled trie
        # nodes (``block_id is None`` — bytes live in the tier's host map)
        # stay hit-able: `acquire` pages them back in instead of recomputing
        # prefill, `adopt` revives them for free
        self.tier = None
        self.allocator = allocator

    # ------------------------------------------------------------------ matching
    def _walk(self, prompt: list[int]) -> list[_TrieNode]:
        """Longest-prefix trie walk over full blocks, capped so at least one
        prompt token is left for the suffix prefill (admission must run the
        final prompt token through the model to sample the first output)."""
        cap = (len(prompt) - 1) // self.block_tokens
        node, path = self._root, []
        while len(path) < cap:
            lo = len(path) * self.block_tokens
            child = node.children.get(tuple(prompt[lo:lo + self.block_tokens]))
            if child is None:
                break
            path.append(child)
            node = child
        return path

    def match_len(self, prompt: list[int]) -> int:
        """Cached-prefix length for ``prompt`` (no pinning — the scheduler's
        suffix-bucketing probe). Multiple of ``block_tokens``, always
        ``< len(prompt)``."""
        return len(self._walk(prompt)) * self.block_tokens

    def acquire(self, prompt: list[int]) -> PrefixMatch:
        """Longest-prefix match that PINS every matched node (ref-count +1
        each) so eviction cannot reclaim blocks an in-flight request is
        copying from / logically depends on. Pair with `release`."""
        path = self._walk(prompt)
        if self.tier is not None:
            # page spilled blocks back to device; a failed page-in (pool
            # exhausted, thrash guard frozen) truncates the match — the
            # caller pins only what is actually device-backed
            path = self.tier.ensure_resident(path)
        for node in path:
            node.ref += 1
            self._touch(node)
        return PrefixMatch(
            tokens=len(path) * self.block_tokens,
            block_ids=tuple(n.block_id for n in path),
            nodes=tuple(path),
        )

    def trim(self, match: PrefixMatch, n_blocks: int) -> PrefixMatch:
        """Shrink a pinned match to its first ``n_blocks`` blocks, releasing
        the pins past the cut (the engine trims when a cached prefix plus the
        suffix bucket would overrun ``n_positions``)."""
        for node in match.nodes[n_blocks:]:
            node.ref -= 1
        return PrefixMatch(
            tokens=n_blocks * self.block_tokens,
            block_ids=match.block_ids[:n_blocks],
            nodes=match.nodes[:n_blocks],
        )

    def release(self, match: PrefixMatch) -> None:
        """Drop the pins taken by `acquire` (slot retirement)."""
        for node in match.nodes:
            node.ref -= 1

    # ------------------------------------------------------------------ donation
    def adopt(self, prompt: list[int], block_ids: list[int],
              owned_from: int) -> int:
        """Donation: transfer ownership of a retired slot's full
        prompt blocks into the trie with ZERO device work — prefill already
        wrote them in place in the shared pool, so the trie simply starts
        pointing at them. ``block_ids[j]`` is the pool block holding prompt
        block ``j`` (the leading row of the slot's block table); blocks
        before ``owned_from`` are the admission-time aliased prefix (already
        trie-owned — just touched to refresh LRU), blocks at/after it are
        slot-private. A private block whose token key is already resident is
        a duplicate raced in by a concurrent retire and goes straight back
        to the shared allocator. Returns how many blocks were newly adopted.
        """
        n_blocks = min(len(prompt) // self.block_tokens, self.blocks_per_row)
        node, new = self._root, 0
        for j in range(n_blocks):
            key = tuple(prompt[j * self.block_tokens:(j + 1) * self.block_tokens])
            child = node.children.get(key)
            if child is None:
                if j < owned_from:
                    # the aliased prefix is pinned until release(); eviction
                    # cannot have removed it mid-flight
                    raise RuntimeError(
                        f"pinned prefix block {j} missing from trie at adopt")
                child = _TrieNode(key, node, int(block_ids[j]))
                node.children[key] = child
                new += 1
            elif j >= owned_from:
                if child.block_id is None and self.tier is not None:
                    # the retiring slot just rewrote this spilled block's
                    # exact bytes on device: adopt the fresh copy and drop
                    # the host buffer — a free page-in
                    self.tier.revive(child, int(block_ids[j]))
                else:
                    self.allocator.free([int(block_ids[j])])
            self._touch(child)
            node = child
        if new and self.metrics is not None:
            self.metrics.prefix_blocks_donated.inc(new)
        return new

    # ------------------------------------------------------------------ eviction
    def reclaim(self, n: int) -> int:
        """Eviction: pop up to ``n`` unpinned LRU leaves and hand
        their blocks back to the shared allocator (admission calls this when
        the free list cannot cover a new request's block reservation).
        Returns how many blocks were actually freed — fewer than ``n`` means
        everything still resident is pinned or interior."""
        freed = 0
        while freed < n:
            block_id = self._evict_one()
            if block_id is None:
                break
            self.allocator.free([block_id])
            freed += 1
        return freed

    def _evict_one(self) -> int | None:
        """Reclaim the least-recently-used evictable block. Only unpinned
        LEAVES qualify: an interior node backs every longer prefix below it,
        and a pinned node is in use by an in-flight request. Deterministic —
        ``last_used`` is a unique monotonic counter, so a replayed trace
        evicts in exactly the same order."""
        victim = None
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.children or node.ref > 0 or node.block_id is None:
                # spilled nodes (block_id None) hold no device block — their
                # host copy is the tier's to drop, not this eviction's
                continue
            if victim is None or node.last_used < victim.last_used:
                victim = node
        if victim is None:
            return None
        del victim.parent.children[victim.key]
        if self.metrics is not None:
            self.metrics.prefix_evictions.inc()
        return victim.block_id

    def _touch(self, node: _TrieNode) -> None:
        self._tick += 1
        node.last_used = self._tick

    # ----------------------------------------------------------------- inspection
    def node_count(self) -> int:
        """Blocks the trie holds, resident or spilled (the shared allocator
        also carries slot-private blocks this class does not see)."""
        count, stack = 0, list(self._root.children.values())
        while stack:
            node = stack.pop()
            count += 1
            stack.extend(node.children.values())
        return count

    def memory_stats(self) -> dict[str, Any]:
        """Host-side occupancy gauges for the telemetry exporter
        (`serving/telemetry.py`, `docs/observability.md`). One trie walk, no
        device work. Resident blocks split three ways:

        - ``blocks_pinned`` — ref-counted by an in-flight request; eviction
          may not touch them;
        - ``blocks_evictable`` — unpinned leaves, exactly what `_evict_one`
          can reclaim right now;
        - ``blocks_stranded`` — unpinned *interior* nodes: resident but
          unreclaimable until their whole subtree drains. ``fragmentation``
          is stranded / resident (0.0 when the trie is empty) — the
          ROADMAP's paged-KV argument wants this number measured, not
          assumed.

        With a host tier attached, spilled nodes (``block_id is None``) are
        counted in the ``host_tier`` sub-dict instead of any device bucket:
        ``blocks_resident`` is device-backed occupancy only, so the device
        conservation ``free + resident + private == total`` keeps holding
        through every spill/page-in transition.
        """
        pinned = evictable = resident = spilled = 0
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.block_id is None:
                spilled += 1
                continue
            resident += 1
            if node.ref > 0:
                pinned += 1
            elif not node.children:
                evictable += 1
        stranded = resident - pinned - evictable
        out: dict[str, Any] = {
            "blocks_resident": resident,
            "blocks_pinned": pinned,
            "blocks_evictable": evictable,
            "blocks_stranded": stranded,
            "fragmentation": stranded / resident if resident else 0.0,
        }
        if self.tier is not None:
            out["host_tier"] = {
                "blocks": spilled,
                "bytes": spilled * self.tier.block_bytes,
            }
        return out
