"""Host-RAM KV block tier + request hibernation behind the paged pool.

Every serving gain since the paged pool is still bounded by one chip's HBM:
when ``blocks_free`` hits zero the engine backpressures admission. The paged
pool already made KV blocks an *ownership* abstraction (block tables,
ref-counted trie pins, journal-backed frontier cursors) — exactly the handle
a tiered store needs: the logical layout (tables, tries, positions) stays
fixed while the physical bytes move between device HBM and pinned host
buffers underneath (ROADMAP item 5, `docs/serving.md` "KV tiering &
hibernation").

Two spill granularities, coldest first:

  - **trie block spill** — evictable (unpinned) prefix-cache blocks are
    paged out to host via ``jax.device_get`` instead of discarded: the trie
    node stays in place with ``block_id = None``, so a later prompt match
    still HITS and pages the bytes back in (one jitted scatter through the
    engine's ``tier_wake`` program) instead of recomputing prefill;
  - **request hibernation** — a whole admitted stream releases ALL its
    device blocks (the slot teardown is `_release_slot` itself, so the
    table-row neutralization that makes stale in-flight writes drop is the
    battle-tested one) and parks as a host-side record. Wake-up chooses
    per-request between re-prefill from ``resume_tokens`` (the journal-proven
    bit-exact path) and host-block upload — whichever is cheaper under the
    measured transfer rate (`choose_wake`) — and re-enters through the
    scheduler's resumed-request front lane.

Durability: host buffers are volatile. The journal — progress-flushed at
hibernate time — is the durable tier, so a SIGKILL mid-spill loses nothing:
`ServingEngine.resume` replays hibernated streams exactly like crashed ones
(`tools/chaos_serve.py` ``hibernate_kill``).

A page-in/page-out **thrash guard** (sliding event window with enter/exit
hysteresis, injectable clock) freezes further spill when the tier starts
churning — the engine then behaves exactly like tier-off (discard eviction +
requeue backpressure), and the freeze raises an `EV_ANOMALY` trace event and
a ``host_tier/thrash_events`` counter.

Parity bar: tier-on greedy token streams are bit-for-bit equal to tier-off
and solo `generate`, across forced spill→page-in cycles mid-decode and
hibernate→wake cycles in both wake modes (tests/test_kv_tier.py).
"""

from __future__ import annotations

import dataclasses
import time
import zlib
from collections import OrderedDict, deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..models.kv_cache import _is_index_leaf
from .scheduler import FIFOScheduler
from .trace import EV_ANOMALY


@dataclasses.dataclass(frozen=True)
class KVTierConfig:
    """Knobs for the engine's ``kv_tier=`` argument (`docs/serving.md`
    "KV tiering & hibernation"). Default-constructed the tier is
    demand-driven: it spills only when a block reservation falls short
    (spill-then-admit), never in the background.

    - ``low_water_blocks`` — background spill trigger: when ``blocks_free``
      drops below it, the per-step poll pages evictable trie blocks out
      until free recovers (0 disables background spill);
    - ``hibernate_idle_s`` — an admitted stream with no token progress for
      this long is hibernated by the poll (inf disables idle hibernation;
      pressure hibernation is governed by ``spill_on_pressure`` instead);
    - ``spill_on_pressure`` — allow the reservation shortfall path to
      hibernate cold slots (long-idle first, then oldest arrival) after
      trie spill alone falls short;
    - ``min_resident_slots`` — pressure hibernation never drops the active
      slot count below this floor (starvation guard);
    - ``wake_policy`` — ``"auto"`` runs `choose_wake` per request;
      ``"upload"`` / ``"prefill"`` force one path (the parity tests pin
      both);
    - ``wake_cooldown_s`` — a just-woken request is exempt from pressure
      hibernation for this long (anti-ping-pong);
    - ``headroom_discount`` — fraction at which `capacity_headroom` counts
      host-backed blocks as token capacity (paging in is slower than
      device-resident decode, so host capacity is not full-price);
    - ``prefill_speedup`` — prefill processes a whole prompt per forward,
      so the wake cost model prices replay at ``decode_rate * speedup``
      tokens/s;
    - ``max_host_blocks`` — cap on host-resident TRIE blocks (LRU spilled
      subtrees are dropped past it; hibernated records are never dropped —
      their durable tier is the journal). None = unbounded;
    - ``thrash_*`` — the guard: freeze when ``thrash_enter_events`` page
      events land within ``thrash_window_s``; unfreeze only after the
      window stays at or below ``thrash_exit_fraction * enter`` for
      ``thrash_exit_s`` (hysteresis, so the guard cannot itself flap).
    """

    low_water_blocks: int = 0
    hibernate_idle_s: float = float("inf")
    spill_on_pressure: bool = True
    min_resident_slots: int = 1
    wake_policy: str = "auto"
    wake_cooldown_s: float = 0.0
    headroom_discount: float = 0.5
    prefill_speedup: float = 8.0
    max_host_blocks: int | None = None
    thrash_window_s: float = 5.0
    thrash_enter_events: int = 64
    thrash_exit_fraction: float = 0.25
    thrash_exit_s: float = 5.0

    def __post_init__(self):
        if self.wake_policy not in ("auto", "upload", "prefill"):
            raise ValueError(
                f"wake_policy must be 'auto', 'upload' or 'prefill', "
                f"got {self.wake_policy!r}")
        if self.min_resident_slots < 0:
            raise ValueError(
                f"min_resident_slots must be >= 0, got {self.min_resident_slots}")
        if self.thrash_enter_events < 1:
            raise ValueError(
                f"thrash_enter_events must be >= 1, got {self.thrash_enter_events}")


def choose_wake(host_bytes: int, replay_tokens: int,
                page_in_bytes_per_s: float,
                prefill_tokens_per_s: float) -> str:
    """Per-request wake decision: ``"upload"`` when restoring the host bytes
    is measurably cheaper than replaying the stream through a continuation
    prefill, else ``"prefill"`` (the journal-proven default — also the
    answer whenever either rate is unmeasured: never bet an unproven path
    on a guess). Pure so the cost-model tests drive it directly."""
    if host_bytes <= 0 or page_in_bytes_per_s <= 0 or prefill_tokens_per_s <= 0:
        return "prefill"
    upload_s = host_bytes / page_in_bytes_per_s
    replay_s = replay_tokens / prefill_tokens_per_s
    return "upload" if upload_s < replay_s else "prefill"


@dataclasses.dataclass
class HostBlocks:
    """Pinned host copies of ``k`` pool blocks: ``tree`` is a pytree
    congruent with the engine's paged cache whose KV leaves are numpy
    arrays ``[k, block_tokens, ...]`` (cache-index leaves are zero
    placeholders), ``crcs`` one content hash per block (crc32 chained over
    the block's leaf bytes in tree-leaf order), ``nbytes`` the exact host
    footprint. Page-in re-hashes and refuses to restore corrupt bytes."""

    tree: Any
    crcs: tuple[int, ...]
    nbytes: int


class HostBlockMap:
    """LRU map of spilled blocks: opaque key (a trie node, a request id) ->
    `HostBlocks`. Insertion refreshes recency; `lru_key` is the drop
    candidate when ``max_host_blocks`` bites."""

    def __init__(self):
        self._entries: OrderedDict[Any, HostBlocks] = OrderedDict()

    def put(self, key: Any, hb: HostBlocks) -> None:
        self._entries[key] = hb
        self._entries.move_to_end(key)

    def pop(self, key: Any) -> HostBlocks:
        return self._entries.pop(key)

    def get(self, key: Any) -> HostBlocks | None:
        return self._entries.get(key)

    def __contains__(self, key: Any) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def lru_key(self) -> Any | None:
        return next(iter(self._entries), None)

    @property
    def blocks(self) -> int:
        return sum(len(hb.crcs) for hb in self._entries.values())

    @property
    def nbytes(self) -> int:
        return sum(hb.nbytes for hb in self._entries.values())


class ThrashGuard:
    """Sliding-window page-event rate detector with enter/exit hysteresis.

    ``record(n)`` logs n page events (in or out — churn is churn) and
    freezes when the window holds ``enter_events`` or more; while frozen,
    ``poll()`` unfreezes only after the window count stays at or below
    ``exit_fraction * enter_events`` for ``exit_s`` continuous seconds —
    the guard itself cannot flap. ``clock`` is injectable (tests drive the
    hysteresis deterministically)."""

    def __init__(self, window_s: float, enter_events: int,
                 exit_fraction: float, exit_s: float, clock=time.perf_counter):
        self.window_s = float(window_s)
        self.enter_events = int(enter_events)
        self.exit_events = int(enter_events * exit_fraction)
        self.exit_s = float(exit_s)
        self.clock = clock
        self.frozen = False
        self._events: deque[float] = deque()
        self._calm_since: float | None = None

    def _prune(self, now: float) -> None:
        while self._events and now - self._events[0] > self.window_s:
            self._events.popleft()

    def record(self, n: int = 1) -> bool:
        """Log ``n`` page events; True exactly when this call froze the
        guard (the caller raises the anomaly on that edge)."""
        now = self.clock()
        self._events.extend([now] * int(n))
        self._prune(now)
        if not self.frozen and len(self._events) >= self.enter_events:
            self.frozen = True
            self._calm_since = None
            return True
        return False

    def poll(self) -> bool:
        """Advance the hysteresis; True exactly when this call unfroze."""
        if not self.frozen:
            return False
        now = self.clock()
        self._prune(now)
        if len(self._events) > self.exit_events:
            self._calm_since = None
            return False
        if self._calm_since is None:
            self._calm_since = now
        if now - self._calm_since >= self.exit_s:
            self.frozen = False
            self._events.clear()
            self._calm_since = None
            return True
        return False

    @property
    def window_events(self) -> int:
        return len(self._events)


@dataclasses.dataclass
class HibernatedRequest:
    """A whole parked stream: the request (seed, params, prompt), its
    emitted tokens (the wake frontier — journal-flushed before parking),
    and host copies of its written KV blocks for the upload wake path."""

    request: Any
    tokens: list[int]
    token_times: list[float]  # `RequestOutput.token_times`, one per token
    blocks: HostBlocks
    n_content: int            # leading table blocks the host copy covers
    first_token_time: float | None
    hit: bool                 # prefix-cache hit flag, restored on wake
    t_hibernated: float


class _Ema:
    """First-sample-seeded exponential moving average (transfer rates)."""

    def __init__(self, alpha: float = 0.3):
        self.alpha = float(alpha)
        self.value = 0.0
        self._seeded = False

    def update(self, x: float) -> None:
        if not self._seeded:
            self.value, self._seeded = float(x), True
        else:
            self.value += self.alpha * (float(x) - self.value)


class KVTier:
    """The engine-side tier driver. Owns the host block map, the hibernation
    records, the thrash guard, and every spill/wake policy decision; all
    device work goes through the engine's jitted ``tier_wake`` scatter and
    plain ``jax.device_get`` reads. Constructed by `ServingEngine` when
    ``kv_tier=`` is set; ``clock`` is injectable for the
    policy/thrash tests — transfer RATES always use real wall time."""

    def __init__(self, engine: Any, config: KVTierConfig | None = None,
                 clock=time.perf_counter):
        self.engine = engine
        self.cfg = config or KVTierConfig()
        self.clock = clock
        self.guard = ThrashGuard(
            self.cfg.thrash_window_s, self.cfg.thrash_enter_events,
            self.cfg.thrash_exit_fraction, self.cfg.thrash_exit_s, clock=clock,
        )
        self.trie_blocks = HostBlockMap()
        self._hibernated: OrderedDict[int, HibernatedRequest] = OrderedDict()
        self._wake_t: dict[int, float] = {}
        self._xfer = _Ema()  # bytes/s over observed device_get/upload walls
        # exact per-block KV bytes, from the engine's pool leaves (the
        # cache-index leaf is per-slot state, not block content)
        self.block_bytes = 0
        num_blocks = engine._allocator.num_blocks
        for path, leaf in jax.tree_util.tree_leaves_with_path(engine._cache):
            if _is_index_leaf(path) or leaf.shape[0] != num_blocks:
                continue
            self.block_bytes += int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize

    # ------------------------------------------------------------- accounting
    @property
    def host_blocks(self) -> int:
        return self.trie_blocks.blocks + sum(
            r.n_content for r in self._hibernated.values())

    @property
    def host_bytes(self) -> int:
        return self.trie_blocks.nbytes + sum(
            r.blocks.nbytes for r in self._hibernated.values())

    @property
    def hibernated_count(self) -> int:
        return len(self._hibernated)

    @property
    def trie_host_blocks(self) -> int:
        return self.trie_blocks.blocks

    @property
    def trie_host_bytes(self) -> int:
        return self.trie_blocks.nbytes

    def records(self) -> list[HibernatedRequest]:
        """Hibernated records in park order (FIFO wake order) — the engine's
        snapshot/abort paths walk these like active slots."""
        return list(self._hibernated.values())

    def pop_record(self, request_id: int) -> HibernatedRequest | None:
        return self._hibernated.pop(request_id, None)

    def memory_stats(self) -> dict[str, int | float]:
        """The ``host_tier/*`` gauge namespace (`docs/observability.md`):
        current host ledger plus the lifetime tier counters. The device
        ledger is untouched by tiering — ``free + resident + private ==
        total`` holds through every spill/page-in transition; the host side
        adds ``bytes == blocks * block_bytes`` (the cross-tier invariant
        tests/test_telemetry.py asserts)."""
        m = self.engine.metrics
        return {
            "bytes": self.host_bytes,
            "blocks": self.host_blocks,
            "block_bytes": self.block_bytes,
            "hibernated": len(self._hibernated),
            "page_ins": int(m.host_page_ins.value),
            "page_outs": int(m.host_page_outs.value),
            "wakeups": int(m.host_wakeups.value),
            "thrash_events": int(m.host_thrash_events.value),
            "spill_frozen": int(self.guard.frozen),
        }

    # ------------------------------------------------------------ host copies
    def _gather(self, block_ids: list[int]) -> HostBlocks:
        """Host copies of pool blocks ``block_ids`` (forces the device to
        drain every dispatched write first — ``np.asarray`` on a jnp index
        result blocks until the value exists)."""
        eng = self.engine
        idx = jnp.asarray(np.asarray(block_ids, np.int32))

        def take(path, leaf):
            if _is_index_leaf(path):
                return np.zeros((len(block_ids),), np.int32)
            return np.asarray(leaf[idx])

        tree = jax.tree_util.tree_map_with_path(take, eng._cache)
        return HostBlocks(tree=tree, crcs=self._crcs(tree),
                          nbytes=self._kv_nbytes(tree))

    @staticmethod
    def _crcs(tree: Any) -> tuple[int, ...]:
        kv_leaves = [leaf for path, leaf in
                     jax.tree_util.tree_leaves_with_path(tree)
                     if not _is_index_leaf(path)]
        n = kv_leaves[0].shape[0] if kv_leaves else 0
        out = []
        for i in range(n):
            c = 0
            for leaf in kv_leaves:
                c = zlib.crc32(np.ascontiguousarray(leaf[i]).tobytes(), c)
            out.append(c)
        return tuple(out)

    @staticmethod
    def _kv_nbytes(tree: Any) -> int:
        return sum(leaf.nbytes for path, leaf in
                   jax.tree_util.tree_leaves_with_path(tree)
                   if not _is_index_leaf(path))

    def _padded(self, hb: HostBlocks, rows: int) -> Any:
        """Pad a host copy to the ``tier_wake`` program's fixed
        ``[blocks_per_slot, ...]`` leaf shapes (excess dest ids are the
        sentinel, so the padding never lands)."""
        def pad(path, leaf):
            if _is_index_leaf(path):
                return np.zeros((rows,), np.int32)
            out = np.zeros((rows,) + leaf.shape[1:], leaf.dtype)
            out[: leaf.shape[0]] = leaf
            return out

        return jax.tree_util.tree_map_with_path(pad, hb.tree)

    def _record_page_events(self, n: int) -> None:
        if self.guard.record(n):
            m = self.engine.metrics
            m.host_thrash_events.inc()
            if self.engine.tracer.enabled:
                self.engine.tracer.emit(
                    EV_ANOMALY, None, detector="host_tier_thrash",
                    edge="enter", window_events=self.guard.window_events,
                )

    # -------------------------------------------------------------- trie spill
    def _spill_victim(self) -> Any | None:
        """LRU unpinned device-backed trie node with no device-backed child
        (deepest-first by construction: a node qualifies only once its
        subtree is host-resident, so device-backed ⇒ parent device-backed
        stays invariant and page-in can always restore top-down)."""
        pc = self.engine.prefix_cache
        victim = None
        stack = list(pc._root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.ref > 0 or node.block_id is None:
                continue
            if any(c.block_id is not None for c in node.children.values()):
                continue
            if victim is None or node.last_used < victim.last_used:
                victim = node
        return victim

    def page_out_trie(self, n: int) -> int:
        """Spill up to ``n`` evictable trie blocks to host (they stay
        hit-able — the discard path this replaces is `PrefixCache.reclaim`).
        Returns device blocks actually freed."""
        if self.engine.prefix_cache is None or self.guard.frozen:
            return 0
        freed = 0
        while freed < n and not self.guard.frozen:
            victim = self._spill_victim()
            if victim is None:
                break
            self._spill_node(victim)
            freed += 1
        return freed

    def _spill_node(self, node: Any) -> None:
        eng = self.engine
        t0 = time.perf_counter()
        hb = self._gather([node.block_id])
        wall = max(time.perf_counter() - t0, 1e-9)
        self.trie_blocks.put(node, hb)
        eng._allocator.free([node.block_id])
        node.block_id = None
        eng.metrics.host_page_outs.inc()
        eng.metrics.host_page_out_s.observe(wall)
        self._xfer.update(hb.nbytes / wall)
        self._record_page_events(1)
        cap = self.cfg.max_host_blocks
        while cap is not None and self.trie_blocks.blocks > cap:
            lru = self.trie_blocks.lru_key()
            if lru is None or lru is node:
                break
            self._drop_spilled(lru)

    def _drop_spilled(self, node: Any) -> None:
        """Host-capacity eviction of a spilled trie subtree: past the host
        cap the content exists nowhere, so the nodes leave the trie (their
        descendants are all spilled — device-backed ⇒ parent device-backed)."""
        if node.parent is not None and node.parent.children.get(node.key) is node:
            del node.parent.children[node.key]
        stack = [node]
        while stack:
            cur = stack.pop()
            stack.extend(cur.children.values())
            if cur in self.trie_blocks:
                self.trie_blocks.pop(cur)
            if self.engine.metrics is not None:
                self.engine.metrics.prefix_evictions.inc()

    def page_in_node(self, node: Any) -> bool:
        """Restore one spilled trie block to a fresh device block. All or
        nothing: allocation failure changes NOTHING (no gauges move, the
        host copy stays); a content-hash mismatch refuses loudly."""
        eng = self.engine
        hb = self.trie_blocks.get(node)
        if hb is None:
            return False
        ids = eng._allocator.alloc(1)
        if ids is None:
            return False
        if self._crcs(hb.tree) != hb.crcs:
            eng._allocator.free(ids)
            raise RuntimeError(
                "host-tier content hash mismatch on trie page-in "
                "(host buffer corrupted)")
        t0 = time.perf_counter()
        rows = eng._blocks_per_slot
        dest = np.full(rows, eng._allocator.num_blocks, np.int32)
        dest[0] = ids[0]
        eng._tier_upload(dest, self._padded(hb, rows))
        wall = max(time.perf_counter() - t0, 1e-9)
        self.trie_blocks.pop(node)
        node.block_id = int(ids[0])
        eng.metrics.host_page_ins.inc()
        eng.metrics.host_page_in_s.observe(wall)
        self._xfer.update(hb.nbytes / wall)
        self._record_page_events(1)
        return True

    def ensure_resident(self, path: list[Any]) -> list[Any]:
        """Page a matched trie path's spilled nodes back in, in order;
        returns the longest leading run that is device-backed (a failed
        page-in truncates the match — the caller pins only what it got)."""
        for i, node in enumerate(path):
            if node.block_id is not None:
                continue
            if self.guard.frozen or not self.page_in_node(node):
                return path[:i]
        return path

    def revive(self, node: Any, block_id: int) -> None:
        """Donation met a spilled node whose bytes a retiring slot just
        rewrote on device (`PrefixCache.adopt`): take ownership of the
        fresh device block and drop the host copy — a free page-in."""
        if node in self.trie_blocks:
            self.trie_blocks.pop(node)
        node.block_id = int(block_id)

    # ------------------------------------------------------------- hibernation
    def _victims(self, now: float) -> list[int]:
        """Pressure-hibernation candidates, coldest first: long-idle slots
        (idle ≥ ``hibernate_idle_s``) by descending idleness, then the rest
        by arrival order (FIFO time-slicing). Slots inside their wake
        cooldown, or without a first emitted token, are exempt."""
        eng, cfg = self.engine, self.cfg
        out = []
        for slot in np.flatnonzero(eng._active):
            slot = int(slot)
            request, o = eng._slot_req[slot], eng._slot_out[slot]
            if request is None or o is None or not o.tokens:
                continue
            rid = request.request_id
            woken = self._wake_t.get(rid)
            if woken is not None and now - woken < cfg.wake_cooldown_s:
                continue
            idle = now - eng._slot_last_token_t[slot]
            long_idle = idle >= cfg.hibernate_idle_s
            arrival = (request.arrival_time
                       if request.arrival_time is not None else 0.0)
            out.append((slot, long_idle, idle, arrival, rid))
        out.sort(key=lambda t: (not t[1], -t[2] if t[1] else 0.0, t[3], t[4]))
        return [t[0] for t in out]

    def hibernate_slot(self, slot: int) -> int:
        """Park one admitted stream: flush its un-journaled tokens (the
        durable tier), copy its written blocks to host, then tear the slot
        down through `_release_slot` — the same generation bump + table-row
        neutralization every cancel relies on, so lagged in-flight writes
        drop. Returns the device blocks freed (the slot's private blocks)."""
        eng = self.engine
        request, out = eng._slot_req[slot], eng._slot_out[slot]
        if request is None or out is None or not out.tokens:
            return 0
        if eng.journal is not None and len(out.tokens) > eng._slot_logged[slot]:
            eng.journal.log_progress(
                out.request_id, out.tokens[int(eng._slot_logged[slot]):],
                len(out.tokens))
            eng._slot_logged[slot] = len(out.tokens)
        plen, m = out.prompt_len, len(out.tokens)
        bt = eng._block_tokens
        # KV written so far covers positions [0, plen + m - 2] (the device
        # may be ahead of the host view by in-flight dispatches — those
        # bytes are the deterministic continuation wake re-decodes, so a
        # fresher copy is still the same copy)
        n_content = -(-(plen + m - 1) // bt)
        table = eng._slot_table_host[slot]
        ids = [int(x) for x in table[:n_content]]
        t0 = time.perf_counter()
        hb = self._gather(ids)
        wall = max(time.perf_counter() - t0, 1e-9)
        rec = HibernatedRequest(
            request=request, tokens=list(out.tokens),
            token_times=list(out.token_times), blocks=hb,
            n_content=n_content, first_token_time=out.first_token_time,
            hit=bool(eng._slot_hit[slot]), t_hibernated=self.clock(),
        )
        freed = len(eng._slot_priv[slot])
        eng._release_slot(slot)
        self._hibernated[request.request_id] = rec
        eng.metrics.host_hibernated.inc()
        eng.metrics.host_page_outs.inc(n_content)
        eng.metrics.host_page_out_s.observe(wall)
        self._xfer.update(hb.nbytes / wall)
        self._record_page_events(n_content)
        return freed

    # --------------------------------------------------------------- pressure
    def release_for(self, demand_blocks: int) -> None:
        """Spill-then-admit (`ServingEngine._reserve_blocks`): free device
        blocks until the allocator can cover ``demand_blocks`` — evictable
        trie blocks to host first, then (``spill_on_pressure``) hibernate
        the coldest slots, which unpins their trie prefixes for the next
        spill round. A frozen guard makes this a no-op; the caller then
        falls back to discard eviction + requeue, the tier-off behavior."""
        eng, cfg = self.engine, self.cfg
        alloc = eng._allocator
        while alloc.free_count < demand_blocks and not self.guard.frozen:
            if self.page_out_trie(demand_blocks - alloc.free_count):
                continue
            if not cfg.spill_on_pressure:
                return
            if int(eng._active.sum()) <= cfg.min_resident_slots:
                return
            victims = self._victims(self.clock())
            if not victims:
                return
            self.hibernate_slot(victims[0])

    def pressure_headroom(self) -> int:
        """Blocks the pressure path could free right now beyond the free
        list and plain trie eviction (`ServingEngine._paged_capacity`'s
        optimistic probe): private blocks of hibernatable slots above the
        residency floor. 0 while frozen."""
        eng, cfg = self.engine, self.cfg
        if self.guard.frozen or not cfg.spill_on_pressure:
            return 0
        spare = max(0, int(eng._active.sum()) - cfg.min_resident_slots)
        if spare == 0:
            return 0
        victims = self._victims(self.clock())
        return sum(len(eng._slot_priv[s]) for s in victims[:spare])

    # -------------------------------------------------------------------- wake
    def _choose(self, rec: HibernatedRequest) -> str:
        if self.cfg.wake_policy != "auto":
            return self.cfg.wake_policy
        replay = len(rec.request.prompt) + len(rec.tokens)
        prefill_tps = (self.engine.metrics.tokens_per_sec()
                       * self.cfg.prefill_speedup)
        return choose_wake(rec.blocks.nbytes, replay, self._xfer.value,
                           prefill_tps)

    def _wake_prefill(self, rec: HibernatedRequest) -> None:
        """Re-enter through the scheduler's resumed-request front lane: the
        continuation prefill from ``resume_tokens`` is the journal-proven
        bit-exact path. Host blocks are dropped (tokens beyond the bucket
        cap are re-decoded deterministically, like `ServingEngine.resume`)."""
        eng = self.engine
        request = rec.request
        plen = len(request.prompt)
        keep = max(0, min(len(rec.tokens), eng.scheduler.max_prompt_len - plen))
        request.resume_tokens = [int(t) for t in rec.tokens[:keep]]
        request.deadline_s = None  # consumed at first admission
        eng.scheduler.requeue(request)

    def try_wakes(self, max_wakes: int = 1) -> int:
        """Wake up to ``max_wakes`` hibernated streams (FIFO park order).
        Upload wake needs a free slot plus an all-or-nothing block
        reservation; when blocks are short it spills trie (never other
        slots — waking must not evict the working set) and otherwise defers
        — except on an idle engine, where deferring would deadlock, so the
        wake falls back to re-prefill and rides ordinary admission
        backpressure."""
        eng = self.engine
        woken = 0
        while self._hibernated and woken < max_wakes:
            if not eng._free:
                break
            rid, rec = next(iter(self._hibernated.items()))
            mode = self._choose(rec)
            idle_engine = (not eng._active.any()
                           and eng.scheduler.queue_depth == 0)
            if mode == "upload":
                extent = FIFOScheduler.decode_extent(rec.request, eng.max_len)
                need = -(-extent // eng._block_tokens)
                if eng._allocator.free_count < need:
                    self.page_out_trie(need - eng._allocator.free_count)
                if eng._allocator.free_count < need:
                    if not idle_engine:
                        break
                    mode = "prefill"
            if mode == "upload" and not eng._wake_hibernated_upload(rec):
                if not idle_engine:
                    break
                mode = "prefill"
            if mode == "prefill":
                self._wake_prefill(rec)
            del self._hibernated[rid]
            self._wake_t[rid] = self.clock()
            eng.metrics.host_wakeups.inc()
            woken += 1
        return woken

    # -------------------------------------------------------------------- poll
    def poll(self) -> None:
        """The per-step tier tick (`ServingEngine._admit_pending` start):
        advance the thrash hysteresis, run background low-water spill and
        idle hibernation, then attempt one wake."""
        self.guard.poll()
        eng, cfg = self.engine, self.cfg
        now = self.clock()
        if (cfg.low_water_blocks > 0 and not self.guard.frozen
                and eng._allocator.free_count < cfg.low_water_blocks):
            self.page_out_trie(cfg.low_water_blocks - eng._allocator.free_count)
        if cfg.hibernate_idle_s != float("inf") and not self.guard.frozen:
            for slot in np.flatnonzero(eng._active):
                slot = int(slot)
                out = eng._slot_out[slot]
                if out is None or not out.tokens:
                    continue
                if now - eng._slot_last_token_t[slot] >= cfg.hibernate_idle_s:
                    self.hibernate_slot(slot)
        self.try_wakes()
