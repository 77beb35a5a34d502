"""Admission queues with prompt-length bucketing and bounded backpressure.

Two schedulers share one interface:

- `FIFOScheduler` — strict arrival order. The default, and the *parity
  oracle*: every ordering policy must degenerate to it when only one
  priority class and one tenant are in play, so greedy token streams stay
  bit-for-bit identical to the FIFO path.
- `FairScheduler` — priority classes served highest-first, with per-tenant
  deficit-weighted round-robin *within* a class and a deterministic
  bypass-count starvation bound across classes (docs/serving.md "Front
  door"). All ordering decisions are host-side integer bookkeeping: the
  jitted decode step never sees the policy, so switching schedulers cannot
  perturb device numerics.

Bucketing keeps prefill static-shape: a prompt is right-padded to the smallest
configured bucket that holds it, so admission compiles once per bucket, never
per prompt length. The queue is bounded; a full queue rejects with a reason
instead of growing without limit (the engine's only unbounded resource would
otherwise be host memory).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

from .request import (
    REJECT_EMPTY_PROMPT,
    REJECT_PROMPT_TOO_LONG,
    REJECT_QUEUE_FULL,
    Request,
    SubmitResult,
)
from .trace import EV_QUEUED, NULL_TRACER


def _stamp_queued(request: Request) -> None:
    """Stamp the request's enqueue time unless it already waits: a group put
    back by block-pool backpressure keeps its first stamp, and the engine
    clears the stamp once a dispatch takes the request (its ``serve.queued``
    span), so a requeue after admission starts a new wait."""
    if request.queued_time is None:
        request.queued_time = time.perf_counter()


class FIFOScheduler:
    """Admission control for the serving engine: validate, enqueue in arrival
    order, hand requests to free slots, and push back when full."""

    def __init__(
        self,
        prompt_buckets: tuple[int, ...] = (32, 128, 512),
        max_queue: int = 128,
        max_prompt_len: int | None = None,
    ):
        self.buckets = tuple(sorted({int(b) for b in prompt_buckets}))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"prompt_buckets must be positive ints, got {prompt_buckets}")
        self.max_queue = int(max_queue)
        # the engine caps this at n_positions - 1 so every admitted request has
        # room for at least one generated token
        self.max_prompt_len = int(max_prompt_len or self.buckets[-1])
        # prefix-aware bucketing hook (set by the engine when its prefix cache
        # is enabled): maps a request to the prompt-token count admission will
        # actually PREFILL — the uncached suffix. Grouping by suffix bucket
        # keeps one batched prefill per (suffix_bucket, batch_bucket) pair, so
        # the compile cache stays bounded even though cached prefixes shrink
        # prompts by arbitrary block multiples.
        self.prefill_len_fn = None
        # tracing hook (serving/trace.py): the engine points this at its
        # tracer so every QUEUED edge — fresh acceptance or watchdog requeue —
        # is stamped where the queue actually changes
        self.tracer = NULL_TRACER
        # block-pool capacity hook (set by the engine):
        # maps the front run's requests to how many of them the block pool can
        # actually seat right now. Admission is gated on BLOCKS, not just free
        # slots — a free slot with no blocks behind it would crash mid-decode,
        # so the gate lives here where the run is sized.
        self.capacity_fn = None
        self._queue: deque[Request] = deque()

    def bucket_for(self, prompt_len: int) -> int:
        """Smallest bucket holding ``prompt_len`` (the prefill pad target)."""
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest bucket {self.buckets[-1]}"
        )

    @staticmethod
    def decode_extent(request: Request, max_len: int) -> int:
        """The furthest KV position + 1 this request can ever occupy:
        ``min(prompt + max_new_tokens, max_len)``. This single number prices
        paged block reservations AND bounds every decode write — the
        admission budget is derived from it so ``pos + remaining + 1 <=
        extent`` holds for live slots, which is what lets a speculative
        k+1-token verify segment clamp its write length to ``remaining + 1``
        and stay inside the reservation (see engine `_build_spec_step_fn`)."""
        return min(len(request.prompt) + int(request.params.max_new_tokens),
                   int(max_len))

    def _validate(self, request: Request) -> SubmitResult | None:
        """Shared admission validation (None = admissible). Validation is
        against the PREFILL length — prompt plus any resumed stream prefix
        (`Request.resume_tokens`): a restored mid-flight request must fit a
        bucket just like a fresh prompt would."""
        if len(request.prompt) == 0:
            return SubmitResult(False, request.request_id, REJECT_EMPTY_PROMPT,
                                "prompt has no tokens")
        n = request.prefill_len
        if n > self.max_prompt_len or n > self.buckets[-1]:
            return SubmitResult(
                False, request.request_id, REJECT_PROMPT_TOO_LONG,
                f"prompt length {n} > max {min(self.max_prompt_len, self.buckets[-1])}",
            )
        if self.queue_depth >= self.max_queue:
            return SubmitResult(
                False, request.request_id, REJECT_QUEUE_FULL,
                f"{self.queue_depth} requests already queued",
            )
        return None

    def submit(self, request: Request) -> SubmitResult:
        """Enqueue or reject-with-reason (never blocks, never raises on load)."""
        rejected = self._validate(request)
        if rejected is not None:
            return rejected
        self._queue.append(request)
        _stamp_queued(request)
        if self.tracer.enabled:
            self.tracer.emit(EV_QUEUED, request.request_id,
                             queue_depth=len(self._queue),
                             bucket=self.prefill_bucket_for(request))
        return SubmitResult(True, request.request_id)

    def next_ready(self) -> Request | None:
        """Pop the oldest queued request (FIFO), or None when idle."""
        return self._queue.popleft() if self._queue else None

    def prefill_bucket_for(self, request: Request) -> int:
        """The bucket admission will pad this request's PREFILL to: its full
        prompt bucket (prompt + resumed prefix), or — with a prefix cache
        probing via ``prefill_len_fn`` — the bucket of just the uncached
        suffix."""
        n = request.prefill_len
        if self.prefill_len_fn is not None:
            n = max(1, min(n, int(self.prefill_len_fn(request))))
        return self.bucket_for(n)

    def _run_key(self, request: Request) -> tuple[int, bool]:
        """The batched-admission grouping key: prefill bucket plus — when a
        prefix cache is probing — the request's ``cache_prefix`` flag. A
        cached and an uncached admission must never share one run: they take
        DIFFERENT jitted programs (cached-gather vs plain prefill), so a mixed
        group would both recompile per mix pattern and push opted-out
        (privacy-scoped) prompts through the block-pool gather path. A
        resumed request (``resume_tokens``) always rides the plain program —
        its continuation prefill never matches the block pool. The cluster's
        journal-backed migration leans on exactly this: a migrated request
        re-submitted with ``prefill_len > 0`` can land on ANY replica
        without ever mixing into that replica's cached-admission runs
        (`serving/cluster.py`; tests/test_cluster.py pins the interaction
        with ``capacity_fn``)."""
        return (
            self.prefill_bucket_for(request),
            (bool(request.cache_prefix) and not request.resume_tokens)
            if self.prefill_len_fn is not None else False,
        )

    def peek_run(self, max_n: int) -> int:
        """Length (up to ``max_n``) of the contiguous run of queued requests at
        the FRONT that share the head's PREFILL bucket (the suffix bucket when
        a prefix cache is probing) and — with the cache enabled — the head's
        ``cache_prefix`` flag (see `_run_key`) — the group one batched
        admission call can prefill together. Only the front run counts:
        skipping past a differently-bucketed head to batch later arrivals
        would break FIFO fairness."""
        if not self._queue or max_n <= 0:
            return 0
        head_key = self._run_key(self._queue[0])
        n = 0
        for r in self._queue:
            if n >= max_n or self._run_key(r) != head_key:
                break
            n += 1
        if n and self.capacity_fn is not None:
            # shrink the run to what the block pool can seat —
            # the hook sees the actual front requests so it can price each
            # one's reservation (prompt + budget, minus any aliased prefix)
            n = max(0, min(n, int(self.capacity_fn(
                [self._queue[i] for i in range(n)]))))
        return n

    def pop_run(self, n: int) -> list[Request]:
        """Pop the ``n`` front requests (the group sized via `peek_run`)."""
        return [self._queue.popleft() for _ in range(min(n, len(self._queue)))]

    def requeue(self, request: Request) -> None:
        """Put a request at the FRONT of the queue (the watchdog's re-prefill
        path: a quarantined request must not wait behind new arrivals)."""
        self._queue.appendleft(request)
        _stamp_queued(request)
        if self.tracer.enabled:
            self.tracer.emit(EV_QUEUED, request.request_id,
                             queue_depth=len(self._queue),
                             bucket=self.prefill_bucket_for(request),
                             requeued=True)

    def pop_expired(self, now: float) -> list[Request]:
        """Remove and return every queued request whose ``deadline_s`` queue
        budget has elapsed (the engine rejects them with REJECT_DEADLINE)."""
        expired = [
            r for r in self._queue
            if r.deadline_s is not None and r.arrival_time is not None
            and now - r.arrival_time >= r.deadline_s
        ]
        if expired:
            dead = set(map(id, expired))
            self._queue = deque(r for r in self._queue if id(r) not in dead)
        return expired

    def cancel(self, request_id: int) -> Request | None:
        """Remove a queued request by id (None if not queued here)."""
        for r in self._queue:
            if r.request_id == request_id:
                self._queue.remove(r)
                return r
        return None

    def snapshot_queue(self) -> list[Request]:
        """The queued requests in order, WITHOUT removing them (the engine's
        `snapshot` serializes the queue through this)."""
        return list(self._queue)

    def drain_queue(self) -> list[Request]:
        """Remove and return everything queued (abort_all's shutdown path)."""
        drained = list(self._queue)
        self._queue.clear()
        return drained

    @property
    def queue_depth(self) -> int:
        return len(self._queue)


@dataclass
class _Entry:
    """One queued request plus the fair scheduler's bookkeeping: its arrival
    sequence number and how many later arrivals have been served ahead of it
    (the starvation-bound counter)."""

    req: Request
    seq: int
    bypass: int = 0


class FairScheduler(FIFOScheduler):
    """Class-based admission ordering: priority classes served highest-first,
    per-tenant deficit round-robin (DRR) within a class, and a deterministic
    starvation bound across everything.

    Ordering rules, in precedence order:

    1. **Watchdog requeues** (`requeue`) always go first — same contract as
       FIFO's appendleft: a quarantined request must not wait behind new
       arrivals.
    2. **Starved requests**: any request that has watched
       ``starvation_bound`` later arrivals get served ahead of it is promoted
       to absolute precedence, oldest first. The bound is a *count*, not a
       wall-clock wait, so it is deterministic under replay and provable in
       tests: no request can be bypassed more than ``starvation_bound`` times,
       regardless of the class/tenant mix.
    3. **Deficit round-robin**: within the highest non-empty priority class,
       tenants take turns; each visit grants ``quantum_tokens`` of budget and
       a tenant serves queued requests while its accumulated deficit covers
       their cost (``prefill_len + max_new_tokens`` — the tokens the request
       can actually consume). A tenant whose queue empties forfeits its
       remaining deficit (standard DRR: no hoarding while idle).

    With a single priority class and a single tenant the rotation has one
    member and DRR degenerates to exact arrival order — bit-for-bit FIFO
    parity, which tests/test_frontend.py pins against `FIFOScheduler` as the
    oracle. All state is host-side integers: the policy can never perturb
    device numerics.

    `peek_run`/`pop_run` keep the batched-admission contract: the run is the
    contiguous same-`_run_key` group at the front OF THE SERVICE ORDER, and
    `peek_run` never commits DRR state — only `pop_run` advances deficits,
    rotation, and bypass counters.
    """

    def __init__(
        self,
        prompt_buckets: tuple[int, ...] = (32, 128, 512),
        max_queue: int = 128,
        max_prompt_len: int | None = None,
        quantum_tokens: int = 64,
        starvation_bound: int = 8,
    ):
        super().__init__(prompt_buckets, max_queue, max_prompt_len)
        if quantum_tokens < 1:
            raise ValueError(f"quantum_tokens must be >= 1, got {quantum_tokens}")
        if starvation_bound < 1:
            raise ValueError(f"starvation_bound must be >= 1, got {starvation_bound}")
        self.quantum_tokens = int(quantum_tokens)
        self.starvation_bound = int(starvation_bound)
        self._seq = 0
        # watchdog requeues: absolute precedence, LIFO at the front
        self._front: deque[_Entry] = deque()
        # priority -> tenant -> FIFO deque of entries. Invariant: a tenant key
        # exists iff its deque is non-empty iff it is in the class rotation.
        self._classes: dict[int, dict[str, deque[_Entry]]] = {}
        # priority -> tenant visit rotation (persists across pop_run calls so
        # round-robin continues where it left off)
        self._rotation: dict[int, deque[str]] = {}
        # priority -> tenant -> accumulated token deficit
        self._deficit: dict[int, dict[str, int]] = {}

    # --- cost model -------------------------------------------------------

    @staticmethod
    def _cost(entry: _Entry) -> int:
        """Tokens this request bills its tenant: everything it can consume —
        its prefill plus its full decode budget."""
        r = entry.req
        return max(1, r.prefill_len + int(r.params.max_new_tokens))

    # --- enqueue / remove -------------------------------------------------

    def _enqueue(self, request: Request) -> None:
        p = int(getattr(request, "priority", 0))
        t = str(getattr(request, "tenant", "") or "")
        tenants = self._classes.setdefault(p, {})
        if t not in tenants:
            tenants[t] = deque()
            self._rotation.setdefault(p, deque()).append(t)
        self._seq += 1
        tenants[t].append(_Entry(request, self._seq))

    def _remove_entry(self, entry: _Entry) -> None:
        if entry in self._front:
            self._front.remove(entry)
            return
        for p, tenants in self._classes.items():
            for t, dq in tenants.items():
                if entry in dq:
                    dq.remove(entry)
                    if not dq:
                        self._forget_tenant(p, t)
                    return

    def _forget_tenant(self, p: int, t: str) -> None:
        """Drop an emptied tenant: its deque, rotation slot, and deficit (DRR
        resets budget on idle so a tenant cannot hoard while absent)."""
        tenants = self._classes.get(p, {})
        if t in tenants and not tenants[t]:
            del tenants[t]
        rot = self._rotation.get(p)
        if rot is not None and t in rot:
            rot.remove(t)
        self._deficit.get(p, {}).pop(t, None)
        if not tenants:
            self._classes.pop(p, None)
            self._rotation.pop(p, None)
            self._deficit.pop(p, None)

    def _entries(self):
        yield from self._front
        for tenants in self._classes.values():
            for dq in tenants.values():
                yield from dq

    # --- the ordering policy ---------------------------------------------

    def _ordered(self, commit_n: int | None = None) -> list[_Entry]:
        """The full service order under current state.

        With ``commit_n=None`` this is a pure function — a *peek* that
        simulates DRR on staging copies and touches nothing. With
        ``commit_n=k`` the first ``k`` entries are actually served: they are
        removed, the rotation/deficit state is advanced exactly as far as the
        simulation got when the k-th entry was served, and every request
        still queued has its bypass counter bumped once per later-arrived
        entry that was served ahead of it.
        """
        front = deque(self._front)
        classes = {p: {t: deque(dq) for t, dq in ts.items()}
                   for p, ts in self._classes.items()}
        rotation = {p: deque(r) for p, r in self._rotation.items()}
        deficit = {p: dict(d) for p, d in self._deficit.items()}
        order: list[_Entry] = []
        limit = self.queue_depth if commit_n is None else min(commit_n,
                                                              self.queue_depth)

        def done() -> bool:
            return commit_n is not None and len(order) >= limit

        # 1. watchdog requeues, in deque order
        while front and not done():
            order.append(front.popleft())
        # 2. starved entries, oldest arrival first
        if not done():
            starved = sorted(
                (e for ts in classes.values() for dq in ts.values()
                 for e in dq if e.bypass >= self.starvation_bound),
                key=lambda e: e.seq)
            for e in starved:
                if done():
                    break
                for ts in classes.values():
                    for dq in ts.values():
                        if e in dq:
                            dq.remove(e)
                order.append(e)
        # 3. DRR over the highest non-empty class downward
        for p in sorted(classes, reverse=True):
            tenants = classes[p]
            rot = rotation.setdefault(p, deque())
            defs = deficit.setdefault(p, {})
            while not done() and any(tenants.get(t) for t in rot):
                t = rot[0]
                dq = tenants.get(t)
                if not dq:
                    rot.popleft()
                    defs.pop(t, None)
                    continue
                defs[t] = defs.get(t, 0) + self.quantum_tokens
                while dq and defs[t] >= self._cost(dq[0]) and not done():
                    e = dq.popleft()
                    defs[t] -= self._cost(e)
                    order.append(e)
                if not dq:
                    rot.popleft()
                    defs.pop(t, None)
                else:
                    rot.rotate(-1)
            if done():
                break

        if commit_n is None:
            return order
        served = order[:limit]
        # commit: write staging back, prune emptied tenants, bump bypasses
        self._front = front
        self._classes = {p: {t: dq for t, dq in ts.items() if dq}
                         for p, ts in classes.items()}
        self._classes = {p: ts for p, ts in self._classes.items() if ts}
        self._rotation = {
            p: deque(t for t in rotation.get(p, ()) if t in self._classes[p])
            for p in self._classes}
        self._deficit = {
            p: {t: v for t, v in deficit.get(p, {}).items()
                if t in self._classes[p]}
            for p in self._classes}
        for e in self._entries():
            e.bypass += sum(1 for s in served if s.seq > e.seq)
        return served

    # --- FIFOScheduler interface -----------------------------------------

    def submit(self, request: Request) -> SubmitResult:
        rejected = self._validate(request)
        if rejected is not None:
            return rejected
        self._enqueue(request)
        _stamp_queued(request)
        if self.tracer.enabled:
            self.tracer.emit(EV_QUEUED, request.request_id,
                             queue_depth=self.queue_depth,
                             bucket=self.prefill_bucket_for(request),
                             priority=int(getattr(request, "priority", 0)),
                             tenant=str(getattr(request, "tenant", "") or ""))
        return SubmitResult(True, request.request_id)

    def next_ready(self) -> Request | None:
        popped = self._ordered(commit_n=1)
        return popped[0].req if popped else None

    def peek_run(self, max_n: int) -> int:
        if self.queue_depth == 0 or max_n <= 0:
            return 0
        order = self._ordered()
        head_key = self._run_key(order[0].req)
        n = 0
        for e in order:
            if n >= max_n or self._run_key(e.req) != head_key:
                break
            n += 1
        if n and self.capacity_fn is not None:
            n = max(0, min(n, int(self.capacity_fn(
                [order[i].req for i in range(n)]))))
        return n

    def pop_run(self, n: int) -> list[Request]:
        return [e.req for e in self._ordered(commit_n=n)]

    def requeue(self, request: Request) -> None:
        self._seq += 1
        self._front.appendleft(_Entry(request, self._seq))
        _stamp_queued(request)
        if self.tracer.enabled:
            self.tracer.emit(EV_QUEUED, request.request_id,
                             queue_depth=self.queue_depth,
                             bucket=self.prefill_bucket_for(request),
                             requeued=True)

    def pop_expired(self, now: float) -> list[Request]:
        expired = [
            e for e in self._entries()
            if e.req.deadline_s is not None and e.req.arrival_time is not None
            and now - e.req.arrival_time >= e.req.deadline_s
        ]
        for e in expired:
            self._remove_entry(e)
        return [e.req for e in expired]

    def cancel(self, request_id: int) -> Request | None:
        for e in list(self._entries()):
            if e.req.request_id == request_id:
                self._remove_entry(e)
                return e.req
        return None

    def snapshot_queue(self) -> list[Request]:
        """Queued requests in SERVICE order (what would be admitted next) —
        a fresh scheduler fed this sequence re-derives the same order."""
        return [e.req for e in self._ordered()]

    def drain_queue(self) -> list[Request]:
        drained = [e.req for e in self._ordered()]
        self._front.clear()
        self._classes.clear()
        self._rotation.clear()
        self._deficit.clear()
        return drained

    @property
    def queue_depth(self) -> int:
        return (len(self._front)
                + sum(len(dq) for ts in self._classes.values()
                      for dq in ts.values()))

    def class_stats(self) -> dict[int, dict[str, object]]:
        """Per-priority-class queue state for telemetry/serve_top: total
        depth, per-tenant depths, and how many entries are starvation-promoted
        right now."""
        stats: dict[int, dict[str, object]] = {}
        for p, tenants in self._classes.items():
            depths = {t: len(dq) for t, dq in tenants.items()}
            starved = sum(1 for dq in tenants.values()
                          for e in dq if e.bypass >= self.starvation_bound)
            stats[p] = {"depth": sum(depths.values()),
                        "tenants": depths, "starved": starved}
        if self._front:
            stats.setdefault(-1, {"depth": 0, "tenants": {}, "starved": 0})
            stats[-1]["depth"] = len(self._front)
        return stats

    def class_gauges(self) -> dict[str, object]:
        """`class_stats` flattened into ``serving/class/<p>/...`` telemetry
        gauges (the per-class rows `tools/serve_top.py` renders)."""
        out: dict[str, object] = {}
        for p, st in self.class_stats().items():
            out[f"serving/class/{p}/queue_depth"] = st["depth"]
            out[f"serving/class/{p}/starved"] = st["starved"]
            out[f"serving/class/{p}/tenants"] = len(st["tenants"])
        return out
