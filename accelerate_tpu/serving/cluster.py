"""Multi-replica serving cluster: a prefix-aware, health-aware router over
supervised engines with journal-backed migration (`docs/serving.md`
"Multi-replica serving").

PRs 1-12 built everything ONE replica needs — continuous batching, paged KV
with copy-free prefix sharing, crash-exact journal resume, a self-healing
supervisor. The :class:`ServingCluster` is the layer above: it fronts N
`EngineSupervisor`-wrapped replicas behind the same ``submit`` / ``step`` /
``drain`` surface the single engine exposes, so a caller scales from one
replica to N by changing a constructor argument, never its serving loop.

Three responsibilities, all host-side (inter-replica placement is orthogonal
to each replica's intra-mesh sharding — the GSPMD split):

**Placement** is *prefix-aware*: each replica's radix trie
(`serving/prefix_cache.py`) answers `PrefixCache.match_len` as a cheap,
non-pinning longest-prefix probe, and a request routes to the replica
holding the longest cached prefix of its prompt, tie-broken by load (queue
depth + active slots). Routing only chooses WHICH replica serves a request —
every replica runs the same module/params, so tokens are bit-for-bit
identical whichever way the coin lands (the cluster parity contract,
`tests/test_cluster.py`). ``policy="round_robin"`` keeps the affinity-blind
baseline for A/B measurement (`benchmarks/bench_serving.py` records the trie
hit-rate and TTFT uplift).

**Health gating** consumes each supervisor's `heartbeat()`: an unhealthy
replica receives no admissions, a stalled one is avoided whenever a calm
replica exists (stall is advisory — a cluster that is ALL slow still admits
rather than bouncing), and a replica in overload brownout stops receiving
admissions its own gate would shed (``priority < brownout_level``) — the
router sends them to a calm replica instead of bouncing them off the hot
one.

**Migration** is journal-backed: when a replica's `RestartBudget` exhausts,
its supervisor fails it loudly and every in-flight request is journaled as
``rejected:unhealthy`` with its partial stream. The cluster intercepts that
death, scans the dead replica's journal (the source of truth), dedups
requests that genuinely finished, and resubmits the rest to healthy replicas
carrying their emitted tokens as ``resume_tokens`` — one continuation
prefill plus a fast-forwarded rng chain continues each stream bit-for-bit,
so a replica kill loses zero requests and re-generates zero emitted tokens
(`tools/chaos_serve.py` ``CHAOS_SCENARIO=replica_kill`` proves it). The
resubmitted progress is re-journaled on the target replica, so a SECOND kill
is just another migration.

Replica **roles** (``prefill`` / ``decode`` / ``mixed``) ship as a routing
policy field: fresh admissions go to prefill-capable replicas, migrated
continuations prefer decode-capable ones. With every replica ``mixed``
(the default) the field is inert — it exists so the follow-up disaggregated
KV-handoff PR slots in without an API change.

Request ids: each engine stamps its own ``request_id``, so the cluster owns
a CLUSTER-level id space and translates on the way in and out — callers see
one monotone id sequence regardless of placement, exactly as with a single
engine.
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Any, Callable

from .journal import RequestJournal
from .metrics import ServingMetrics, aggregate_snapshots
from .request import (
    FINISH_EOS,
    FINISH_LENGTH,
    REJECT_OVERLOAD,
    REJECT_UNHEALTHY,
    Request,
    RequestOutput,
    SamplingParams,
    SubmitResult,
)
from ..reliability import faults
from .supervisor import EngineSupervisor, EngineUnhealthyError, SupervisorConfig
from .trace import EV_MIGRATE, EV_ROUTE, EV_SCALE

# replica roles (routing policy field — see module docstring)
ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"
ROLE_MIXED = "mixed"
ROLES = (ROLE_PREFILL, ROLE_DECODE, ROLE_MIXED)

# placement policies
POLICY_PREFIX = "prefix"
POLICY_ROUND_ROBIN = "round_robin"
POLICIES = (POLICY_PREFIX, POLICY_ROUND_ROBIN)

# replica lifecycle states (docs/reliability.md "Elastic fleet"): OK serves,
# DRAINING is excluded from placement but still stepped until its in-flight
# work finishes or journal-migrates, DEAD is a budget-exhausted supervisor
# awaiting replacement, RETIRED is terminal — journal closed, index never
# reused, the handle stays in ``replicas`` so positional lookups stay valid
STATE_OK = "ok"
STATE_DRAINING = "draining"
STATE_DEAD = "dead"
STATE_RETIRED = "retired"
STATES = (STATE_OK, STATE_DRAINING, STATE_DEAD, STATE_RETIRED)

_UNHEALTHY_REASON = f"rejected:{REJECT_UNHEALTHY}"


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """Knobs for the routing layer.

    - ``policy``: ``"prefix"`` (longest-cached-prefix placement, tie-broken
      by load) or ``"round_robin"`` (the affinity-blind baseline);
    - ``roles``: one role per replica (``prefill`` / ``decode`` /
      ``mixed``); None means every replica is ``mixed``. Fresh admissions
      route to prefill-capable replicas, migrated continuations prefer
      decode-capable ones (falling back to any healthy replica rather than
      stranding work);
    - ``migrate``: journal-backed migration off a budget-exhausted replica
      (True, the default). With False a dead replica's backlog is delivered
      as ``rejected:unhealthy`` — the single-supervisor fail-loud behavior.
    """

    policy: str = POLICY_PREFIX
    roles: tuple[str, ...] | None = None
    migrate: bool = True

    def __post_init__(self):
        if self.policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, "
                             f"got {self.policy!r}")
        if self.roles is not None:
            bad = [r for r in self.roles if r not in ROLES]
            if bad:
                raise ValueError(f"roles must be drawn from {ROLES}, "
                                 f"got {bad}")


class _SumCounter:
    """Duck-types `metrics.Counter` (``.value``) over a live aggregate."""

    __slots__ = ("_fn",)

    def __init__(self, fn: Callable[[], int]):
        self._fn = fn

    @property
    def value(self) -> int:
        return self._fn()


class _ClusterMetricsView:
    """The cluster's ``metrics`` attribute: duck-types the slice of
    `ServingMetrics` the telemetry exporter reads (``snapshot()``, ``steps``)
    as a live aggregate over the replicas' own metrics, plus ``cluster/*``
    routing gauges. Per-replica detail stays on each replica's metrics and
    is exported under the ``replica<i>/`` namespace (`serving/telemetry.py`).
    """

    def __init__(self, cluster: "ServingCluster"):
        self._cluster = cluster
        self.steps = _SumCounter(lambda: sum(
            r.metrics.steps.value for r in cluster.replicas))

    def snapshot(self) -> dict[str, Any]:
        cluster = self._cluster
        out = aggregate_snapshots(
            [r.metrics.snapshot() for r in cluster.replicas])
        out.update(cluster.router_stats())
        if cluster.autoscaler is not None:
            out.update(cluster.autoscaler.gauges())
        return out


class ReplicaHandle:
    """One supervised replica: its index, role, supervisor, journal, and
    lifecycle position (module ``STATE_*`` constants). ``index`` is stable
    and never reused across retire/replace — handles stay in
    ``ServingCluster.replicas`` after retirement so ``replicas[i].index == i``
    holds for the cluster's positional routing tables."""

    __slots__ = ("index", "role", "supervisor", "journal_path", "metrics",
                 "draining", "retired", "migrated")

    def __init__(self, index: int, role: str, supervisor: EngineSupervisor,
                 journal_path: Path, metrics: ServingMetrics):
        self.index = index
        self.role = role
        self.supervisor = supervisor
        self.journal_path = journal_path
        self.metrics = metrics
        self.draining = False
        self.retired = False
        # whether this replica's journal backlog has already been migrated
        # (step()'s death intercept or a force retire) — replace_replica must
        # not re-run the migration and duplicate the resubmits
        self.migrated = False

    @property
    def healthy(self) -> bool:
        return not self.retired and not self.supervisor.unhealthy

    @property
    def state(self) -> str:
        if self.retired:
            return STATE_RETIRED
        if self.supervisor.unhealthy:
            return STATE_DEAD
        if self.draining:
            return STATE_DRAINING
        return STATE_OK

    @property
    def accepting(self) -> bool:
        """Eligible for NEW placements: healthy and not mid-retire."""
        return self.healthy and not self.draining

    @property
    def engine(self) -> Any:
        return self.supervisor.engine


class ServingCluster:
    """Front N supervised replicas behind the single-engine serving API
    (module docstring). ``engine_factory`` is the SAME factory a lone
    `EngineSupervisor` takes — it must forward ``journal=`` / ``metrics=`` /
    ``tracer=`` into `ServingEngine` and reuse one module/params pair, so
    every replica (and every rebuild) shares the process jit cache::

        cluster = ServingCluster(
            lambda **kw: ServingEngine(module, params, max_concurrency=4,
                                       prefix_cache=True, **kw),
            workdir, replicas=2,
            supervisor_config=SupervisorConfig(max_restarts=1),
        )
        rid = cluster.submit(prompt).request_id
        while cluster.has_work:
            for out in cluster.step(): ...

    Replica ``i`` journals to ``workdir/replica{i}/requests.journal``; a
    cluster rebuilt over a populated workdir auto-resumes every replica
    (the supervisors recover at construction) and re-announces the recovered
    streams under fresh cluster ids.

    ``tracers`` / ``headroom_fns`` are optional per-replica sequences
    forwarded to each supervisor (tests and the chaos harness drive health
    transitions through them); ``clock`` is injectable for determinism.
    """

    def __init__(
        self,
        engine_factory: Callable[..., Any],
        workdir: str | Path,
        *,
        replicas: int = 2,
        config: ClusterConfig | None = None,
        supervisor_config: SupervisorConfig | None = None,
        tracers: Any = None,
        headroom_fns: Any = None,
        clock: Callable[[], float] = time.perf_counter,
    ):
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        self.config = config if config is not None else ClusterConfig()
        roles = self.config.roles
        if roles is not None and len(roles) != replicas:
            raise ValueError(f"roles has {len(roles)} entries for "
                             f"{replicas} replicas")
        self.workdir = Path(workdir)
        self._clock = clock
        self._factory = engine_factory
        self._supervisor_config = supervisor_config
        self._next_rid = 0
        self._rr = 0  # round-robin cursor
        # cluster rid <-> (replica index, engine rid); a migrated request
        # keeps its cluster rid across placements
        self._routes: dict[int, tuple[int, int]] = {}
        self._by_engine: dict[tuple[int, int], int] = {}
        self._delivered: set[int] = set()
        # cluster-id outputs minted outside step() (replace_replica's
        # migration deliverables) — drained by the next step()
        self._pending_outputs: list[RequestOutput] = []
        self.migrations = 0  # replica deaths migrated
        self.migrated_requests = 0
        self.retired_replicas = 0
        self.replaced_replicas = 0
        self._routed = {POLICY_PREFIX: 0, POLICY_ROUND_ROBIN: 0}
        self._route_match_tokens = 0
        # a FleetAutoscaler attaches itself here (serving/autoscaler.py);
        # step() then runs one control evaluation per cluster step
        self.autoscaler: Any = None
        self._next_replica_index = 0
        self.replicas: list[ReplicaHandle] = []
        for i in range(replicas):
            self.add_replica(
                role=roles[i] if roles is not None else ROLE_MIXED,
                tracer=tracers[i] if tracers is not None else None,
                headroom_fn=(headroom_fns[i] if headroom_fns is not None
                             else None),
            )
        self.metrics = _ClusterMetricsView(self)

    # -------------------------------------------------------- elastic fleet
    def add_replica(self, role: str = ROLE_MIXED, *, tracer: Any = None,
                    headroom_fn: Callable[[], dict[str, Any]] | None = None,
                    ) -> ReplicaHandle:
        """Spawn one fresh replica through the construction-time factory into
        ``workdir/replica<i>/`` under the next never-reused index. The
        ``cluster.replica_spawn`` fault point fires BEFORE any filesystem
        effect, so a failed spawn leaves no debris and is safely retried
        (`serving/autoscaler.py`'s seeded RetryPolicy). Same module/params
        through the factory means `_SHARED_JITS` makes the spawn skip
        recompilation — the cheap-scale-event contract."""
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        faults.fault_point(faults.SCOPE_REPLICA_SPAWN)
        index = self._next_replica_index
        rep_dir = self.workdir / f"replica{index}"
        rep_dir.mkdir(parents=True, exist_ok=True)
        metrics = ServingMetrics()
        sup = EngineSupervisor(
            self._factory,
            rep_dir / "requests.journal",
            config=self._supervisor_config,
            metrics=metrics,
            tracer=tracer,
            headroom_fn=headroom_fn,
        )
        rep = ReplicaHandle(index, role, sup,
                            rep_dir / "requests.journal", metrics)
        self._next_replica_index += 1
        self.replicas.append(rep)
        return rep

    def retire_replica(self, index: int, *, force: bool = False
                       ) -> list[RequestOutput]:
        """Begin (or, with ``force``, complete) the drain-and-retire
        lifecycle on one replica. DRAINING excludes it from new placements
        (`_eligible`) while `step()` keeps stepping its in-flight work; once
        idle, `step()` finalizes it to RETIRED — journal closed, fsck-clean,
        zero requests lost. ``force=True`` ends the grace period NOW: the
        remaining in-flight work journal-migrates to peers (the PR-13
        machinery, streams bit-exact) and the replica retires immediately.
        Returns any cluster-id outputs the forced migration delivered."""
        rep = self.replicas[index]
        if rep.retired:
            return []
        sup = rep.supervisor
        if sup.unhealthy:
            # already failed loudly: the journal is closed and the backlog
            # was migrated (step's intercept) or accounted — just finalize
            self._finalize_retire(rep)
            return []
        rep.draining = True
        sup.begin_drain()
        if not force:
            return []
        if self.config.migrate:
            produced = rep.engine.abort_all(reason=_UNHEALTHY_REASON)
            produced = self._migrate(rep, produced)
            rep.migrated = True
        else:
            produced = rep.engine.abort_all()
        outputs = self._translate(rep.index, produced)
        self._finalize_retire(rep)
        return outputs

    def replace_replica(self, index: int) -> ReplicaHandle:
        """Replace a budget-exhausted (DEAD) replica: spawn a successor under
        a fresh index, run the dead-journal migration into the fleet (unless
        `step()`'s death intercept already did), and retire the dead handle.
        Raises ``ValueError`` on a live or retired replica; spawn failures
        (the ``cluster.replica_spawn`` fault point) propagate BEFORE any
        state changes, so the caller may retry. Returns the successor."""
        dead = self.replicas[index]
        if dead.retired:
            raise ValueError(f"replica {index} is already retired")
        if not dead.supervisor.unhealthy:
            raise ValueError(f"replica {index} is alive — use retire_replica")
        successor = self.add_replica(role=dead.role)
        if self.config.migrate and not dead.migrated:
            # a replica that died outside step() (or with migrate deferred)
            # still owes its backlog to the fleet; deliverables surface on
            # the next step() via the successor's pending outputs path
            self._pending_outputs.extend(
                self._translate(dead.index, self._migrate(dead, [])))
            dead.migrated = True
        self._finalize_retire(dead, emit=False)
        self.replaced_replicas += 1
        tracer = getattr(successor.engine, "tracer", None)
        if tracer is not None and tracer.enabled:
            tracer.emit(EV_SCALE, None, action="replace",
                        replica=successor.index, replaced=dead.index,
                        live=self.live_replicas)
        return successor

    def _finalize_retire(self, rep: ReplicaHandle, *, emit: bool = True
                         ) -> None:
        """DRAINING/DEAD -> RETIRED: close the journal (idempotent — a
        fail-loud supervisor already closed it), keep the handle (stable
        indices), stop its telemetry emission (`replica_samples` skips
        retired handles)."""
        if rep.retired:
            return
        tracer = getattr(rep.engine, "tracer", None)
        try:
            rep.supervisor.close()
        except Exception:
            pass
        rep.draining = False
        rep.retired = True
        self.retired_replicas += 1
        if emit and tracer is not None and tracer.enabled:
            tracer.emit(EV_SCALE, None, action="retire", replica=rep.index,
                        live=self.live_replicas)

    @property
    def live_replicas(self) -> int:
        """Replicas not yet RETIRED (OK + DRAINING + DEAD)."""
        return sum(1 for rep in self.replicas if not rep.retired)

    # ------------------------------------------------------------------ ids
    @property
    def n_replicas(self) -> int:
        """Total handles ever created (retired included — stable indices)."""
        return len(self.replicas)

    def _cluster_rid_for(self, replica: int, engine_rid: int) -> int:
        """The cluster id for an engine-level id, minted on first sight (a
        supervisor's construction-time auto-resume delivers outputs for
        requests this cluster never submitted — they get fresh ids)."""
        key = (replica, engine_rid)
        rid = self._by_engine.get(key)
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
            self._by_engine[key] = rid
            self._routes[rid] = key
        return rid

    def _bind(self, cluster_rid: int, replica: int, engine_rid: int) -> None:
        self._routes[cluster_rid] = (replica, engine_rid)
        self._by_engine[(replica, engine_rid)] = cluster_rid

    def placement(self, cluster_rid: int) -> tuple[int, int] | None:
        """(replica index, engine rid) currently serving a cluster id."""
        return self._routes.get(cluster_rid)

    # -------------------------------------------------------------- routing
    def _eligible(self, request: Request, *, resumed: bool
                  ) -> list[ReplicaHandle]:
        """Health- and role-gated candidates: never an unhealthy replica;
        never a replica whose brownout would shed this priority (route
        around the hot replica instead of bouncing off its gate). A replica
        whose last step ran long (`heartbeat`'s ``stalled``) is only
        AVOIDED — when every live replica looks stalled (e.g. a compiling
        cold start) the work still places rather than bouncing, and the
        supervisor's own stall detector arbitrates from there. Fresh
        admissions need prefill capability, continuations prefer decode
        capability (falling back rather than stranding work)."""
        alive: list[ReplicaHandle] = []
        calm: list[ReplicaHandle] = []
        for rep in self.replicas:
            sup = rep.supervisor
            if rep.retired or rep.draining or sup.unhealthy:
                continue
            if sup.brownout_level > 0 and request.priority < sup.brownout_level:
                continue
            alive.append(rep)
            if not sup.heartbeat()["stalled"]:
                calm.append(rep)
        healthy = calm if calm else alive
        want = ROLE_DECODE if resumed else ROLE_PREFILL
        preferred = [r for r in healthy if r.role in (ROLE_MIXED, want)]
        return preferred if preferred else healthy

    def _rank(self, request: Request, candidates: list[ReplicaHandle],
              *, resumed: bool) -> list[ReplicaHandle]:
        """Preference order under the configured policy. Prefix placement
        probes each candidate's radix trie with the cheap non-pinning
        `PrefixCache.match_len` and prefers the longest holder; load (queue
        depth + active slots) breaks ties and is the whole story for
        round-robin's rotation."""
        if not candidates:
            return []
        if self.config.policy == POLICY_ROUND_ROBIN or resumed:
            # continuations never ride the cached-prefix program
            # (scheduler._run_key), so trie affinity buys them nothing:
            # spread them by load like the baseline does
            start = self._rr
            self._rr += 1
            rotated = [candidates[(start + j) % len(candidates)]
                       for j in range(len(candidates))]
            self._last_rank = {rep.index: {"match_len": 0} for rep in rotated}
            return rotated
        scored = []
        self._last_rank = {}
        for rep in candidates:
            cache = getattr(rep.engine, "prefix_cache", None)
            match = (cache.match_len(request.prompt)
                     if cache is not None and request.cache_prefix else 0)
            load = (rep.engine.scheduler.queue_depth
                    + rep.engine.active_slots)
            self._last_rank[rep.index] = {"match_len": match, "load": load}
            scored.append((-match, load, rep.index, rep))
        scored.sort(key=lambda t: t[:3])
        if scored and -scored[0][0] > 0:
            self._route_match_tokens += -scored[0][0]
        return [t[3] for t in scored]

    # -------------------------------------------------------------- serving
    def submit(self, request: Request | Any,
               params: SamplingParams | None = None) -> SubmitResult:
        """Route-and-admit. Returns a `SubmitResult` carrying a CLUSTER
        request id; rejections carry the most specific reason the router
        saw (every replica dead -> ``unhealthy``; all shedding ->
        ``overload``; otherwise the last replica's own verdict)."""
        if not isinstance(request, Request):
            request = Request(prompt=list(request),
                              params=params or SamplingParams())
        return self._place(request, resumed=False)

    def _place(self, request: Request, *, resumed: bool) -> SubmitResult:
        candidates = self._rank(request,
                                self._eligible(request, resumed=resumed),
                                resumed=resumed)
        if not candidates:
            if all(rep.supervisor.unhealthy for rep in self.replicas):
                return SubmitResult(False, None, REJECT_UNHEALTHY,
                                    "every replica is unhealthy")
            return SubmitResult(False, None, REJECT_OVERLOAD,
                                "every healthy replica is shedding load")
        last: SubmitResult | None = None
        for rank, rep in enumerate(candidates):
            result = rep.supervisor.submit(request)
            if result.accepted:
                rid = self._next_rid
                self._next_rid += 1
                self._bind(rid, rep.index, result.request_id)
                self._routed[POLICY_ROUND_ROBIN if resumed
                             else self.config.policy] += 1
                tracer = getattr(rep.engine, "tracer", None)
                if tracer is not None and tracer.enabled:
                    # routing forensics: how many replicas were in the race,
                    # the chosen one's trie match, and WHY it won — "fallback:"
                    # prefixes the reason when earlier-ranked replicas
                    # rejected and placement fell through to this one
                    info = getattr(self, "_last_rank", {}).get(rep.index, {})
                    match_len = int(info.get("match_len", 0))
                    if resumed:
                        reason = "resumed"
                    elif self.config.policy == POLICY_ROUND_ROBIN:
                        reason = "round_robin"
                    elif match_len > 0:
                        reason = "prefix_match"
                    else:
                        reason = "load_tiebreak"
                    if rank > 0:
                        reason = f"fallback:{reason}"
                    tracer.emit(EV_ROUTE, result.request_id,
                                replica=rep.index,
                                policy=self.config.policy,
                                resumed=resumed,
                                candidates=len(candidates),
                                match_len=match_len,
                                reason=reason)
                return SubmitResult(True, rid)
            last = result
        return SubmitResult(False, None, last.reason, last.detail)

    def _translate(self, replica: int, outputs: list[RequestOutput]
                   ) -> list[RequestOutput]:
        """Engine-id outputs -> cluster-id outputs, delivery recorded."""
        out = []
        for o in outputs:
            rid = self._cluster_rid_for(replica, o.request_id)
            self._delivered.add(rid)
            out.append(dataclasses.replace(o, request_id=rid))
        return out

    def step(self) -> list[RequestOutput]:
        """One cluster step: step every healthy replica with work (DRAINING
        included — drain-aware stepping is what lets in-flight work finish),
        translate ids, and — when a replica's restart budget just exhausted —
        migrate its backlog before returning, so the caller never sees a
        ``rejected:unhealthy`` for work another replica can finish. A
        DRAINING replica finalizes to RETIRED the moment it goes idle (or
        dies mid-drain — its backlog just migrated, nothing left to wait
        for). An attached `FleetAutoscaler` then runs one control
        evaluation."""
        outputs: list[RequestOutput] = self._pending_outputs
        self._pending_outputs = []
        for rep in self.replicas:
            if rep.retired:
                continue
            sup = rep.supervisor
            if not sup.unhealthy and sup.has_work:
                try:
                    produced = sup.step()
                except EngineUnhealthyError:
                    produced = []
                if sup.unhealthy and self.config.migrate:
                    produced = self._migrate(rep, produced)
                    rep.migrated = True
                outputs.extend(self._translate(rep.index, produced))
            if rep.draining and (sup.unhealthy or not sup.has_work):
                self._finalize_retire(rep)
        if self.autoscaler is not None:
            outputs.extend(self.autoscaler.evaluate())
        return outputs

    @property
    def has_work(self) -> bool:
        return bool(self._pending_outputs) or any(
            rep.healthy and rep.supervisor.has_work
            for rep in self.replicas)

    def drain(self, max_steps: int | None = None) -> list[RequestOutput]:
        """Graceful cluster shutdown: stop admissions everywhere, then step
        (migrating along the way) until idle, bounded by ``max_steps``."""
        for rep in self.replicas:
            if rep.healthy:
                rep.engine.begin_drain()
        outputs: list[RequestOutput] = []
        steps = 0
        try:
            while self.has_work:
                outputs.extend(self.step())
                steps += 1
                if max_steps is not None and steps >= max_steps and self.has_work:
                    for rep in self.replicas:
                        if rep.healthy:
                            outputs.extend(self._translate(
                                rep.index, rep.engine.abort_all()))
                    break
        finally:
            for rep in self.replicas:
                if rep.healthy:
                    rep.engine.end_drain()
        return outputs

    def close(self) -> None:
        for rep in self.replicas:
            rep.supervisor.close()

    # ------------------------------------------------------------ migration
    def _migrate(self, dead: ReplicaHandle, produced: list[RequestOutput]
                 ) -> list[RequestOutput]:
        """Journal-backed migration off a failed replica (module docstring).

        ``produced`` is the dying step's output — the supervisor's fail-loud
        accounting, where every in-flight request is ``rejected:unhealthy``
        with its partial stream. Those are superseded here: the journal is
        scanned, genuinely-finished requests are deduped (their terminals
        were already delivered, or are delivered now from the journal), and
        everything else is resubmitted to a healthy replica with its emitted
        tokens as ``resume_tokens``. Only a request NO healthy replica will
        accept falls back to the fail-loud output — zero requests are ever
        silently dropped."""
        self.migrations += 1
        try:
            scan = RequestJournal.scan(dead.journal_path)
        except Exception:
            # no readable journal -> nothing to improve on: deliver the
            # supervisor's own fail-loud accounting unchanged
            return produced
        deliver = [o for o in produced
                   if o.finish_reason != _UNHEALTHY_REASON]
        fallback = {o.request_id: o for o in produced
                    if o.finish_reason == _UNHEALTHY_REASON}
        # a FINISH whose terminal never reached the caller (e.g. journaled
        # by a restart's resume replay and lost with the next failure) is
        # completed work — deliver it from the journal, don't re-decode it
        now = self._clock()
        for erid, (reason, toks) in scan.finishes.items():
            if reason == _UNHEALTHY_REASON:
                continue
            rid = self._by_engine.get((dead.index, erid))
            if rid is not None and rid in self._delivered:
                continue
            sub = scan.submits.get(erid, {})
            deliver.append(RequestOutput(
                request_id=erid,
                prompt_len=len(sub.get("prompt", ())),
                tokens=list(toks), finish_reason=reason, finish_time=now))
        # migration candidates: every accepted request without a genuine
        # terminal — admitted ones first (admission order), then queued
        # (submit order), exactly the resume replay order
        candidates = [erid for erid in scan.admit_order
                      if scan.finishes.get(erid, (_UNHEALTHY_REASON,))[0]
                      == _UNHEALTHY_REASON]
        seen = set(candidates)
        candidates += [erid for erid in scan.submits
                       if erid not in seen
                       and scan.finishes.get(erid, (_UNHEALTHY_REASON,))[0]
                       == _UNHEALTHY_REASON]
        for erid in candidates:
            out = self._migrate_one(dead, scan, erid, fallback.get(erid))
            if out is not None:
                deliver.append(out)
        return deliver

    def _migrate_one(self, dead: ReplicaHandle, scan: Any, erid: int,
                     fallback: RequestOutput | None) -> RequestOutput | None:
        """Rebuild one request from its journal identity and place it on a
        healthy replica. Returns an output to deliver NOW (stream already
        complete, or nobody would take it); None when the request is live
        again elsewhere."""
        sub = scan.submits[erid]
        prompt = [int(t) for t in sub["prompt"]]
        sp = SamplingParams(
            temperature=float(sub["params"]["temperature"]),
            top_k=sub["params"]["top_k"],
            seed=int(sub["params"]["seed"]),
            max_new_tokens=int(sub["params"]["max_new_tokens"]),
        )
        if erid in scan.finishes:  # abort record carries the full stream
            toks = list(scan.finishes[erid][1])
        else:
            toks = list(scan.tokens.get(erid, []))
        admitted = erid in scan.admit_order
        cluster_rid = self._cluster_rid_for(dead.index, erid)
        # mirror resume(): a stream that already satisfied its budget or
        # emitted EOS completes here instead of being re-admitted
        target = next((r for r in self.replicas if r.accepting), None)
        done_reason = None
        eos = target.engine.eos_token_id if target is not None else None
        budget = sp.max_new_tokens
        if target is not None:
            budget = min(budget, target.engine.max_len - len(prompt))
        if eos is not None and eos in toks:
            toks = toks[: toks.index(eos) + 1]
            done_reason = FINISH_EOS
        elif len(toks) >= budget > 0:
            toks = toks[:budget]
            done_reason = FINISH_LENGTH
        if done_reason is not None:
            self._delivered.add(cluster_rid)
            return RequestOutput(request_id=erid, prompt_len=len(prompt),
                                 tokens=toks, finish_reason=done_reason,
                                 finish_time=self._clock())
        keep = len(toks)
        if target is not None:
            # the continuation must fit a prompt bucket; rewind past the
            # largest admissible prefix and re-decode the rest (seeded, so
            # the final stream is unchanged — same rule as resume())
            keep = max(0, min(keep,
                              target.engine.scheduler.max_prompt_len
                              - len(prompt)))
        request = Request(
            prompt=prompt, params=sp,
            # an admitted request's queue-wait deadline was consumed before
            # the replica died; keeping it would instantly expire the stream
            deadline_s=None if admitted else sub.get("deadline_s"),
            cache_prefix=bool(sub.get("cache_prefix", True)),
            priority=int(sub.get("priority", 0)),
            tenant=str(sub.get("tenant", "")),
            resume_tokens=toks[:keep],
        )
        result = self._place(request, resumed=True)
        if not result.accepted:
            # nobody would take it: account for it loudly, never drop it
            self._delivered.add(cluster_rid)
            if fallback is not None:
                return fallback
            return RequestOutput(
                request_id=erid, prompt_len=len(prompt), tokens=toks,
                finish_reason=_UNHEALTHY_REASON, finish_time=self._clock())
        # _place minted a fresh cluster id for the new engine id; fold it
        # back onto the request's original cluster id
        new_key = self._routes.pop(result.request_id)
        self._next_rid -= 1 if result.request_id == self._next_rid - 1 else 0
        self._bind(cluster_rid, *new_key)
        self.migrated_requests += 1
        rep = self.replicas[new_key[0]]
        # make the TARGET journal self-contained for the next crash: the
        # engine write-ahead logged the submit, but the resumed prefix only
        # exists here — same idiom as resume()'s foreign-journal copy
        if request.resume_tokens and rep.engine.journal is not None:
            rep.engine.journal.log_progress(
                new_key[1], list(request.resume_tokens),
                len(request.resume_tokens))
        tracer = getattr(rep.engine, "tracer", None)
        if tracer is not None and tracer.enabled:
            tracer.emit(EV_MIGRATE, new_key[1], from_replica=dead.index,
                        to_replica=rep.index,
                        resumed=len(request.resume_tokens))
        return None

    # ----------------------------------------------------------- telemetry
    def heartbeat(self) -> dict[str, Any]:
        """Cluster health roll-up: each replica's supervisor heartbeat plus
        its index/role, and the healthy count the router admits against."""
        rows = []
        for rep in self.replicas:
            if rep.retired:
                continue
            hb = rep.supervisor.heartbeat()
            hb["replica"] = rep.index
            hb["role"] = rep.role
            hb["state"] = rep.state
            rows.append(hb)
        return {
            "replicas": rows,
            "healthy": sum(1 for rep in self.replicas if rep.healthy),
            "unhealthy": sum(1 for rep in self.replicas
                             if not rep.retired and not rep.healthy),
            "draining": sum(1 for rep in self.replicas
                            if not rep.retired and rep.draining),
            "retired": self.retired_replicas,
            "migrations": self.migrations,
        }

    def router_stats(self) -> dict[str, Any]:
        """The ``cluster/*`` gauges (`ServingMetrics.snapshot` shape)."""
        return {
            "cluster/replicas": self.live_replicas,
            "cluster/healthy_replicas": sum(
                1 for rep in self.replicas if rep.healthy),
            "cluster/draining_replicas": sum(
                1 for rep in self.replicas
                if not rep.retired and rep.draining),
            "cluster/retired_replicas": self.retired_replicas,
            "cluster/replaced_replicas": self.replaced_replicas,
            "cluster/migrations": self.migrations,
            "cluster/migrated_requests": self.migrated_requests,
            "cluster/routed_prefix": self._routed[POLICY_PREFIX],
            "cluster/routed_round_robin": self._routed[POLICY_ROUND_ROBIN],
            "cluster/route_match_tokens": self._route_match_tokens,
        }

    def memory_stats(self) -> dict[str, Any]:
        """Additive roll-up of every healthy replica's `memory_stats` (the
        telemetry exporter namespaces it under ``serving/mem/``; per-replica
        detail rides under ``replica<i>/serving/mem/``)."""
        totals: dict[str, Any] = {}
        for rep in self.replicas:
            if not rep.healthy:
                continue
            for k, v in rep.engine.memory_stats().items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                totals[k] = totals.get(k, 0) + v
        return totals

    # headroom keys where a sum is meaningless: the best replica's slot wait
    # is the cluster's admission wait (router sends work there), and the
    # slowest replica to exhaust bounds the cluster's runway
    _HEADROOM_MIN = frozenset({"est_slot_free_s"})
    _HEADROOM_MAX = frozenset({"seconds_to_exhaustion"})

    def capacity_headroom(self) -> dict[str, Any]:
        """Cluster-level headroom: additive gauges sum across healthy
        replicas; ``est_slot_free_s`` takes the min (the router places work
        on the calmest replica) and ``seconds_to_exhaustion`` the max."""
        totals: dict[str, Any] = {}
        for rep in self.replicas:
            # DRAINING capacity is not admission capacity: a retiring
            # replica takes no new placements, so its free slots must not
            # relieve the fleet's predicted-TTFT admission gate
            if not rep.accepting:
                continue
            for k, v in rep.engine.capacity_headroom().items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                if k in self._HEADROOM_MIN:
                    totals[k] = v if k not in totals else min(totals[k], v)
                elif k in self._HEADROOM_MAX:
                    totals[k] = v if k not in totals else max(totals[k], v)
                else:
                    totals[k] = totals.get(k, 0) + v
        return totals

    def replica_samples(self) -> list[tuple[int, dict[str, Any]]]:
        """Per-replica ``(stable index, gauge dict)`` pairs for the telemetry
        exporter's ``replica<i>/`` namespace (`TelemetryExporter.sample`):
        each replica's metrics snapshot, memory/headroom gauges, and its
        cluster-view health (`cluster/healthy`, state, brownout level, role).
        RETIRED replicas are skipped — they stop emitting rather than
        renumbering, so every live series keeps its index across
        retire/replace (the namespace-stability contract)."""
        samples: list[tuple[int, dict[str, Any]]] = []
        for rep in self.replicas:
            if rep.retired:
                continue
            gauges: dict[str, Any] = dict(rep.metrics.snapshot())
            if rep.healthy:
                for k, v in rep.engine.memory_stats().items():
                    gauges[f"serving/mem/{k}"] = v
                for k, v in rep.engine.capacity_headroom().items():
                    gauges[f"serving/headroom/{k}"] = v
                class_gauges = getattr(rep.engine.scheduler, "class_gauges",
                                       None)
                if callable(class_gauges):
                    gauges.update(class_gauges())
            hb = rep.supervisor.heartbeat()
            gauges["cluster/healthy"] = int(rep.healthy)
            gauges["cluster/draining"] = int(rep.draining)
            gauges["cluster/state"] = rep.state
            gauges["cluster/brownout_level"] = hb["brownout_level"]
            gauges["cluster/restarts"] = hb["restarts"]
            gauges["cluster/role"] = rep.role
            samples.append((rep.index, gauges))
        return samples
