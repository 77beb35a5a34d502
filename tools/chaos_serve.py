"""Chaos replay: the bench_serving Poisson trace through `ServingEngine` with
deterministic faults injected, asserting ZERO lost requests.

"Lost" is the one unforgivable serving failure: a request that was accepted
but never produced a terminal output. Under this harness every submitted
request must end in exactly one of: finished (``eos``/``length``), watchdog
error (``error``, after one re-prefill retry), deadline expiry
(``rejected:deadline``), or a structural rejection — whatever faults fire.

Faults injected (seeded via `reliability.FaultInjector`, so a failing run
replays bit-identically):
  - NaN-poisoned decode logits on slot 0 every ``CHAOS_POISON_EVERY`` steps
    (exercising the watchdog quarantine/retry/FINISH_ERROR chain);
  - a tight queue-wait deadline on every ``CHAOS_DEADLINE_EVERY``-th request
    (exercising REJECT_DEADLINE queue expiry under load).

The replay runs with the PREFIX CACHE enabled by default (``CHAOS_PREFIX=1``,
`serving/prefix_cache.py`) over a deliberately tiny block pool
(``CHAOS_PREFIX_BLOCKS``, default 6) so LRU eviction fires mid-chaos, and
every third request duplicates an earlier prompt so donation -> hit reuse
actually happens under quarantine churn. Beyond zero-lost, the harness then
asserts ZERO PARITY DRIFT: every request that finished ``eos``/``length`` —
cached, evicted, or watchdog-re-prefilled — must match its solo
``generate`` token-for-token (``CHAOS_VERIFY_PARITY=0`` skips the solo
reference pass when you only want the lost-request invariant).

Prints ONE JSON line: {"metric": "chaos_serve_lost_requests", "value": 0, ...}.

**Crash scenarios** (``CHAOS_SCENARIO=sigterm|sigkill``): instead of the
fault-injection replay, spawn a CHILD serving process that journals every
request (`serving/journal.py`), wait until the journal proves it is
mid-decode (>= 1 FIRST_TOKEN on disk, not all finished), and kill it —
SIGTERM (the child's `ServingPreemptionHandler` drains inside a short grace
window, snapshots the rest, exits 143) or SIGKILL (no handler runs; the
fsync'd journal is the only survivor). The parent then builds a fresh engine,
`resume`s from the snapshot (sigterm) or the journal (sigkill), runs the
replayed work to completion, and asserts BOTH invariants across the crash:
zero lost accepted requests, and zero token drift vs solo generate for every
cleanly finished stream — including the ones that resumed mid-stream. The
child blocks SIGTERM around each ``engine.step()`` and unblocks between
steps, so the handler's drain never re-enters a half-completed step.

Run: JAX_PLATFORMS=cpu python tools/chaos_serve.py
Env knobs:
  CHAOS_REQUESTS        trace length (default 24)
  CHAOS_CONCURRENCY     engine slots (default 4)
  CHAOS_RATE            Poisson arrival rate, req/s (default 500: saturating)
  CHAOS_SEED            trace + injector rng seed (default 0)
  CHAOS_POISON_EVERY    poison slot 0 every N decode steps (default 5; 0 = off)
  CHAOS_DEADLINE_EVERY  every N-th request gets a deadline (default 6; 0 = off)
  CHAOS_DEADLINE_S      that deadline, seconds of queue wait (default 0.0)
  CHAOS_DEPTH           engine pipeline_depth (default 2: the replay must prove
                        the zero-lost guarantee survives LAGGED retirement —
                        set 1 to bisect a failure against synchronous dispatch)
  CHAOS_PREFIX          1 (default) serves through the prefix cache; 0 = off
  CHAOS_PREFIX_BLOCKS   with the prefix cache on, the engine's block pool
                        holds one full context plus this many blocks
                        (default 6: forces eviction). Block-gated admission,
                        zero-copy prefix aliasing, and block reclaim
                        (docs/serving.md "Paged KV") all run under the chaos,
                        with the zero-lost / zero-drift bar PLUS full pool
                        reclamation — after the drain (and, with the trie on,
                        after evicting every resident block) ``blocks_free``
                        must return to its initial value; a single leaked or
                        double-freed block fails the replay. The crash
                        scenarios too (the resumed engine re-prefills into
                        fresh blocks)
  CHAOS_SYNC_TOKENS     engine ``tokens_per_sync`` (default 1): k > 1 runs k
                        decode iterations inside one jitted lax.scan per
                        dispatch (docs/serving.md "Fused paged decode"), so
                        quarantine, deadline expiry, and the crash scenarios
                        all land MID-SCAN — the zero-lost / zero-drift bar is
                        unchanged, and a crash abandons up to k un-journaled
                        tokens per slot that resume must replay exactly
  CHAOS_SPEC            engine ``speculation`` draft depth (default 0 = off):
                        k >= 1 serves the whole replay through SPECULATIVE
                        decoding (docs/serving.md "Speculative decoding") —
                        every decode dispatch verifies k drafter-proposed
                        tokens, so quarantine, deadline expiry, and the crash
                        scenarios all land MID-SPECULATION. The zero-lost /
                        zero-drift bar is unchanged (greedy speculation is
                        bit-exact by construction), and a crash abandons up
                        to k+1 un-journaled accepted tokens per slot that
                        resume must replay exactly. Mutually exclusive with
                        CHAOS_SYNC_TOKENS > 1
  CHAOS_QUANT           "int8" serves the crash scenario over int8 KV-cache
                        storage (docs/serving.md "Quantized serving"): the
                        parity oracle becomes the quantized solo generate,
                        and resume must be crash-exact through
                        re-quantization. Default "" = fp cache
  CHAOS_VERIFY_PARITY   1 (default) checks finished outputs against solo
                        generate; 0 skips the reference pass
  CHAOS_MESH            "DxM" (e.g. "2x2") replays through a mesh-sharded
                        engine (`ServingEngine(mesh=(D, M))`): zero-lost AND
                        zero-drift must hold with params tensor-parallel and
                        the slot pool sharded — the watchdog quarantine,
                        deadline expiry, and prefix reuse all ride over
                        collectives. On CPU the D*M virtual devices are
                        forced. Default: unsharded (single device)
  CHAOS_SCENARIO        "sigterm" or "sigkill" runs the kill-mid-decode
                        crash scenario instead of the fault-injection replay;
                        "stream_kill" runs the STREAMING crash scenario
                        (`serving/frontend.py`, docs/serving.md "Front
                        door"): the parent tails the child's journal as a
                        streaming consumer, SIGKILLs the child mid-stream,
                        resumes a fresh engine and re-attaches every stream
                        at its exact pre-crash frontier with
                        `ServingFrontend.resume_stream` — asserting every
                        resumed stream byte-identical to solo generate with
                        no duplicated events (works under CHAOS_SPEC /
                        CHAOS_SYNC_TOKENS too);
                        "hang" or "storm" runs the SELF-HEALING scenario
                        (`serving/supervisor.py`): a wedged mid-decode
                        dispatch / a NaN quarantine storm that the engine
                        SUPERVISOR — not this harness — must detect and
                        recover via automatic journal-backed restart, with
                        zero lost requests and zero token drift;
                        "hibernate_kill" runs the HOST-TIER scenario
                        (`serving/kv_tier.py`): SIGKILL a tier-on engine
                        while requests are hibernated and blocks spilled to
                        volatile host buffers, resume from the journal —
                        zero lost, zero drift, host-tier gauges back to
                        steady state, `journal_fsck` exit 0;
                        "replica_kill" runs the MULTI-REPLICA scenario
                        (`serving/cluster.py`): a `ServingCluster` of
                        CHAOS_REPLICAS zero-restart-budget replicas takes a
                        deterministic device loss, the hit replica dies, and
                        the CLUSTER must migrate its journaled backlog onto
                        the survivors with resume_tokens — zero lost, zero
                        drift, clean `journal_fsck --all` over the workdir
                        "surge_drain" runs the ELASTIC-FLEET scenario
                        (`serving/autoscaler.py`): a one-replica cluster
                        with a `FleetAutoscaler` takes a 4x load step, the
                        autoscaler scales up, a simulated SIGKILL lands on
                        the original replica MID-DRAIN, and the load drop
                        drains the fleet back to the floor — >= 1 scale-up,
                        >= 1 retire, zero lost, zero drift, clean
                        `journal_fsck --all`, scaling never thrash-frozen
  CHAOS_REPLICAS        replica_kill scenario: cluster size (default 2)
  CHAOS_MAX_REPLICAS    surge_drain scenario: autoscaler ceiling (default 3)
  CHAOS_WARMUP          surge_drain scenario: baseline requests before the
                        load step (default 4 — sizes the TTFT target off
                        the measured idle prediction)
  CHAOS_WORKDIR         replica_kill / surge_drain scenarios: cluster
                        workdir holding each replica's journal (default: a
                        fresh temp dir)
  CHAOS_RESTART_BUDGET  hang/storm scenarios: the supervisor's max_restarts
                        (default 3). 0 asserts the fail-fast contract
                        instead: first failure goes straight to unhealthy,
                        every in-flight request accounted rejected:unhealthy
  CHAOS_STALL_TIMEOUT   hang scenario: supervisor stall_timeout_s (default
                        0.15 — well under the injected 0.5 s hang)
  CHAOS_GRACE           sigterm scenario: the child handler's drain grace
                        window, seconds (default 0.05 — small on purpose, so
                        work REMAINS and the snapshot path is exercised)
  CHAOS_TRACE           path: attach a `serving.Tracer` to the replay engine
                        (the RESUMING engine under a crash scenario), export
                        its Perfetto-loadable trace-event JSON here, and
                        assert the stream passes the trace invariants —
                        exactly one terminal per request, balanced
                        dispatch/fetch — even under quarantine/expiry/crash
                        churn (summarize with tools/trace_report.py).
                        Default: tracing off (the zero-overhead NULL_TRACER)

Every replayed request also carries an `SLOSpec` (class "deadline" for the
tight-deadline victims, "plain" otherwise; no latency bounds — attainment
under chaos means "finished cleanly"), so the summary detail carries a
goodput row: watchdog FINISH_ERRORs and deadline expiries surface as
per-class attainment misses (`docs/observability.md`).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_serving import BUCKETS, _trace  # noqa: E402


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _pool(module, prefix_cache: bool, prefix_blocks: int):
    """The replay engine's ``paged_kv=``: with the prefix cache on, a pool of
    one full context plus ``prefix_blocks`` blocks, small on purpose so trie
    eviction fires mid-chaos; the engine's default without it."""
    from accelerate_tpu.serving import PagedKVConfig

    if not prefix_cache:
        return True
    per_slot = int(module.config.n_positions) // PagedKVConfig().block_tokens
    return PagedKVConfig(num_blocks=per_slot + prefix_blocks)


def _assert_steady_state(engine) -> dict:
    """The telemetry gauges (`engine.memory_stats` / `capacity_headroom`,
    serving/telemetry.py) must report a fully clean engine once the chaos
    drains: no leaked slots or queued work, zero stuck block-pool pins,
    block accounting consistent, and admission headroom restored to full
    capacity. A leak surviving the drain is an engine bug the chaos
    uncovered — same bar as zero-lost. Returns the gauges for the summary."""
    mem = engine.memory_stats()
    head = engine.capacity_headroom()
    assert (mem["slots_active"] == 0
            and mem["slots_free"] == engine.max_concurrency), \
        f"leaked slots after drain: {mem}"
    assert mem["queue_depth"] == 0 and mem["inflight_dispatches"] == 0, \
        f"work left after drain: {mem}"
    assert mem["block_pool/blocks_pinned"] == 0, \
        f"stuck block pins after drain: {mem}"
    assert mem["block_pool/blocks_private"] == 0, \
        f"retired slots still hold private blocks: {mem}"
    assert (mem["block_pool/blocks_free"]
            + mem["block_pool/blocks_resident"]
            + mem["block_pool/blocks_private"]
            == mem["block_pool/blocks_total"]), \
        f"block accounting inconsistent after drain: {mem}"
    # full reclamation: every resident (trie-donated) block must still be
    # evictable, and evicting them all returns the pool to its initial
    # fully-free state. The replay is over, so mutating the trie here costs
    # nothing.
    if engine.prefix_cache is not None:
        engine.prefix_cache.reclaim(int(mem["block_pool/blocks_resident"]))
    mem = engine.memory_stats()
    assert (mem["block_pool/blocks_free"]
            == mem["block_pool/blocks_total"]), \
        f"pool not fully reclaimed after drain + evict-all: {mem}"
    assert head["slots_free"] == engine.max_concurrency, \
        f"headroom not restored after drain: {head}"
    assert head["admissible_requests"] == engine.max_concurrency, \
        f"headroom not restored after drain: {head}"
    return {
        "slot_pool_bytes": mem["slot_pool_bytes"],
        "blocks_pinned": mem.get("block_pool/blocks_pinned", 0),
        "blocks_resident": mem.get("block_pool/blocks_resident", 0),
        "blocks_free": mem.get("block_pool/blocks_free", 0),
        "blocks_total": mem.get("block_pool/blocks_total", 0),
        "fragmentation": mem.get("block_pool/fragmentation", 0.0),
        "admissible_requests": head["admissible_requests"],
    }


def run(
    n_requests: int = 24,
    concurrency: int = 4,
    rate: float = 500.0,
    seed: int = 0,
    poison_every: int = 5,
    deadline_every: int = 6,
    deadline_s: float = 0.0,
    module=None,
    params=None,
    pipeline_depth: int = 2,
    prefix_cache: bool = True,
    prefix_blocks: int = 6,
    verify_parity: bool = True,
    mesh=None,
    trace_path: str | None = None,
    sync_tokens: int = 1,
    speculation: int = 0,
) -> dict:
    """Replay the trace under injected faults; assert zero lost requests and
    (with ``verify_parity``) zero token drift against solo generate; return
    the summary dict (importable — tests/test_reliability.py runs it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import generate
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.reliability import FaultInjector, FaultSpec, inject
    from accelerate_tpu.serving import (
        FINISH_EOS,
        FINISH_LENGTH,
        Request,
        ServingEngine,
        SLOSpec,
        Tracer,
    )

    if module is None:
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        module = GPT2LMHead(cfg)
        params = module.init_params(jax.random.key(0))
    trace = _trace(n_requests, rate, seed, int(module.config.vocab_size))
    # every third request duplicates an earlier block-sized prompt, so
    # retire-time donation -> prefix hits actually occur under the chaos (the
    # base trace's prompts are all-distinct random tokens and would never
    # share blocks). The source sits >= concurrency+1 requests back: any
    # closer and it would typically still be decoding — not yet donated —
    # when the duplicate is admitted at a saturating arrival rate.
    for j in range(2, len(trace), 3):
        donors = [k for k in range(j - concurrency - 1)
                  if len(trace[k].prompt) > 16]
        if donors:
            trace[j] = Request(prompt=list(trace[donors[-1]].prompt),
                               params=trace[j].params,
                               arrival_time=trace[j].arrival_time)

    specs = []
    if poison_every:
        specs.append(FaultSpec.poison(
            at_steps=tuple(range(poison_every - 1, 100_000, poison_every)),
            slots=(0,),
        ))
    injector = FaultInjector(seed=seed, specs=specs)
    tracer = Tracer() if trace_path else None
    engine = ServingEngine(
        module, params, max_concurrency=concurrency,
        prompt_buckets=BUCKETS, max_queue=n_requests + 1,
        pipeline_depth=pipeline_depth,
        prefix_cache=prefix_cache,
        paged_kv=_pool(module, prefix_cache, prefix_blocks),
        mesh=mesh,
        tracer=tracer,
        tokens_per_sync=sync_tokens,
        speculation=speculation or None,
    )
    blocks_free_initial = engine.memory_stats()["block_pool/blocks_free"]
    slo_plain = SLOSpec(name="plain")
    slo_deadline = SLOSpec(name="deadline")

    submitted: dict[int, str] = {}
    terminal: dict[int, str] = {}
    outputs: dict[int, list[int]] = {}
    req_by_id: dict[int, Request] = {}
    t0 = time.perf_counter()
    pending = list(trace)
    i = 0
    with inject(injector):
        while pending or engine.has_work:
            now = time.perf_counter() - t0
            while pending and pending[0].arrival_time <= now:
                src = pending.pop(0)
                tight = deadline_every and i % deadline_every == deadline_every - 1
                result = engine.submit(Request(
                    src.prompt, src.params,
                    deadline_s=deadline_s if tight else None,
                    slo=slo_deadline if tight else slo_plain,
                ))
                submitted[result.request_id] = "deadline" if tight else "plain"
                req_by_id[result.request_id] = src
                if not result.accepted:
                    terminal[result.request_id] = f"rejected:{result.reason}"
                i += 1
            for out in engine.step():
                terminal[out.request_id] = out.finish_reason
                outputs[out.request_id] = out.tokens
            if not engine.has_work and pending:
                time.sleep(max(0.0, pending[0].arrival_time - (time.perf_counter() - t0)))

    lost = sorted(set(submitted) - set(terminal))
    assert not lost, f"lost requests (accepted but no terminal output): {lost}"
    steady = _assert_steady_state(engine)
    assert steady["blocks_free"] == blocks_free_initial, \
        (f"block pool did not return to its initial state: "
         f"{steady['blocks_free']} != {blocks_free_initial}")

    # parity drift: every cleanly finished request — whether its prefill came
    # cold, from cached blocks, after an eviction, or via a watchdog
    # re-prefill — must match the solo lockstep reference token-for-token.
    # Runs OUTSIDE the injector context: the reference must stay unpoisoned.
    drift, checked = [], 0
    if verify_parity:
        for rid, reason in terminal.items():
            if reason not in (FINISH_EOS, FINISH_LENGTH):
                continue
            src = req_by_id[rid]
            ids = jnp.asarray(np.asarray(src.prompt, np.int32)[None, :])
            ref = generate(
                module, params, ids,
                max_new_tokens=src.params.max_new_tokens,
                temperature=src.params.temperature, top_k=src.params.top_k,
                rng=jax.random.key(src.params.seed),
            )
            checked += 1
            if outputs[rid] != np.asarray(ref)[0].tolist():
                drift.append(rid)
        assert not drift, f"parity drift vs solo generate: requests {drift}"

    reasons: dict[str, int] = {}
    for reason in terminal.values():
        reasons[reason] = reasons.get(reason, 0) + 1
    m = engine.metrics
    gp = m.goodput()
    trace_summary = None
    if tracer is not None:
        exported = tracer.export(trace_path)
        valid = tracer.validate()
        # the trace invariants must hold under the chaos, same bar as
        # zero-lost: a malformed span is an engine bug, not viewer noise
        assert not valid["anomalies"], f"trace anomalies: {valid['anomalies']}"
        trace_summary = {"path": exported["path"],
                         "events": exported["events"],
                         "dropped": exported["dropped"],
                         "malformed_spans": 0}
    return {
        "metric": "chaos_serve_lost_requests",
        "value": len(lost),
        "unit": "requests",
        "detail": {
            "requests": n_requests,
            "concurrency": concurrency,
            "poisson_rate": rate,
            "seed": seed,
            "pipeline_depth": pipeline_depth,
            "prefix_cache": bool(prefix_cache),
            "tokens_per_sync": sync_tokens,
            "speculation": speculation,
            "spec_forwards": m.spec_forwards.value,
            "spec_accept_len_mean": round(m.spec_accept_len.mean, 3),
            "tokens_per_dispatch_mean": round(m.tokens_per_dispatch.mean, 3),
            "blocks_free_initial": blocks_free_initial,
            "mesh": f"{engine.mesh_shape[0]}x{engine.mesh_shape[1]}"
                    if engine.mesh is not None else None,
            "compile_count": m.compile_count.value,
            "prefix_blocks": prefix_blocks if prefix_cache else 0,
            "prefix_hits": m.prefix_hits.value,
            "prefix_misses": m.prefix_misses.value,
            "prefix_evictions": m.prefix_evictions.value,
            "prefix_blocks_donated": m.prefix_blocks_donated.value,
            "parity_checked": checked,
            "parity_drift": len(drift),
            "terminal_reasons": reasons,
            "steps": m.steps.value,
            "steps_poisoned": m.steps_poisoned.value,
            "requests_retried": m.requests_retried.value,
            "requests_expired": m.requests_expired.value,
            "goodput_tokens_per_sec": round(gp["goodput_tokens_per_sec"], 2),
            "slo_attainment": round(gp["slo_attainment"], 4),
            "slo_classes": {name: round(c["attainment"], 4)
                            for name, c in gp["classes"].items()},
            "steady_state": steady,
            "trace": trace_summary,
            "wall_s": round(time.perf_counter() - t0, 3),
        },
    }


def run_supervised(
    scenario: str = "hang",
    n_requests: int = 12,
    concurrency: int = 2,
    seed: int = 0,
    pipeline_depth: int = 2,
    max_restarts: int = 3,
    stall_timeout_s: float = 0.15,
    hang_s: float = 0.5,
    verify_parity: bool = True,
    trace_path: str | None = None,
    workdir: str | None = None,
) -> dict:
    """Self-healing scenarios (``CHAOS_SCENARIO=hang|storm``): the SUPERVISOR
    — not this harness — must recover the engine. A mid-decode hang (injected
    dispatch sleep past the stall timeout) or a NaN storm (quarantines on two
    slots inside the storm window) forces the restart ladder: engine rebuild
    + automatic journal resume, with NO manual `resume()` call anywhere in
    this function. Asserts zero lost requests, zero token drift vs solo
    generate, and every shed request accounted as rejected. With
    ``max_restarts=0`` (``CHAOS_RESTART_BUDGET=0``) the same run must instead
    fail FAST: the supervisor goes unhealthy on the first failure and every
    in-flight request comes back ``rejected:unhealthy``."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import generate
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.reliability import FaultInjector, FaultSpec, inject
    from accelerate_tpu.serving import (
        FINISH_EOS,
        FINISH_LENGTH,
        REJECT_UNHEALTHY,
        AnomalyConfig,
        AnomalyMonitor,
        EngineSupervisor,
        Request,
        ServingEngine,
        SupervisorConfig,
        Tracer,
    )

    if scenario not in ("hang", "storm"):
        raise ValueError(f"unknown supervised scenario {scenario!r}")
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_supervised_")
    journal = os.path.join(workdir, "requests.journal")
    # flight recorder (docs/observability.md): chaos-tuned detectors — tiny
    # baseline + single-step entry, so the injected fault's latency spike
    # must cut exactly one debug bundle inside the rate-limit window
    bundle_dir = os.path.join(workdir, "anomaly")
    os.makedirs(bundle_dir, exist_ok=True)
    monitor = AnomalyMonitor(AnomalyConfig(
        min_samples=4, zscore=4.0, enter_steps=1, exit_steps=4,
        bundle_dir=bundle_dir, bundle_min_interval_s=60.0))
    # the trace doubles as explain_request's input, so always record one
    trace_path = trace_path or os.path.join(workdir, "chaos.trace.json")
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    # saturating trace: everything arrives up front so the dispatch/step
    # schedule — and therefore where the injected fault lands — is a pure
    # function of the seed, not of wall-clock arrival timing
    trace = _trace(n_requests, 1e9, seed, int(module.config.vocab_size))

    if scenario == "hang":
        # several candidate dispatch indices, capped at 2 firings: if one
        # lands on a first-dispatch compile (which the supervisor's
        # compile-guard rightly excuses), a later one hits a pure decode
        # dispatch and the stall classification fires
        specs = [FaultSpec.step_hang(at_calls=tuple(range(6, 200, 7)),
                                     hang_s=hang_s, max_faults=2)]
        sup_cfg = SupervisorConfig(stall_timeout_s=stall_timeout_s,
                                   max_restarts=max_restarts)
    else:
        # two quarantines on DIFFERENT slots inside the window: each request
        # is poisoned at most once (first-offence retry keeps it clean), and
        # the storm classifier escalates the pair to a rebuild
        specs = [FaultSpec.poison(at_steps=(3,), slots=(0,)),
                 FaultSpec.poison(at_steps=(4,), slots=(1 % concurrency,))]
        sup_cfg = SupervisorConfig(storm_quarantines=2, storm_window_steps=8,
                                   max_restarts=max_restarts)
    injector = FaultInjector(seed=seed, specs=specs)
    tracer = Tracer()

    def factory(**kw):
        # the SAME module/params objects on every rebuild: the restarted
        # engine's jitted programs come from the process-level shared-jit
        # cache, so recovery skips recompilation. The anomaly monitor is
        # closed in HERE (the supervisor only forwards journal/metrics/
        # tracer) so its detector state survives every rebuild.
        return ServingEngine(
            module, params, max_concurrency=concurrency,
            prompt_buckets=BUCKETS, max_queue=n_requests + 1,
            pipeline_depth=pipeline_depth, anomaly=monitor, **kw,
        )

    sup = EngineSupervisor(factory, journal, config=sup_cfg, tracer=tracer)
    t0 = time.perf_counter()
    submitted: list[int] = []
    shed = 0
    terminal: dict[int, str] = {}
    outputs: dict[int, list[int]] = {}
    req_by_id: dict[int, Request] = {}
    failed_fast = False
    with inject(injector):
        for src in trace:
            result = sup.submit(Request(src.prompt, src.params))
            if result.accepted:
                submitted.append(result.request_id)
                req_by_id[result.request_id] = src
            else:
                shed += 1
        while sup.has_work:
            for out in sup.step():
                terminal[out.request_id] = out.finish_reason
                outputs[out.request_id] = out.tokens
    if sup.unhealthy:
        # budget exhausted: the fail-loud contract — no flapping, a raising
        # step(), rejecting admission, and EVERY accepted request accounted
        failed_fast = True
        try:
            sup.step()
            raise AssertionError("unhealthy supervisor step() did not raise")
        except Exception as exc:
            assert type(exc).__name__ == "EngineUnhealthyError", exc
        probe = sup.submit(trace[0].prompt)
        assert not probe.accepted and probe.reason == REJECT_UNHEALTHY, probe
        shed += 1
        unhealthy_reason = f"rejected:{REJECT_UNHEALTHY}"
        sheded = [r for r in terminal.values() if r == unhealthy_reason]
        assert sheded, f"no request accounted {unhealthy_reason}: {terminal}"

    lost = sorted(set(submitted) - set(terminal))
    assert not lost, f"lost requests across supervised recovery: {lost}"
    if not failed_fast:
        assert sup.restarts >= 1, \
            f"supervisor never restarted under the {scenario} scenario"
        _assert_steady_state(sup.engine)

    drift, checked = [], 0
    if verify_parity:
        for rid, reason in sorted(terminal.items()):
            if reason not in (FINISH_EOS, FINISH_LENGTH):
                continue
            src = req_by_id[rid]
            ids = jnp.asarray(np.asarray(src.prompt, np.int32)[None, :])
            ref = generate(
                module, params, ids,
                max_new_tokens=src.params.max_new_tokens,
                temperature=src.params.temperature, top_k=src.params.top_k,
                rng=jax.random.key(src.params.seed),
            )
            checked += 1
            if outputs[rid] != np.asarray(ref)[0].tolist():
                drift.append(rid)
        assert not drift, \
            f"token drift across supervised {scenario} recovery: {drift}"

    m = sup.metrics
    reasons: dict[str, int] = {}
    for reason in terminal.values():
        reasons[reason] = reasons.get(reason, 0) + 1
    trace_summary = None
    if tracer is not None:
        exported = tracer.export(trace_path)
        valid = tracer.validate()
        assert not valid["anomalies"], f"trace anomalies: {valid['anomalies']}"
        trace_summary = {"path": exported["path"],
                         "events": exported["events"],
                         "dropped": exported["dropped"]}

    bundles: list[str] = []
    if not failed_fast:
        # the injected fault's latency spike must have tripped the flight
        # recorder: at least one bundle, valid JSON in the v1 schema, no
        # torn tmp files (atomic-write contract), and `explain_request`
        # must attribute a recovered request's wall time clean (exit 0)
        import glob as _glob
        import subprocess

        from accelerate_tpu.serving.anomaly import BUNDLE_FORMAT

        bundles = sorted(_glob.glob(os.path.join(bundle_dir, "anomaly-*.json")))
        assert bundles, (f"no debug bundle under the {scenario} scenario "
                         f"(events={monitor.events})")
        with open(bundles[0]) as f:
            doc = json.load(f)
        assert doc.get("format") == BUNDLE_FORMAT, doc.get("format")
        assert doc["trigger"]["detector"] in monitor.detectors, doc["trigger"]
        assert not _glob.glob(os.path.join(bundle_dir, "*.tmp")), \
            "torn bundle tmp file left behind"
        clean = sorted(rid for rid, reason in terminal.items()
                       if reason in (FINISH_EOS, FINISH_LENGTH))
        assert clean, f"no cleanly finished request to explain: {reasons}"
        explain = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "explain_request.py"),
             str(clean[0]), trace_path, "--json"],
            capture_output=True, text=True, timeout=120)
        assert explain.returncode == 0, \
            (f"explain_request rid={clean[0]} exited "
             f"{explain.returncode}: {explain.stdout[-500:]}"
             f"{explain.stderr[-500:]}")
    sup.close()
    return {
        "metric": "chaos_serve_supervised_lost_requests",
        "value": len(lost),
        "unit": "requests",
        "detail": {
            "scenario": scenario,
            "requests": n_requests,
            "concurrency": concurrency,
            "seed": seed,
            "pipeline_depth": pipeline_depth,
            "restart_budget": max_restarts,
            "failed_fast": failed_fast,
            "restarts": sup.restarts,
            "stalls_detected": m.supervisor_stalls.value,
            "storms_detected": m.supervisor_storms.value,
            "shed_requests": shed,
            "shed_counter": m.supervisor_shed.value,
            "faults_fired": [(e.scope, e.call_index, e.kind)
                             for e in injector.fired],
            "compile_count": m.compile_count.value,
            "terminal_reasons": reasons,
            "parity_checked": checked,
            "parity_drift": len(drift),
            "trace": trace_summary,
            "anomaly_events": monitor.events,
            "anomaly_bundles": bundles,
            "anomaly_bundle_errors": monitor.bundle_errors,
            "wall_s": round(time.perf_counter() - t0, 3),
        },
    }


def run_replica_kill(
    n_replicas: int = 2,
    n_requests: int = 16,
    concurrency: int = 2,
    seed: int = 0,
    pipeline_depth: int = 2,
    verify_parity: bool = True,
    trace_path: str | None = None,
    workdir: str | None = None,
) -> dict:
    """Multi-replica kill scenario (``CHAOS_SCENARIO=replica_kill``,
    ``CHAOS_REPLICAS=n``): the whole trace runs through a `ServingCluster`
    with every replica on a ZERO restart budget, and an injected device loss
    kills whichever replica's dispatch it lands on — budget exhausted, the
    supervisor fails it loud, and the CLUSTER (not this harness) must
    migrate the dead replica's journaled backlog onto the survivors with
    ``resume_tokens``. Asserts zero lost requests, zero token drift vs solo
    generate for every clean finish — including the migrated mid-stream
    continuations — plus clean journals under `tools/journal_fsck.py`'s
    ``--all`` sweep and steady-state gauges on every surviving replica."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import generate
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.reliability import FaultInjector, FaultSpec, inject
    from accelerate_tpu.serving import (
        FINISH_EOS,
        FINISH_LENGTH,
        Request,
        ServingCluster,
        ServingEngine,
        SupervisorConfig,
        Tracer,
    )

    if n_replicas < 2:
        raise ValueError("replica_kill needs CHAOS_REPLICAS >= 2 "
                         "(a survivor must exist to migrate onto)")
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_cluster_")
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    trace = _trace(n_requests, 1e9, seed, int(module.config.vocab_size))

    # one device loss, deterministically scheduled (several candidate
    # dispatch indices, one firing): whichever replica's dispatch it lands
    # on dies — budget 0 means the first failure exhausts the ladder
    injector = FaultInjector(seed=seed, specs=[
        FaultSpec.device_error(at_calls=tuple(range(8, 400, 9)),
                               max_faults=1)])
    tracers = [Tracer() for _ in range(n_replicas)] if trace_path else None

    def factory(**kw):
        return ServingEngine(
            module, params, max_concurrency=concurrency,
            prompt_buckets=BUCKETS, max_queue=n_requests + 1,
            pipeline_depth=pipeline_depth, **kw,
        )

    cluster = ServingCluster(
        factory, workdir, replicas=n_replicas,
        supervisor_config=SupervisorConfig(max_restarts=0),
        tracers=tracers)
    t0 = time.perf_counter()
    submitted: list[int] = []
    shed = 0
    terminal: dict[int, str] = {}
    outputs: dict[int, list[int]] = {}
    req_by_id: dict[int, object] = {}
    with inject(injector):
        for src in trace:
            result = cluster.submit(Request(src.prompt, src.params))
            if result.accepted:
                submitted.append(result.request_id)
                req_by_id[result.request_id] = src
            else:
                shed += 1
        while cluster.has_work:
            for out in cluster.step():
                terminal[out.request_id] = out.finish_reason
                outputs[out.request_id] = out.tokens

    dead = [rep.index for rep in cluster.replicas if not rep.healthy]
    assert dead, "the injected device loss never landed — no replica died"
    assert len(dead) < n_replicas, "every replica died; nothing to migrate to"
    assert cluster.migrations >= 1, \
        f"dead replica(s) {dead} but the cluster never migrated"
    lost = sorted(set(submitted) - set(terminal))
    assert not lost, f"lost requests across replica kill: {lost}"

    drift, checked = [], 0
    if verify_parity:
        for rid, reason in sorted(terminal.items()):
            if reason not in (FINISH_EOS, FINISH_LENGTH):
                continue
            src = req_by_id[rid]
            ids = jnp.asarray(np.asarray(src.prompt, np.int32)[None, :])
            ref = generate(
                module, params, ids,
                max_new_tokens=src.params.max_new_tokens,
                temperature=src.params.temperature, top_k=src.params.top_k,
                rng=jax.random.key(src.params.seed),
            )
            checked += 1
            if outputs[rid] != np.asarray(ref)[0].tolist():
                drift.append(rid)
        assert not drift, \
            f"token drift across replica-kill migration: {drift}"

    # the cluster workdir's journals must audit clean as a set — the same
    # sweep an operator runs (tools/journal_fsck.py --all WORKDIR)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from journal_fsck import fsck_all  # noqa: E402
    fsck_report, fsck_code = fsck_all(workdir)
    assert fsck_code == 0, f"journal fsck --all failed: {fsck_report}"
    assert fsck_report["journals"] == n_replicas, fsck_report

    for rep in cluster.replicas:
        if rep.healthy:
            _assert_steady_state(rep.engine)

    trace_summary = None
    if tracers is not None:
        from trace_report import multi_report  # tools/ is on sys.path now
        os.makedirs(trace_path, exist_ok=True)
        paths = []
        for i, tr in enumerate(tracers):
            exported = tr.export(os.path.join(
                trace_path, f"replica{i}.trace.json"))
            paths.append(exported["path"])
        combined = multi_report(paths, top=3)
        assert combined["clean"], f"trace anomalies: {combined}"
        trace_summary = {"paths": paths, "events": combined["events"]}

    reasons: dict[str, int] = {}
    for reason in terminal.values():
        reasons[reason] = reasons.get(reason, 0) + 1
    snap = cluster.metrics.snapshot()
    cluster.close()
    return {
        "metric": "chaos_serve_cluster_lost_requests",
        "value": len(lost),
        "unit": "requests",
        "detail": {
            "scenario": "replica_kill",
            "replicas": n_replicas,
            "dead_replicas": dead,
            "requests": n_requests,
            "concurrency": concurrency,
            "seed": seed,
            "pipeline_depth": pipeline_depth,
            "migrations": cluster.migrations,
            "migrated_requests": cluster.migrated_requests,
            "routed_prefix": snap["cluster/routed_prefix"],
            "routed_round_robin": snap["cluster/routed_round_robin"],
            "shed_requests": shed,
            "faults_fired": [(e.scope, e.call_index, e.kind)
                             for e in injector.fired],
            "terminal_reasons": reasons,
            "parity_checked": checked,
            "parity_drift": len(drift),
            "journals_clean": fsck_report["clean_journals"],
            "trace": trace_summary,
            "wall_s": round(time.perf_counter() - t0, 3),
        },
    }


def run_surge_drain(
    n_requests: int = 20,
    warmup: int = 4,
    concurrency: int = 2,
    seed: int = 0,
    pipeline_depth: int = 2,
    max_replicas: int = 3,
    verify_parity: bool = True,
    workdir: str | None = None,
) -> dict:
    """Elastic-fleet scenario (``CHAOS_SCENARIO=surge_drain``,
    `serving/autoscaler.py`, docs/reliability.md "Elastic fleet"): a
    `ServingCluster` starts at ONE replica with a `FleetAutoscaler`
    attached, a 4x load step drives the fleet-wide predicted TTFT past the
    target so the AUTOSCALER (not this harness) scales up, and while the
    surge is still in flight the original — most loaded — replica is put
    into the DRAINING lifecycle and a simulated SIGKILL (a device error on
    a zero-restart budget) lands on it MID-DRAIN: its journaled backlog
    must migrate to the freshly spawned replicas bit-exactly. When the load
    drops, idle windows accumulate and the autoscaler drain-and-retires the
    fleet back to ``min_replicas``. Asserts: >= 1 scale-up, >= 1 autoscaled
    retire, zero lost requests, zero token drift vs solo generate, every
    journal clean under `tools/journal_fsck.py` ``--all`` (retired and
    replaced replica dirs included), the fleet back at the floor, and
    scaling NOT thrash-frozen."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import generate
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.serving import (
        FINISH_EOS,
        FINISH_LENGTH,
        AutoscalerConfig,
        FleetAutoscaler,
        Request,
        ServingCluster,
        ServingEngine,
        SupervisorConfig,
        predict_ttft,
    )

    workdir = workdir or tempfile.mkdtemp(prefix="chaos_surge_")
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    trace = _trace(n_requests, 1e9, seed, int(module.config.vocab_size))
    warmup = max(1, min(warmup, n_requests - 1))

    def factory(**kw):
        return ServingEngine(
            module, params, max_concurrency=concurrency,
            prompt_buckets=BUCKETS, max_queue=n_requests + 1,
            pipeline_depth=pipeline_depth, **kw,
        )

    cluster = ServingCluster(
        factory, workdir, replicas=1,
        supervisor_config=SupervisorConfig(max_restarts=0))
    t0 = time.perf_counter()
    submitted: list[int] = []
    shed = 0
    terminal: dict[int, str] = {}
    outputs: dict[int, list[int]] = {}
    req_by_id: dict[int, object] = {}

    def pump(reqs):
        nonlocal shed
        for src in reqs:
            result = cluster.submit(Request(src.prompt, src.params))
            if result.accepted:
                submitted.append(result.request_id)
                req_by_id[result.request_id] = src
            else:
                shed += 1

    def record(outs):
        for out in outs:
            terminal[out.request_id] = out.finish_reason
            outputs[out.request_id] = out.tokens

    # phase 1 — baseline at the fleet floor: compiles the decode step and
    # establishes the idle TTFT prediction the surge threshold is sized
    # against (a fixed threshold would race the host's actual step time)
    pump(trace[:warmup])
    while cluster.has_work:
        record(cluster.step())
    rep0 = cluster.replicas[0]
    baseline = predict_ttft(
        cluster.capacity_headroom(),
        getattr(rep0.engine, "last_step_timings", None) or {},
        max_concurrency=rep0.engine.max_concurrency) or 0.0
    scaler = FleetAutoscaler(cluster, AutoscalerConfig(
        min_replicas=1, max_replicas=max_replicas,
        # idle predicts ~one step; the 4x queue predicts many slot
        # turnarounds — 6x idle splits the two robustly on any host
        target_ttft_s=max(6.0 * baseline, 0.02),
        scale_up_windows=2,
        idle_slots_fraction=0.5, scale_down_idle_windows=3,
        dwell_s=0.0, drain_grace_evals=6,
        # loose thrash window: this scenario's scripted churn must not
        # freeze scaling (the freeze path has its own unit tests)
        thrash_enter_events=64,
    ))

    # phase 2 — the 4x load step, then the kill: once the autoscaler has
    # spawned, the ORIGINAL replica (holding the surge queue) starts the
    # drain-and-retire lifecycle and immediately takes a fatal device error
    # on its zero-restart budget — the in-process stand-in for a SIGKILL
    # landing on a DRAINING replica mid-migration
    pump(trace[warmup:])
    killed = False
    kill_state = None

    def _killed_step():
        raise RuntimeError("chaos: injected kill on draining replica")

    while cluster.has_work:
        if (not killed and scaler.scale_ups >= 1
                and rep0.accepting and rep0.supervisor.has_work):
            cluster.retire_replica(rep0.index)
            kill_state = rep0.state
            rep0.engine.step = _killed_step
            killed = True
        record(cluster.step())
    assert killed, ("the surge never triggered a scale-up — no draining "
                    "replica to kill")
    assert kill_state == "draining", kill_state
    assert rep0.retired, "the killed draining replica never finalized"
    assert cluster.migrations >= 1, \
        "the mid-drain kill never migrated the backlog"

    # phase 3 — the load drop: idle evaluations accumulate and the
    # autoscaler drains the spawned replicas back to the floor
    for _ in range(200):
        record(cluster.step())
        accepting = sum(1 for r in cluster.replicas if r.accepting)
        draining = sum(1 for r in cluster.replicas
                       if not r.retired and r.draining)
        if accepting == 1 and draining == 0 and not cluster.has_work:
            break
    accepting = sum(1 for r in cluster.replicas if r.accepting)
    assert accepting == 1, \
        f"fleet never converged to min_replicas: {accepting} accepting"
    assert scaler.scale_ups >= 1, "no scale-up recorded"
    assert scaler.retires >= 1, "the idle fleet never drain-and-retired"
    assert not scaler.frozen, "scripted churn thrash-froze the autoscaler"
    lost = sorted(set(submitted) - set(terminal))
    assert not lost, f"lost requests across surge/drain: {lost}"

    drift, checked = [], 0
    if verify_parity:
        for rid, reason in sorted(terminal.items()):
            if reason not in (FINISH_EOS, FINISH_LENGTH):
                continue
            src = req_by_id[rid]
            ids = jnp.asarray(np.asarray(src.prompt, np.int32)[None, :])
            ref = generate(
                module, params, ids,
                max_new_tokens=src.params.max_new_tokens,
                temperature=src.params.temperature, top_k=src.params.top_k,
                rng=jax.random.key(src.params.seed),
            )
            checked += 1
            if outputs[rid] != np.asarray(ref)[0].tolist():
                drift.append(rid)
        assert not drift, \
            f"token drift across surge-drain migration: {drift}"

    # every journal the elastic fleet left behind — retired, replaced, and
    # live replica dirs alike — must audit clean as one sweep
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from journal_fsck import fsck_all  # noqa: E402
    fsck_report, fsck_code = fsck_all(workdir)
    assert fsck_code == 0, f"journal fsck --all failed: {fsck_report}"
    assert fsck_report["journals"] == cluster.n_replicas, fsck_report

    for rep in cluster.replicas:
        if rep.accepting:
            _assert_steady_state(rep.engine)

    reasons: dict[str, int] = {}
    for reason in terminal.values():
        reasons[reason] = reasons.get(reason, 0) + 1
    gauges = scaler.gauges()
    cluster.close()
    return {
        "metric": "chaos_serve_surge_lost_requests",
        "value": len(lost),
        "unit": "requests",
        "detail": {
            "scenario": "surge_drain",
            "requests": n_requests,
            "concurrency": concurrency,
            "seed": seed,
            "pipeline_depth": pipeline_depth,
            "max_replicas": max_replicas,
            "baseline_ttft_s": round(baseline, 6),
            "scale_ups": scaler.scale_ups,
            "retires": scaler.retires,
            "retired_replicas": cluster.retired_replicas,
            "replicas_ever": cluster.n_replicas,
            "migrations": cluster.migrations,
            "migrated_requests": cluster.migrated_requests,
            "spawn_retries": scaler.spawn_retries,
            "scale_frozen": gauges["autoscaler/scale_frozen"],
            "shed_requests": shed,
            "terminal_reasons": reasons,
            "parity_checked": checked,
            "parity_drift": len(drift),
            "journals_clean": fsck_report["clean_journals"],
            "replica_indices": fsck_report["replica_indices"],
            "wall_s": round(time.perf_counter() - t0, 3),
        },
    }


def run_stream_kill(
    n_requests: int = 12,
    concurrency: int = 2,
    seed: int = 0,
    pipeline_depth: int = 2,
    prefix_cache: bool = True,
    prefix_blocks: int = 6,
    timeout_s: float = 240.0,
    workdir: str | None = None,
    sync_tokens: int = 1,
    speculation: int = 0,
) -> dict:
    """Streaming crash scenario (``CHAOS_SCENARIO=stream_kill``): a STREAMING
    consumer tails the child's journal while the child serves, the child is
    SIGKILLed mid-stream (>= 1 stream with delivered tokens and no FINISH on
    disk), and the parent resumes a fresh engine from the journal with
    `ServingFrontend.resume_stream` re-attached at each consumer's exact
    pre-crash frontier. Asserts the exactly-once streaming contract across
    the crash: every resumed stream's pre-crash prefix + post-crash events is
    BYTE-IDENTICAL to solo generate, no token is delivered twice (the
    re-decoded overlap is verified against the frontier — a divergence raises
    `StreamStall`), and no events are duplicated (each stream's cumulative
    ``n`` is strictly increasing). Works under ``CHAOS_SPEC`` speculation and
    ``CHAOS_SYNC_TOKENS`` multi-token scan too; return the summary dict
    (importable — tests/test_frontend.py runs it)."""
    import signal as _signal
    import subprocess
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import generate
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.serving import (
        FINISH_EOS,
        FINISH_LENGTH,
        RequestJournal,
        ServingEngine,
        ServingFrontend,
    )
    from accelerate_tpu.serving.frontend import _JournalTailer

    workdir = workdir or tempfile.mkdtemp(prefix="chaos_stream_")
    journal = os.path.join(workdir, "requests.journal")
    env = dict(
        os.environ,
        CHAOS_CRASH_CHILD="1", CHAOS_JOURNAL=journal,
        CHAOS_SNAPSHOT=os.path.join(workdir, "unused.snap"),
        CHAOS_SCENARIO="stream_kill", CHAOS_REQUESTS=str(n_requests),
        CHAOS_CONCURRENCY=str(concurrency), CHAOS_SEED=str(seed),
        CHAOS_DEPTH=str(pipeline_depth), CHAOS_PREFIX=str(int(prefix_cache)),
        CHAOS_PREFIX_BLOCKS=str(prefix_blocks),
        CHAOS_SYNC_TOKENS=str(sync_tokens),
        CHAOS_SPEC=str(speculation),
        JAX_PLATFORMS="cpu",
    )
    t0 = time.perf_counter()
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    # the parent IS the streaming consumer: tail the child's journal exactly
    # the way a `TokenStream` does, recording each request's delivered
    # frontier. Kill only once >= 1 stream is provably mid-flight (tokens
    # delivered, no FINISH on disk).
    tailer = _JournalTailer(journal)
    pre: dict[int, list[int]] = {}
    rc = None
    try:
        deadline = time.time() + timeout_s
        while time.time() < deadline and child.poll() is None:
            tailer.poll()
            mid = [rid for rid, toks in tailer.tokens.items()
                   if toks and rid not in tailer.finishes]
            if mid:
                break
            time.sleep(0.02)
        else:
            raise AssertionError(
                f"child never reached mid-stream (rc={child.poll()})")
        pre = {rid: list(toks) for rid, toks in tailer.tokens.items()}
        child.send_signal(_signal.SIGKILL)
        rc = child.wait(timeout=timeout_s)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    assert rc == -_signal.SIGKILL, f"stream_kill child exited {rc}"
    mid_stream = sorted(rid for rid in mid)

    scan = RequestJournal.scan(journal)
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    engine = ServingEngine(
        module, params, max_concurrency=concurrency,
        prompt_buckets=BUCKETS, max_queue=n_requests + 1,
        pipeline_depth=pipeline_depth,
        prefix_cache=prefix_cache,
        paged_kv=_pool(module, prefix_cache, prefix_blocks),
        journal=journal,
        tokens_per_sync=sync_tokens,
        speculation=speculation or None,
    )
    report = engine.resume(journal)
    frontend = ServingFrontend(engine)
    streams = {rid: frontend.resume_stream(rid, delivered=list(pre.get(rid, [])))
               for rid in sorted(scan.submits)}
    events: dict[int, list] = {rid: [] for rid in streams}
    stalls = 0
    while engine.has_work or frontend.open_streams():
        if engine.has_work:
            engine.step()
            stalls = 0
        else:
            stalls += 1
            assert stalls < 1000, (
                f"streams never finished after the drain: "
                f"{[s.request_id for s in frontend.open_streams()]}")
        for ev in frontend.pump():
            events[ev.request_id].append(ev)

    # exactly-once across the crash, stream by stream
    divergent = []
    duplicated = []
    for rid, stream in streams.items():
        assert stream.finished, f"stream {rid} never saw a FINISH record"
        prefix = pre.get(rid, [])
        # the pre-crash frontier survived verbatim (TokenStream verifies the
        # re-journaled overlap internally — a divergence would have raised)
        assert stream.delivered[:len(prefix)] == prefix, rid
        # no duplicated events: token events carry the post-crash suffix
        # exactly once, with strictly increasing cumulative n
        suffix = []
        last_n = len(prefix)
        for ev in events[rid]:
            if ev.tokens:
                suffix.extend(ev.tokens)
            if ev.n < last_n:
                duplicated.append(rid)
            last_n = max(last_n, ev.n)
        if prefix + suffix != stream.delivered:
            duplicated.append(rid)
        if stream.finish_reason in (FINISH_EOS, FINISH_LENGTH):
            rec = scan.submits[rid]
            sp = rec["params"]
            ids = jnp.asarray(np.asarray(rec["prompt"], np.int32)[None, :])
            ref = generate(
                module, params, ids,
                max_new_tokens=sp["max_new_tokens"],
                temperature=sp["temperature"], top_k=sp["top_k"],
                rng=jax.random.key(sp["seed"]),
            )
            if stream.delivered != np.asarray(ref)[0].tolist():
                divergent.append(rid)
    assert not duplicated, f"duplicated stream events across crash: {duplicated}"
    assert not divergent, (
        f"resumed streams not byte-identical to solo generate: {divergent}")
    steady = _assert_steady_state(engine)

    return {
        "metric": "chaos_serve_stream_kill_divergent_streams",
        "value": len(divergent),
        "unit": "streams",
        "detail": {
            "scenario": "stream_kill",
            "child_exit_code": rc,
            "requests": n_requests,
            "concurrency": concurrency,
            "seed": seed,
            "pipeline_depth": pipeline_depth,
            "prefix_cache": bool(prefix_cache),
            "tokens_per_sync": sync_tokens,
            "speculation": speculation,
            "streams": len(streams),
            "mid_stream_at_kill": mid_stream,
            "pre_crash_tokens": {str(r): len(t) for r, t in pre.items()},
            "finished_pre_crash": len(scan.finishes),
            "resumed_mid_stream": len(report.resumed),
            "restored_queued": len(report.restored),
            "replayed_tokens": engine.metrics.replayed_tokens.value,
            "journal_records": scan.records,
            "truncated_tail_bytes": scan.truncated_tail_bytes,
            "byte_identical_streams": len(streams) - len(divergent),
            "steady_state": steady,
            "wall_s": round(time.perf_counter() - t0, 3),
        },
    }


def _crash_child() -> None:
    """Child half of the crash scenarios: serve the trace with a journal (and,
    under sigterm, a drain-or-snapshot preemption handler) until killed."""
    import signal as _signal

    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.reliability import install_serving_preemption_handler
    from accelerate_tpu.serving import Request, ServingEngine

    n = _env_int("CHAOS_REQUESTS", 12)
    quant = os.environ.get("CHAOS_QUANT", "")
    cfg = GPT2Config.tiny(
        dtype=jnp.float32,
        kv_cache_dtype=jnp.int8 if quant == "int8" else None,
    )
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    trace = _trace(n, 1e9, _env_int("CHAOS_SEED", 0),
                   int(module.config.vocab_size))
    engine = ServingEngine(
        module, params,
        max_concurrency=_env_int("CHAOS_CONCURRENCY", 2),
        prompt_buckets=BUCKETS, max_queue=n + 1,
        pipeline_depth=_env_int("CHAOS_DEPTH", 2),
        prefix_cache=bool(_env_int("CHAOS_PREFIX", 1)),
        paged_kv=_pool(module, bool(_env_int("CHAOS_PREFIX", 1)),
                       _env_int("CHAOS_PREFIX_BLOCKS", 6)),
        journal=os.environ["CHAOS_JOURNAL"],
        tokens_per_sync=_env_int("CHAOS_SYNC_TOKENS", 1),
        speculation=_env_int("CHAOS_SPEC", 0) or None,
    )
    if os.environ.get("CHAOS_SCENARIO") == "sigterm":
        install_serving_preemption_handler(
            engine, os.environ["CHAOS_SNAPSHOT"],
            grace_s=float(os.environ.get("CHAOS_GRACE", 0.05)),
        )
    for src in trace:
        engine.submit(Request(src.prompt, src.params))
    while engine.has_work:
        # deliver-at-step-boundary: SIGTERM is blocked while a step is in
        # flight and delivered at the unblock, so the handler's drain loop
        # never re-enters a half-completed step. SIGKILL cannot be blocked —
        # it kills mid-anything, which is exactly what the journal's torn-tail
        # tolerance exists for.
        _signal.pthread_sigmask(_signal.SIG_BLOCK, {_signal.SIGTERM})
        engine.step()
        _signal.pthread_sigmask(_signal.SIG_UNBLOCK, {_signal.SIGTERM})
    # finished everything before the kill landed: park so the parent's signal
    # still hits a live process (the scenario then degenerates to "all
    # completed pre-crash", which the recovery asserts trivially)
    while True:
        time.sleep(0.05)


def _hibernate_kill_child() -> None:
    """Child half of the hibernate_kill scenario: a paged tier-on engine
    serves the trace until the harness has FORCED the host tier into its
    riskiest durable state — requests hibernated (slots released, KV only in
    volatile host buffers) AND trie blocks spilled — then freezes there,
    writes the marker, and waits for the parent's SIGKILL. Everything that
    must survive is already on disk: hibernation flushes journal progress
    before releasing blocks, host buffers are deliberately not durable."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.serving import (
        KVTierConfig,
        PagedKVConfig,
        Request,
        ServingEngine,
    )

    n = _env_int("CHAOS_REQUESTS", 12)
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    trace = _trace(n, 1e9, _env_int("CHAOS_SEED", 0),
                   int(module.config.vocab_size))
    engine = ServingEngine(
        module, params,
        max_concurrency=_env_int("CHAOS_CONCURRENCY", 4),
        prompt_buckets=BUCKETS, max_queue=n + 1,
        pipeline_depth=_env_int("CHAOS_DEPTH", 2),
        prefix_cache=True,
        journal=os.environ["CHAOS_JOURNAL"],
        paged_kv=PagedKVConfig(block_tokens=16, num_blocks=32),
        kv_tier=KVTierConfig(),
    )
    for src in trace:
        engine.submit(Request(src.prompt, src.params))
    tier = engine.kv_tier
    while engine.has_work:
        engine.step()
        for s in range(engine.max_concurrency):
            if tier.hibernated_count >= 2:
                break
            if (engine._active[s] and engine._slot_out[s] is not None
                    and engine._slot_out[s].tokens):
                tier.hibernate_slot(s)
        tier.page_out_trie(4)
        if tier.hibernated_count >= 2 and tier.trie_host_blocks >= 1:
            break
    with open(os.environ["CHAOS_MARKER"] + ".tmp", "w") as f:
        json.dump(tier.memory_stats(), f)
    os.replace(os.environ["CHAOS_MARKER"] + ".tmp", os.environ["CHAOS_MARKER"])
    # hold the hibernated + spilled state so the parent's SIGKILL lands on it
    while True:
        time.sleep(0.05)


def run_hibernate_kill(
    n_requests: int = 12,
    concurrency: int = 4,
    seed: int = 0,
    pipeline_depth: int = 2,
    timeout_s: float = 240.0,
    workdir: str | None = None,
    verify_parity: bool = True,
) -> dict:
    """SIGKILL a child engine WHILE requests are hibernated and blocks are
    spilled to (volatile) host buffers, resume a fresh tier-on engine from
    the journal, and assert zero lost requests, zero token drift, host-tier
    gauges back to steady state, and `journal_fsck` exit 0. The durability
    contract under test: the journal — not host RAM — is the durable tier
    (`docs/serving.md` "KV tiering & hibernation")."""
    import signal as _signal
    import subprocess
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import generate
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.serving import (
        FINISH_EOS,
        FINISH_LENGTH,
        KVTierConfig,
        PagedKVConfig,
        RequestJournal,
        ServingEngine,
    )

    workdir = workdir or tempfile.mkdtemp(prefix="chaos_hibernate_")
    journal = os.path.join(workdir, "requests.journal")
    marker = os.path.join(workdir, "hibernated.marker")
    env = dict(
        os.environ,
        CHAOS_HIBERNATE_CHILD="1", CHAOS_JOURNAL=journal,
        CHAOS_MARKER=marker, CHAOS_REQUESTS=str(n_requests),
        CHAOS_CONCURRENCY=str(concurrency), CHAOS_SEED=str(seed),
        CHAOS_DEPTH=str(pipeline_depth),
        JAX_PLATFORMS="cpu",
    )
    t0 = time.perf_counter()
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    rc = None
    try:
        deadline = time.time() + timeout_s
        while time.time() < deadline and child.poll() is None:
            if os.path.exists(marker):
                break
            time.sleep(0.02)
        else:
            raise AssertionError(
                f"child never reached the hibernated+spilled state "
                f"(rc={child.poll()})")
        with open(marker) as f:
            killed_gauges = json.load(f)
        child.send_signal(_signal.SIGKILL)
        rc = child.wait(timeout=timeout_s)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    assert rc == -_signal.SIGKILL, f"sigkill child exited {rc}"
    assert killed_gauges["hibernated"] >= 2, killed_gauges
    assert killed_gauges["blocks"] >= 1, killed_gauges

    scan = RequestJournal.scan(journal)
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    engine = ServingEngine(
        module, params, max_concurrency=concurrency,
        prompt_buckets=BUCKETS, max_queue=n_requests + 1,
        pipeline_depth=pipeline_depth,
        prefix_cache=True,
        journal=journal,
        paged_kv=PagedKVConfig(block_tokens=16, num_blocks=32),
        kv_tier=KVTierConfig(),
    )
    report = engine.resume(journal)
    outcomes: dict[int, tuple[str, list[int]]] = {
        rid: (reason, toks) for rid, (reason, toks) in scan.finishes.items()
    }
    for rid, out in report.completed.items():
        outcomes[rid] = (out.finish_reason, out.tokens)
    for out in report.expired:
        outcomes[out.request_id] = (out.finish_reason, out.tokens)
    while engine.has_work:
        for out in engine.step():
            outcomes[out.request_id] = (out.finish_reason, out.tokens)
    lost = sorted(rid for rid in scan.submits if rid not in outcomes)
    assert not lost, (
        f"lost requests (journaled as accepted, no terminal outcome after "
        f"hibernate_kill + resume): {lost}")
    steady = _assert_steady_state(engine)
    # the host tier itself must settle: nothing left parked or spilled, no
    # thrash freeze — the drained engine's tier is indistinguishable from a
    # fresh one except for its lifetime counters
    mem = engine.memory_stats()
    assert mem["host_tier/hibernated"] == 0, mem
    assert mem["host_tier/blocks"] == 0 and mem["host_tier/bytes"] == 0, mem
    assert mem["host_tier/spill_frozen"] == 0, mem

    drift, checked = [], 0
    if verify_parity:
        for rid, (reason, toks) in sorted(outcomes.items()):
            if reason not in (FINISH_EOS, FINISH_LENGTH):
                continue
            rec = scan.submits[rid]
            sp = rec["params"]
            ids = jnp.asarray(np.asarray(rec["prompt"], np.int32)[None, :])
            ref = generate(
                module, params, ids,
                max_new_tokens=sp["max_new_tokens"],
                temperature=sp["temperature"], top_k=sp["top_k"],
                rng=jax.random.key(sp["seed"]),
            )
            checked += 1
            if toks != np.asarray(ref)[0].tolist():
                drift.append(rid)
        assert not drift, (
            f"token drift across hibernate_kill + resume: requests {drift}")

    fsck = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "journal_fsck.py"), journal],
        capture_output=True, text=True)
    assert fsck.returncode == 0, f"journal_fsck failed: {fsck.stdout}"

    return {
        "metric": "chaos_serve_hibernate_lost_requests",
        "value": len(lost),
        "unit": "requests",
        "detail": {
            "scenario": "hibernate_kill",
            "child_exit_code": rc,
            "requests": n_requests,
            "concurrency": concurrency,
            "seed": seed,
            "pipeline_depth": pipeline_depth,
            "killed_host_tier": killed_gauges,
            "finished_pre_crash": len(scan.finishes),
            "resumed_mid_stream": len(report.resumed),
            "restored_queued": len(report.restored),
            "expired_on_restore": len(report.expired),
            "journal_records": scan.records,
            "truncated_tail_bytes": scan.truncated_tail_bytes,
            "downtime_s": round(report.downtime_s, 3),
            "parity_checked": checked,
            "parity_drift": len(drift),
            "steady_state": steady,
            "journal_fsck_exit": fsck.returncode,
            "wall_s": round(time.perf_counter() - t0, 3),
        },
    }


def run_crash(
    scenario: str = "sigkill",
    n_requests: int = 12,
    concurrency: int = 2,
    seed: int = 0,
    pipeline_depth: int = 2,
    prefix_cache: bool = True,
    prefix_blocks: int = 6,
    grace_s: float = 0.05,
    timeout_s: float = 240.0,
    workdir: str | None = None,
    verify_parity: bool = True,
    trace_path: str | None = None,
    sync_tokens: int = 1,
    speculation: int = 0,
    quant: str = "",
) -> dict:
    """Kill a child serving process mid-decode (SIGTERM or SIGKILL), resume a
    fresh engine from what survived on disk, and assert zero lost accepted
    requests plus zero token drift; return the summary dict (importable —
    tests/test_serving_recovery.py runs it). ``quant="int8"`` runs the whole
    scenario over int8 KV storage — the parity oracle becomes the quantized
    solo generate, and the resume must be crash-exact through re-quantization
    (prompt + replayed tokens land at the same positions -> same scales)."""
    import signal as _signal
    import subprocess
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.generation import generate
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.reliability import SIGTERM_EXIT_CODE
    from accelerate_tpu.serving import (
        FINISH_EOS,
        FINISH_LENGTH,
        RequestJournal,
        ServingEngine,
        Tracer,
    )
    from accelerate_tpu.serving.journal import REC_FIRST_TOKEN

    if scenario not in ("sigterm", "sigkill"):
        raise ValueError(f"unknown crash scenario {scenario!r}")
    workdir = workdir or tempfile.mkdtemp(prefix="chaos_crash_")
    journal = os.path.join(workdir, "requests.journal")
    snapshot = os.path.join(workdir, "engine.snap")
    env = dict(
        os.environ,
        CHAOS_CRASH_CHILD="1", CHAOS_JOURNAL=journal, CHAOS_SNAPSHOT=snapshot,
        CHAOS_SCENARIO=scenario, CHAOS_REQUESTS=str(n_requests),
        CHAOS_CONCURRENCY=str(concurrency), CHAOS_SEED=str(seed),
        CHAOS_DEPTH=str(pipeline_depth), CHAOS_PREFIX=str(int(prefix_cache)),
        CHAOS_PREFIX_BLOCKS=str(prefix_blocks), CHAOS_GRACE=str(grace_s),
        CHAOS_SYNC_TOKENS=str(sync_tokens),
        CHAOS_SPEC=str(speculation),
        CHAOS_QUANT=quant,
        JAX_PLATFORMS="cpu",
    )
    t0 = time.perf_counter()
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    rc = None
    try:
        # kill only once the journal PROVES the child is mid-decode: >= 1
        # FIRST_TOKEN on disk and >= 1 accepted request not yet finished
        deadline = time.time() + timeout_s
        while time.time() < deadline and child.poll() is None:
            if os.path.exists(journal):
                try:
                    s = RequestJournal.scan(journal)
                except Exception:
                    s = None
                if (s is not None and s.submits
                        and s.records_by_type.get(REC_FIRST_TOKEN, 0) >= 1
                        and any(r not in s.finishes for r in s.submits)):
                    break
            time.sleep(0.02)
        else:
            raise AssertionError(
                f"child never reached mid-decode (rc={child.poll()})")
        child.send_signal(
            _signal.SIGTERM if scenario == "sigterm" else _signal.SIGKILL)
        rc = child.wait(timeout=timeout_s)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if scenario == "sigterm":
        assert rc == SIGTERM_EXIT_CODE, f"sigterm child exited {rc}"
    else:
        assert rc == -_signal.SIGKILL, f"sigkill child exited {rc}"

    scan = RequestJournal.scan(journal)
    # sigterm resumes from the handler's snapshot when one landed (the drain
    # may have finished everything inside the grace window); sigkill always
    # replays the journal — nothing else survived
    source = (snapshot if scenario == "sigterm" and os.path.exists(snapshot)
              else journal)
    # the resume (and the parity oracle below) must run the SAME quant mode
    # the child served — generate over the int8-cache module IS the
    # quantized-solo reference the streams are held to
    cfg = GPT2Config.tiny(
        dtype=jnp.float32,
        kv_cache_dtype=jnp.int8 if quant == "int8" else None,
    )
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    tracer = Tracer() if trace_path else None
    engine = ServingEngine(
        module, params, max_concurrency=concurrency,
        prompt_buckets=BUCKETS, max_queue=n_requests + 1,
        pipeline_depth=pipeline_depth,
        prefix_cache=prefix_cache,
        paged_kv=_pool(module, prefix_cache, prefix_blocks),
        journal=journal,
        tracer=tracer,
        tokens_per_sync=sync_tokens,
        speculation=speculation or None,
    )
    report = engine.resume(source)
    # terminal outcome per accepted rid: child finishes from the journal,
    # then everything the resumed engine produces on top
    outcomes: dict[int, tuple[str, list[int]]] = {
        rid: (reason, toks) for rid, (reason, toks) in scan.finishes.items()
    }
    for rid, out in report.completed.items():
        outcomes[rid] = (out.finish_reason, out.tokens)
    for out in report.expired:
        outcomes[out.request_id] = (out.finish_reason, out.tokens)
    while engine.has_work:
        for out in engine.step():
            outcomes[out.request_id] = (out.finish_reason, out.tokens)
    lost = sorted(rid for rid in scan.submits if rid not in outcomes)
    assert not lost, (
        f"lost requests (journaled as accepted, no terminal outcome after "
        f"{scenario} + resume): {lost}")
    # the RESUMED engine must also settle to clean gauges — a crash-recovery
    # path that leaks a pin or a slot would surface here
    steady = _assert_steady_state(engine)

    # cross-crash parity: every cleanly finished stream — finished by the
    # child, drained by its handler, or resumed mid-stream by the fresh
    # engine — must match solo generate token-for-token. The reference is
    # reconstructed from the journal's SUBMIT records alone.
    drift, checked = [], 0
    if verify_parity:
        for rid, (reason, toks) in sorted(outcomes.items()):
            if reason not in (FINISH_EOS, FINISH_LENGTH):
                continue
            rec = scan.submits[rid]
            sp = rec["params"]
            ids = jnp.asarray(np.asarray(rec["prompt"], np.int32)[None, :])
            ref = generate(
                module, params, ids,
                max_new_tokens=sp["max_new_tokens"],
                temperature=sp["temperature"], top_k=sp["top_k"],
                rng=jax.random.key(sp["seed"]),
            )
            checked += 1
            if toks != np.asarray(ref)[0].tolist():
                drift.append(rid)
        assert not drift, (
            f"token drift across {scenario} + resume: requests {drift}")

    m = engine.metrics
    trace_summary = None
    if tracer is not None:
        exported = tracer.export(trace_path)
        valid = tracer.validate()
        # resume() replays every surviving request through the tracer
        # (EV_SUBMIT recovered=True), so the invariants must hold across the
        # crash boundary too
        assert not valid["anomalies"], f"trace anomalies: {valid['anomalies']}"
        trace_summary = {"path": exported["path"],
                         "events": exported["events"],
                         "dropped": exported["dropped"],
                         "malformed_spans": 0}
    return {
        "metric": "chaos_serve_crash_lost_requests",
        "value": len(lost),
        "unit": "requests",
        "detail": {
            "scenario": scenario,
            "child_exit_code": rc,
            "requests": n_requests,
            "concurrency": concurrency,
            "seed": seed,
            "pipeline_depth": pipeline_depth,
            "prefix_cache": bool(prefix_cache),
            "tokens_per_sync": sync_tokens,
            "speculation": speculation,
            "quant": quant or None,
            "finished_pre_crash": len(scan.finishes),
            "resumed_mid_stream": len(report.resumed),
            "restored_queued": len(report.restored),
            "expired_on_restore": len(report.expired),
            "replayed_tokens": m.replayed_tokens.value,
            "journal_records": scan.records,
            "truncated_tail_bytes": scan.truncated_tail_bytes,
            "resume_source": "snapshot" if source == snapshot else "journal",
            "downtime_s": round(report.downtime_s, 3),
            "parity_checked": checked,
            "parity_drift": len(drift),
            "steady_state": steady,
            "trace": trace_summary,
            "wall_s": round(time.perf_counter() - t0, 3),
        },
    }


def main() -> None:
    if os.environ.get("CHAOS_HIBERNATE_CHILD"):
        _hibernate_kill_child()
        return
    if os.environ.get("CHAOS_CRASH_CHILD"):
        _crash_child()
        return
    if os.environ.get("CHAOS_SCENARIO", "").lower() == "hibernate_kill":
        summary = run_hibernate_kill(
            n_requests=_env_int("CHAOS_REQUESTS", 12),
            concurrency=_env_int("CHAOS_CONCURRENCY", 4),
            seed=_env_int("CHAOS_SEED", 0),
            pipeline_depth=_env_int("CHAOS_DEPTH", 2),
            verify_parity=bool(_env_int("CHAOS_VERIFY_PARITY", 1)),
            workdir=os.environ.get("CHAOS_WORKDIR") or None,
        )
        print(json.dumps(summary), flush=True)
        return
    if os.environ.get("CHAOS_SCENARIO", "").lower() == "replica_kill":
        summary = run_replica_kill(
            n_replicas=_env_int("CHAOS_REPLICAS", 2),
            n_requests=_env_int("CHAOS_REQUESTS", 16),
            concurrency=_env_int("CHAOS_CONCURRENCY", 2),
            seed=_env_int("CHAOS_SEED", 0),
            pipeline_depth=_env_int("CHAOS_DEPTH", 2),
            verify_parity=bool(_env_int("CHAOS_VERIFY_PARITY", 1)),
            trace_path=os.environ.get("CHAOS_TRACE") or None,
            workdir=os.environ.get("CHAOS_WORKDIR") or None,
        )
        print(json.dumps(summary), flush=True)
        return
    if os.environ.get("CHAOS_SCENARIO", "").lower() == "surge_drain":
        summary = run_surge_drain(
            n_requests=_env_int("CHAOS_REQUESTS", 20),
            warmup=_env_int("CHAOS_WARMUP", 4),
            concurrency=_env_int("CHAOS_CONCURRENCY", 2),
            seed=_env_int("CHAOS_SEED", 0),
            pipeline_depth=_env_int("CHAOS_DEPTH", 2),
            max_replicas=_env_int("CHAOS_MAX_REPLICAS", 3),
            verify_parity=bool(_env_int("CHAOS_VERIFY_PARITY", 1)),
            workdir=os.environ.get("CHAOS_WORKDIR") or None,
        )
        print(json.dumps(summary), flush=True)
        return
    if os.environ.get("CHAOS_SCENARIO", "").lower() in ("hang", "storm"):
        summary = run_supervised(
            scenario=os.environ["CHAOS_SCENARIO"].lower(),
            n_requests=_env_int("CHAOS_REQUESTS", 12),
            concurrency=_env_int("CHAOS_CONCURRENCY", 2),
            seed=_env_int("CHAOS_SEED", 0),
            pipeline_depth=_env_int("CHAOS_DEPTH", 2),
            max_restarts=_env_int("CHAOS_RESTART_BUDGET", 3),
            stall_timeout_s=float(os.environ.get("CHAOS_STALL_TIMEOUT", 0.15)),
            verify_parity=bool(_env_int("CHAOS_VERIFY_PARITY", 1)),
            trace_path=os.environ.get("CHAOS_TRACE") or None,
        )
        print(json.dumps(summary), flush=True)
        return
    if os.environ.get("CHAOS_SCENARIO", "").lower() == "stream_kill":
        summary = run_stream_kill(
            n_requests=_env_int("CHAOS_REQUESTS", 12),
            concurrency=_env_int("CHAOS_CONCURRENCY", 2),
            seed=_env_int("CHAOS_SEED", 0),
            pipeline_depth=_env_int("CHAOS_DEPTH", 2),
            prefix_cache=bool(_env_int("CHAOS_PREFIX", 1)),
            prefix_blocks=_env_int("CHAOS_PREFIX_BLOCKS", 6),
            workdir=os.environ.get("CHAOS_WORKDIR") or None,
            sync_tokens=_env_int("CHAOS_SYNC_TOKENS", 1),
            speculation=_env_int("CHAOS_SPEC", 0),
        )
        print(json.dumps(summary), flush=True)
        return
    if os.environ.get("CHAOS_SCENARIO"):
        summary = run_crash(
            scenario=os.environ["CHAOS_SCENARIO"].lower(),
            n_requests=_env_int("CHAOS_REQUESTS", 12),
            concurrency=_env_int("CHAOS_CONCURRENCY", 2),
            seed=_env_int("CHAOS_SEED", 0),
            pipeline_depth=_env_int("CHAOS_DEPTH", 2),
            prefix_cache=bool(_env_int("CHAOS_PREFIX", 1)),
            prefix_blocks=_env_int("CHAOS_PREFIX_BLOCKS", 6),
            grace_s=float(os.environ.get("CHAOS_GRACE", 0.05)),
            verify_parity=bool(_env_int("CHAOS_VERIFY_PARITY", 1)),
            trace_path=os.environ.get("CHAOS_TRACE") or None,
            sync_tokens=_env_int("CHAOS_SYNC_TOKENS", 1),
            speculation=_env_int("CHAOS_SPEC", 0),
            quant=os.environ.get("CHAOS_QUANT", ""),
        )
        print(json.dumps(summary), flush=True)
        return
    mesh = None
    if os.environ.get("CHAOS_MESH"):
        d, m = os.environ["CHAOS_MESH"].lower().replace(" ", "").split("x")
        mesh = (int(d), int(m))
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # must run before the backend initializes (the import of jax
            # inside run() is what first touches it)
            from accelerate_tpu.test_utils.platform import force_cpu_platform

            force_cpu_platform(mesh[0] * mesh[1])
    summary = run(
        n_requests=_env_int("CHAOS_REQUESTS", 24),
        concurrency=_env_int("CHAOS_CONCURRENCY", 4),
        rate=float(os.environ.get("CHAOS_RATE", 500.0)),
        seed=_env_int("CHAOS_SEED", 0),
        poison_every=_env_int("CHAOS_POISON_EVERY", 5),
        deadline_every=_env_int("CHAOS_DEADLINE_EVERY", 6),
        deadline_s=float(os.environ.get("CHAOS_DEADLINE_S", 0.0)),
        pipeline_depth=_env_int("CHAOS_DEPTH", 2),
        prefix_cache=bool(_env_int("CHAOS_PREFIX", 1)),
        prefix_blocks=_env_int("CHAOS_PREFIX_BLOCKS", 6),
        verify_parity=bool(_env_int("CHAOS_VERIFY_PARITY", 1)),
        mesh=mesh,
        trace_path=os.environ.get("CHAOS_TRACE") or None,
        sync_tokens=_env_int("CHAOS_SYNC_TOKENS", 1),
        speculation=_env_int("CHAOS_SPEC", 0),
    )
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
