"""Multi-replica serving cluster (`serving/cluster.py`, `docs/serving.md`
"Multi-replica serving").

The load-bearing contracts: routing only chooses WHICH replica serves a
request, so a 2-replica cluster's outputs are bit-for-bit the single
engine's (including after a replica kill — journal-backed migration moves
the backlog with its emitted prefix as ``resume_tokens``, losing zero
requests and re-generating zero tokens); prefix-aware placement follows the
radix-trie `match_len` probe; health gating routes around browned-out
replicas instead of bouncing admissions off their gates; and a migrated
request's continuation prefill (``prefill_len > 0``) never mixes into a
cached-admission run on its new replica (`scheduler._run_key`).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = [pytest.mark.serving, pytest.mark.cluster]

# every engine-driving test compiles this module's own jitted serving
# programs (~5-10 s each on CPU) — that budget lives in the slow tier with
# the other compile-heavy serving suites (`pytest -m cluster` runs all of
# them); tier-1 keeps the host-only cluster logic: config validation,
# dead-cluster accounting, scheduler-run isolation
_drives_engine = pytest.mark.slow

from accelerate_tpu.models.generation import generate
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.serving import (
    FINISH_LENGTH,
    REJECT_UNHEALTHY,
    ClusterConfig,
    Request,
    SamplingParams,
    ServingCluster,
    ServingEngine,
    SupervisorConfig,
    TelemetryConfig,
    TelemetryExporter,
    Tracer,
)
from accelerate_tpu.serving.cluster import (
    POLICY_ROUND_ROBIN,
    ROLE_DECODE,
    ROLE_PREFILL,
    _UNHEALTHY_REASON,
)
from accelerate_tpu.serving.scheduler import FIFOScheduler
from accelerate_tpu.serving.telemetry import (
    parse_prometheus_text,
    to_prometheus_text,
)


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    return module, params


def _solo(module, params, prompt, n, temperature=0.0, top_k=None, seed=0):
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    out = generate(module, params, ids, max_new_tokens=n,
                   temperature=temperature, top_k=top_k, rng=jax.random.key(seed))
    return np.asarray(out)[0].tolist()


def _prompts(rng_seed, lengths, vocab=256):
    r = np.random.default_rng(rng_seed)
    return [r.integers(0, vocab, (n,)).astype(np.int32).tolist() for n in lengths]


def _mixed_requests(prompts, n_tokens):
    return [
        Request(list(p), SamplingParams(
            max_new_tokens=n_tokens,
            temperature=0.9 if i % 2 else 0.0,
            top_k=5 if i % 2 else None,
            seed=100 + i,
        ))
        for i, p in enumerate(prompts)
    ]


def _factory(module, params, concurrency=2, **fixed):
    """Replica engine factory: same module/params objects on every replica
    (and every rebuild), so the whole cluster shares one jit cache."""
    def build(**kw):
        return ServingEngine(module, params, max_concurrency=concurrency,
                             prompt_buckets=(16, 32), max_queue=32,
                             **fixed, **kw)
    return build


def _drive(cluster):
    outs = {}
    while cluster.has_work:
        for o in cluster.step():
            outs[o.request_id] = o
    return outs


def _assert_parity(module, params, reqs, rids, outs):
    """Every request finished FINISH_LENGTH with exactly the tokens an
    uninterrupted solo `generate` emits (engine outputs are new tokens only)."""
    for i, rid in enumerate(rids):
        r = reqs[i]
        assert outs[rid].finish_reason == FINISH_LENGTH, outs[rid]
        ref = _solo(module, params, r.prompt, r.params.max_new_tokens,
                    temperature=r.params.temperature, top_k=r.params.top_k,
                    seed=r.params.seed)
        assert outs[rid].tokens == ref, f"token drift on rid {rid}"


def _kill(replica):
    """Break a replica's engine in place: the next step raises a recoverable
    class; with ``max_restarts=0`` the supervisor fails unhealthy at once."""
    def boom():
        raise RuntimeError("injected device loss")
    replica.engine.step = boom


# --------------------------------------------------------------- validation
def test_cluster_config_validation(model, tmp_path):
    module, params = model
    with pytest.raises(ValueError, match="policy"):
        ClusterConfig(policy="fastest")
    with pytest.raises(ValueError, match="roles"):
        ClusterConfig(roles=("mixed", "bogus"))
    with pytest.raises(ValueError, match="replicas"):
        ServingCluster(_factory(module, params), tmp_path, replicas=0)
    with pytest.raises(ValueError, match="roles"):
        ServingCluster(_factory(module, params), tmp_path, replicas=2,
                       config=ClusterConfig(roles=("mixed",)))


# ------------------------------------------------------------------- parity
@_drives_engine
def test_two_replica_parity_with_single_engine(model, tmp_path):
    """The cluster parity contract: greedy AND sampled streams from a
    2-replica cluster are bit-for-bit a solo `generate`'s, whichever replica
    each request landed on, under one monotone cluster id sequence."""
    module, params = model
    prompts = _prompts(0, [5, 9, 12, 7, 3, 10])
    reqs = _mixed_requests(prompts, 8)
    cluster = ServingCluster(_factory(module, params), tmp_path, replicas=2)
    rids = [cluster.submit(r).request_id for r in reqs]
    assert rids == list(range(len(reqs)))
    outs = _drive(cluster)
    cluster.close()
    _assert_parity(module, params, reqs, rids, outs)
    placements = {cluster.placement(rid)[0] for rid in rids}
    assert placements <= {0, 1}
    stats = cluster.router_stats()
    assert stats["cluster/routed_prefix"] == len(reqs)
    assert stats["cluster/healthy_replicas"] == 2
    assert stats["cluster/migrations"] == 0


@_drives_engine
def test_round_robin_placement_alternates(model, tmp_path):
    module, params = model
    cluster = ServingCluster(
        _factory(module, params), tmp_path, replicas=2,
        config=ClusterConfig(policy=POLICY_ROUND_ROBIN))
    prompts = _prompts(1, [4, 6, 4, 6])
    rids = [cluster.submit(Request(p, SamplingParams(max_new_tokens=2)))
            .request_id for p in prompts]
    assert [cluster.placement(r)[0] for r in rids] == [0, 1, 0, 1]
    outs = _drive(cluster)
    cluster.close()
    assert all(outs[r].finish_reason == FINISH_LENGTH for r in rids)
    assert cluster.router_stats()["cluster/routed_round_robin"] == 4


# ------------------------------------------------------------------ routing
@_drives_engine
def test_prefix_routing_follows_trie_affinity(model, tmp_path):
    """A request routes to the replica whose radix trie holds the longest
    cached prefix of its prompt — match beats the load/index tie-break."""
    module, params = model
    cluster = ServingCluster(
        _factory(module, params, prefix_cache=True),
        tmp_path, replicas=2)
    r = np.random.default_rng(3)
    tenant_a = r.integers(0, 256, (16,)).astype(np.int32).tolist()
    tenant_b = r.integers(0, 256, (16,)).astype(np.int32).tolist()
    # seed each replica's trie directly; the probe is what's under test
    cluster.replicas[0].supervisor.submit(
        Request(tenant_a + [1, 2], SamplingParams(max_new_tokens=2)))
    cluster.replicas[1].supervisor.submit(
        Request(tenant_b + [3, 4], SamplingParams(max_new_tokens=2)))
    _drive(cluster)
    probe = tenant_a + [9, 9]
    assert cluster.replicas[0].engine.prefix_cache.match_len(probe) > 0
    assert cluster.replicas[1].engine.prefix_cache.match_len(probe) == 0
    rid_a = cluster.submit(Request(tenant_a + [5, 6],
                                   SamplingParams(max_new_tokens=2))).request_id
    rid_b = cluster.submit(Request(tenant_b + [7, 8],
                                   SamplingParams(max_new_tokens=2))).request_id
    assert cluster.placement(rid_a)[0] == 0
    assert cluster.placement(rid_b)[0] == 1
    _drive(cluster)
    cluster.close()
    assert cluster.router_stats()["cluster/route_match_tokens"] > 0


@_drives_engine
def test_brownout_replica_routed_around(model, tmp_path):
    """A replica in overload brownout stops receiving the admissions its own
    gate would shed — they place on the calm replica instead of bouncing."""
    module, params = model
    cluster = ServingCluster(
        _factory(module, params), tmp_path, replicas=2,
        config=ClusterConfig(policy=POLICY_ROUND_ROBIN),
        supervisor_config=SupervisorConfig(brownout_ttft_s=0.01),
        headroom_fns=[lambda: {"est_slot_free_s": 99.0},
                      lambda: {"est_slot_free_s": 0.0}],
    )
    rid0 = cluster.submit(Request(list(range(1, 5)),
                                  SamplingParams(max_new_tokens=4))).request_id
    assert cluster.placement(rid0)[0] == 0
    cluster.step()  # replica 0's overloaded step raises its brownout level
    assert cluster.replicas[0].supervisor.brownout_level >= 1
    rid1 = cluster.submit(Request(list(range(1, 6)),
                                  SamplingParams(max_new_tokens=2))).request_id
    assert cluster.placement(rid1)[0] == 1  # priority 0 < level: shed there
    outs = _drive(cluster)
    cluster.close()
    assert outs[rid0].finish_reason == FINISH_LENGTH
    assert outs[rid1].finish_reason == FINISH_LENGTH


@_drives_engine
def test_role_gating_prefers_capable_replicas(model, tmp_path):
    """Fresh admissions go to prefill-capable replicas; the decode-only
    replica only takes fresh work when nobody else can."""
    module, params = model
    cluster = ServingCluster(
        _factory(module, params), tmp_path, replicas=2,
        config=ClusterConfig(policy=POLICY_ROUND_ROBIN,
                             roles=(ROLE_DECODE, ROLE_PREFILL)))
    rids = [cluster.submit(Request(list(range(1, 5)),
                                   SamplingParams(max_new_tokens=2)))
            .request_id for _ in range(3)]
    # every fresh admission lands on the prefill replica, never the decode one
    assert [cluster.placement(r)[0] for r in rids] == [1, 1, 1]
    _drive(cluster)
    cluster.close()


# ---------------------------------------------------------------- migration
@_drives_engine
def test_replica_kill_migrates_zero_lost_bit_exact(model, tmp_path):
    """The tentpole contract: a replica kill (restart budget 0) loses zero
    requests and every stream — mid-flight ones resumed on the survivor with
    their emitted prefix — stays bit-for-bit the solo `generate`'s."""
    module, params = model
    cluster = ServingCluster(
        _factory(module, params), tmp_path, replicas=2,
        config=ClusterConfig(policy=POLICY_ROUND_ROBIN),
        supervisor_config=SupervisorConfig(max_restarts=0))
    prompts = _prompts(7, [5, 9, 12, 7])
    reqs = _mixed_requests(prompts, 10)
    rids = [cluster.submit(r).request_id for r in reqs]
    assert [cluster.placement(r)[0] for r in rids] == [0, 1, 0, 1]
    for _ in range(2):  # emit a few tokens on both replicas first
        cluster.step()
    _kill(cluster.replicas[0])
    outs = _drive(cluster)
    cluster.close()
    assert not cluster.replicas[0].healthy
    assert cluster.migrations == 1
    assert cluster.migrated_requests >= 1
    assert sorted(outs) == sorted(rids)  # zero lost, cluster ids stable
    _assert_parity(module, params, reqs, rids, outs)
    hb = cluster.heartbeat()
    assert (hb["healthy"], hb["unhealthy"], hb["migrations"]) == (1, 1, 1)


@_drives_engine
def test_double_kill_remigrates_bit_exact(model, tmp_path):
    """The foreign-journal idiom: migration re-journals the resumed prefix on
    the TARGET replica, so a second kill is just another migration — the
    stream still finishes bit-exact on the third replica."""
    module, params = model
    cluster = ServingCluster(
        _factory(module, params), tmp_path, replicas=3,
        config=ClusterConfig(policy=POLICY_ROUND_ROBIN),
        supervisor_config=SupervisorConfig(max_restarts=0))
    prompt = _prompts(11, [9])[0]
    rid = cluster.submit(Request(list(prompt),
                                 SamplingParams(max_new_tokens=12))).request_id
    assert cluster.placement(rid)[0] == 0
    for _ in range(3):
        cluster.step()
    _kill(cluster.replicas[0])
    outs = dict()
    for o in cluster.step():  # the dying step migrates before returning
        outs[o.request_id] = o
    first_home = cluster.placement(rid)[0]
    assert first_home != 0
    cluster.step()  # progress on the new home
    _kill(cluster.replicas[first_home])
    outs.update(_drive(cluster))
    cluster.close()
    assert cluster.migrations == 2
    assert cluster.placement(rid)[0] not in (0, first_home)
    assert outs[rid].finish_reason == FINISH_LENGTH
    assert outs[rid].tokens == _solo(module, params, prompt, 12)


@_drives_engine
def test_migration_disabled_fails_loud(model, tmp_path):
    """``migrate=False`` keeps the single-supervisor fail-loud behavior: the
    dead replica's backlog comes back ``rejected:unhealthy``, nothing moves."""
    module, params = model
    cluster = ServingCluster(
        _factory(module, params), tmp_path, replicas=2,
        config=ClusterConfig(policy=POLICY_ROUND_ROBIN, migrate=False),
        supervisor_config=SupervisorConfig(max_restarts=0))
    prompts = _prompts(13, [5, 6])
    rids = [cluster.submit(Request(p, SamplingParams(max_new_tokens=8)))
            .request_id for p in prompts]
    cluster.step()
    _kill(cluster.replicas[0])
    outs = _drive(cluster)
    cluster.close()
    assert cluster.migrations == 0
    assert outs[rids[0]].finish_reason == _UNHEALTHY_REASON
    assert outs[rids[1]].finish_reason == FINISH_LENGTH
    assert sorted(outs) == sorted(rids)  # loud, but still zero silently lost


def test_all_replicas_dead_rejects_unhealthy(model, tmp_path):
    module, params = model
    cluster = ServingCluster(
        _factory(module, params), tmp_path, replicas=2,
        config=ClusterConfig(policy=POLICY_ROUND_ROBIN),
        supervisor_config=SupervisorConfig(max_restarts=0))
    rid = cluster.submit(Request([1, 2, 3],
                                 SamplingParams(max_new_tokens=4))).request_id
    _kill(cluster.replicas[0])
    _kill(cluster.replicas[1])
    outs = _drive(cluster)
    cluster.close()
    # with no survivor the backlog is accounted loudly, and new admissions
    # carry the most specific reason the router saw
    assert outs[rid].finish_reason == _UNHEALTHY_REASON
    res = cluster.submit(Request([4, 5], SamplingParams(max_new_tokens=2)))
    assert not res.accepted and res.reason == REJECT_UNHEALTHY


# ------------------------------------------------- scheduler interaction
def test_resumed_requests_never_join_cached_runs():
    """`scheduler._run_key`: a migrated request re-submitted with
    ``prefill_len > 0`` heads its OWN admission run (plain-prefill program),
    and ``capacity_fn`` prices exactly the front run's requests."""
    sched = FIFOScheduler(prompt_buckets=(16, 32), max_queue=16)
    sched.prefill_len_fn = lambda req: req.prefill_len  # cache probing on
    seen = []

    def cap(reqs):
        seen.append([r.request_id for r in reqs])
        return len(reqs)

    sched.capacity_fn = cap
    reqs = [
        Request(list(range(1, 9)), SamplingParams(max_new_tokens=4)),
        Request(list(range(1, 9)), SamplingParams(max_new_tokens=4)),
        Request(list(range(1, 9)), SamplingParams(max_new_tokens=4),
                resume_tokens=[7, 8, 9]),  # the migrated continuation
        Request(list(range(1, 9)), SamplingParams(max_new_tokens=4)),
    ]
    for i, r in enumerate(reqs):
        r.request_id = i
        assert sched.submit(r).accepted
    # the front run stops BEFORE the resumed request: same bucket, different
    # program (cached-gather vs plain prefill)
    assert sched.peek_run(8) == 2
    assert seen[-1] == [0, 1]
    assert [r.request_id for r in sched.pop_run(2)] == [0, 1]
    # the continuation heads its own run of one; capacity prices only it
    assert sched.peek_run(8) == 1
    assert seen[-1] == [2]
    assert [r.request_id for r in sched.pop_run(1)] == [2]
    # and the trailing fresh request never rode the continuation's run
    assert sched.peek_run(8) == 1
    assert seen[-1] == [3]
    # a capacity clamp shrinks the run without touching FIFO order
    sched.capacity_fn = lambda rs: 0
    assert sched.peek_run(8) == 0


# ---------------------------------------------------------------- telemetry
@_drives_engine
def test_cluster_telemetry_replica_namespace(model, tmp_path):
    """One telemetry point carries the aggregated cluster gauges AND each
    replica's own under ``replica<i>/``; the Prometheus render folds the
    prefix into a ``{replica="i"}`` label with one TYPE line per metric."""
    module, params = model
    cluster = ServingCluster(_factory(module, params), tmp_path / "c",
                             replicas=2)
    cluster.submit(Request([1, 2, 3], SamplingParams(max_new_tokens=2)))
    _drive(cluster)
    jsonl = tmp_path / "telemetry.jsonl"
    exporter = TelemetryExporter(TelemetryConfig(interval_s=0.0,
                                                 jsonl_path=jsonl))
    point = exporter.sample(cluster)
    exporter.close()
    cluster.close()
    assert point["cluster/replicas"] == 2
    assert point["serving/requests_finished"] == 1  # the aggregate
    assert "replica0/serving/steps" in point
    assert "replica1/serving/steps" in point
    assert point["replica0/cluster/role"] == "mixed"
    assert jsonl.exists() and jsonl.read_text().count("\n") == 1

    text = to_prometheus_text(
        {k: v for k, v in point.items() if not k.startswith("_")})
    assert text.count("# TYPE accelerate_tpu_serving_steps gauge") == 1
    assert 'accelerate_tpu_serving_steps{replica="0"}' in text
    assert 'accelerate_tpu_serving_steps{replica="1"}' in text
    parsed = parse_prometheus_text(text)
    assert (parsed['accelerate_tpu_serving_steps{replica="0"}']
            == float(point["replica0/serving/steps"]))


@_drives_engine
def test_serve_top_renders_cluster_and_replica_rows(model, tmp_path):
    module, params = model
    cluster = ServingCluster(_factory(module, params), tmp_path / "c",
                             replicas=2)
    cluster.submit(Request([1, 2, 3, 4], SamplingParams(max_new_tokens=2)))
    _drive(cluster)
    jsonl = tmp_path / "telemetry.jsonl"
    exporter = TelemetryExporter(TelemetryConfig(interval_s=0.0,
                                                 jsonl_path=jsonl))
    exporter.sample(cluster)
    exporter.close()
    cluster.close()
    import tools.serve_top as serve_top

    points = serve_top.load_points(str(jsonl))
    screen = serve_top.render(points[-1])
    assert "cluster 2/2 replicas healthy" in screen
    assert "r0 [mixed" in screen and "r1 [mixed" in screen


# -------------------------------------------------------------------- tools
@_drives_engine
def test_journal_fsck_all_audits_cluster_workdir(model, tmp_path):
    module, params = model
    workdir = tmp_path / "cluster"
    cluster = ServingCluster(_factory(module, params), workdir, replicas=2,
                             config=ClusterConfig(policy=POLICY_ROUND_ROBIN))
    for p in _prompts(17, [4, 5]):
        cluster.submit(Request(p, SamplingParams(max_new_tokens=2)))
    _drive(cluster)
    cluster.close()
    import tools.journal_fsck as journal_fsck

    report, code = journal_fsck.fsck_all(str(workdir))
    assert code == 0 and report["clean"]
    assert report["journals"] == 2 and report["clean_journals"] == 2
    assert report["finished"] == 2 and report["in_flight"] == 0
    # a directory with no journals is not auditable state — worst status
    report, code = journal_fsck.fsck_all(str(tmp_path / "nowhere"))
    assert code == 2 and "error" in report


@_drives_engine
def test_trace_report_merges_replica_traces(model, tmp_path):
    tracers = [Tracer(), Tracer()]
    module, params = model
    cluster = ServingCluster(_factory(module, params), tmp_path / "c",
                             replicas=2,
                             config=ClusterConfig(policy=POLICY_ROUND_ROBIN),
                             tracers=tracers)
    for p in _prompts(19, [4, 6]):
        cluster.submit(Request(p, SamplingParams(max_new_tokens=2)))
    _drive(cluster)
    cluster.close()
    paths = []
    for i, t in enumerate(tracers):
        exported = t.export(str(tmp_path / f"replica{i}.trace.json"))
        paths.append(exported["path"])
    import tools.trace_report as trace_report

    combined = trace_report.multi_report(paths)
    assert combined["clean"] and combined["requests"] == 2
    # cross-replica slowest rows carry their origin as an r<i>: prefix
    assert {row["rid"].split(":")[0] for row in combined["slowest"]} == \
        {"r0", "r1"}


# ---------------------------------------------------------- chaos (tier 2)
@pytest.mark.slow
def test_chaos_replica_kill_zero_lost_zero_drift():
    import tools.chaos_serve as chaos_serve

    summary = chaos_serve.run_replica_kill(n_replicas=2, n_requests=8,
                                           concurrency=2)
    assert summary["value"] == 0  # zero lost requests
    assert summary["detail"]["parity_drift"] == 0
    assert summary["detail"]["migrations"] >= 1
    assert summary["detail"]["journals_clean"] == 2


# ------------------------------------------------- front-door stream survival
@_drives_engine
@pytest.mark.frontend
def test_stream_survives_replica_migration_bit_exact(model, tmp_path):
    """The front-door leg of the migration contract: a `TokenStream` opened
    through `ServingFrontend` keeps delivering across a replica kill — the
    tailer re-points to the survivor's journal via `placement()`, the
    re-journaled prefix is absorbed by the exactly-once frontier, and every
    stream finishes bit-for-bit the solo `generate`'s with no duplicated
    and no lost tokens."""
    from accelerate_tpu.serving import ServingFrontend

    module, params = model
    cluster = ServingCluster(
        _factory(module, params), tmp_path, replicas=2,
        config=ClusterConfig(policy=POLICY_ROUND_ROBIN),
        supervisor_config=SupervisorConfig(max_restarts=0))
    fe = ServingFrontend(cluster)
    prompts = _prompts(13, [5, 9, 12, 7])
    reqs = _mixed_requests(prompts, 10)
    streams = [fe.submit_stream(r) for r in reqs]
    assert all(s.result.accepted for s in streams)
    assert [cluster.placement(s.request_id)[0] for s in streams] == [0, 1, 0, 1]
    for _ in range(2):  # emit a few tokens on both replicas first
        cluster.step()
        fe.pump()
    pre_kill = {s.request_id: list(s.delivered) for s in streams}
    assert any(pre_kill.values())  # at least one stream was mid-flight
    _kill(cluster.replicas[0])
    events = {s.request_id: [] for s in streams}
    while cluster.has_work or fe.open_streams():
        cluster.step()
        for ev in fe.pump():
            events[ev.request_id].append(ev)
    cluster.close()
    assert cluster.migrations == 1
    for i, stream in enumerate(streams):
        r = reqs[i]
        assert stream.finished and stream.finish_reason == FINISH_LENGTH
        ref = _solo(module, params, r.prompt, r.params.max_new_tokens,
                    temperature=r.params.temperature, top_k=r.params.top_k,
                    seed=r.params.seed)
        assert stream.delivered == ref, f"stream {stream.request_id} diverged"
        # exactly-once across the migration: pre-kill tokens never re-emitted
        assert stream.delivered[:len(pre_kill[stream.request_id])] == \
            pre_kill[stream.request_id]
        flat = [t for ev in events[stream.request_id] for t in ev.tokens]
        assert pre_kill[stream.request_id] + flat == stream.delivered
        ns = [ev.n for ev in events[stream.request_id]]
        assert ns == sorted(ns)
