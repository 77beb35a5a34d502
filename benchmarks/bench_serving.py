"""Continuous batching vs lockstep `generate`: aggregate tokens/sec on a
Poisson arrival trace of ragged, skewed-length requests.

The lockstep baseline serves the same trace the way `models/generation.generate`
forces: requests grouped into arrival-order batches of ``max_concurrency``,
prompts padded to the batch bucket, every row decoding until the LONGEST
request in the batch finishes. The engine (`serving/ServingEngine`) instead
recycles a slot the moment its request completes — the win measured here is
exactly the padded/lockstep waste, so it grows with the skew of the
``max_new_tokens`` distribution.

The engine runs TWICE — ``pipeline_depth=1`` (synchronous dispatch) and
``pipeline_depth=BENCH_SERVE_DEPTH`` (pipelined) — so the dispatch-overlap win
is measured directly: host-blocked time per decode step (the seconds
``step()`` spends stalled in ``device_get``) must be strictly lower at depth 2,
and inter-token latency p50/p99 ride along with TTFT/tokens-per-sec.

Both sides run one warm pass first (compiles excluded) and count only the
tokens requests actually asked for. Prints ONE machine-readable JSON line:
{"metric": "serving_tokens_per_sec", "value", "unit", "vs_baseline", "detail"}
with vs_baseline = pipelined_tps / lockstep_tps (>1.0 = continuous batching
wins); detail carries engine_depth1/engine_pipelined/lockstep breakdowns.

A second machine-readable row, {"metric": "serving_decode_dispatches_per_token",
...}, measures the fused paged-decode amortization (`docs/serving.md` "Fused
paged decode"): the trace's head runs through paged engines across every
(batch, tokens_per_sync, gather|fused) combination, each sub-row carrying ITL
p50/p99 and dispatches-per-token (decode fetches / generated tokens — the
number ``tokens_per_sync=k`` divides by ~k). value = dispatches-per-token of
the fused engine at the largest ``tokens_per_sync``; vs_baseline = the
single-step gather engine's dispatches-per-token over value (>1.0 = the scan
amortizes). On CPU the fused kernel runs in Pallas interpret mode, so the
sub-rows default to a short head of the trace (``BENCH_SERVE_FUSED_REQUESTS``).

A third machine-readable row, {"metric": "serving_spec_forwards_per_accepted",
...}, measures speculative decoding (`docs/serving.md` "Speculative
decoding"): a prompt-lookup-friendly trace (motif-repeated prompts, greedy)
runs through paged engines across every (batch, draft_k, drafter)
combination, each sub-row carrying accept rate, mean accept length,
per-sequence forwards-per-accepted-token, and ITL p50/p99. value =
forwards-per-accepted-token of the deepest-draft engine (verify forwards one
request costs per emitted token; the PR-12 acceptance bar is < 1.0 —
strictly cheaper than plain decode's exact one-forward-per-token floor);
vs_baseline = the spec-off floor (1.0) over value (>1.0 = drafting
amortizes). `tools/bench_gate.py` treats the metric as lower-is-better via
its ``forwards_per_accepted`` name hint.

Two front-door rows (`docs/serving.md` "Front door") re-run the ragged trace
through a `ServingFrontend` over a journaled, `FairScheduler`-backed engine
with every request STREAMED: {"metric": "serving_goodput_under_slo", ...} —
goodput tokens/sec at the same fixed offered load, with attainment, per-class
attainment, and predictive-admission shed counts in detail — and
{"metric": "serving_streamed_ttft_p99_s", ...} — submit-to-first-STREAMED-
token latency at the caller (engine TTFT plus journal append + tailer
delivery), p50 and stream-lag quantiles in detail. The streamed bytes are
asserted identical to the engine's completed outputs before either row
prints.

Every row stamps ``detail.platform`` explicitly: "cpu-host" when the backend
is CPU (the honest label for host-produced numbers — see ROADMAP.md's
perf-record caveat), the real platform name otherwise.

``BENCH_SERVE_WORKLOAD=prefix`` switches to the shared-system-prompt workload
instead: every request repeats one long system prefix with a short unique
tail (plus a configurable fraction of cold, unique-prefix requests), and the
engine runs twice on the SAME trace — prefix cache off, then on
(`serving/prefix_cache.py`). The JSON line then carries metric
"serving_prefix_cache" with value = prefill-token reduction (fraction of
prompt prefill skipped via reuse; the PR-4 acceptance bar is >= 0.30),
vs_baseline = tokens_per_sec(on) / tokens_per_sec(off), and detail splits
TTFT p50/p99 by cache hit vs miss.

``BENCH_SERVE_WORKLOAD=cluster`` measures the multi-replica router
(`serving/cluster.py`, `docs/serving.md` "Multi-replica serving") and prints
TWO rows. "serving_cluster_tokens_per_sec": a WEAK-scaling sweep — the
ragged trace grows with the replica count (``BENCH_SERVE_REQUESTS`` per
replica, tiled copies of one base trace so the request mix is identical)
and each replica carries the same load at every ``BENCH_SERVE_REPLICAS``
count (default 1,2,4). On one host every replica
shares the same CPU, so the honest claim this row can make is that the
routing layer conserves per-host throughput: value = tokens/sec at the
largest count, vs_baseline = largest / 1-replica (≈ 1.0 = the router adds
no overhead; real fleets give each replica its own accelerator), detail
carries per-count tokens/sec + TTFT mean/p50/p99.
"serving_cluster_prefix_routing_hit_rate": a multi-tenant shared-prefix
trace (``BENCH_SERVE_TENANTS`` distinct system prompts, slow fixed-interval
arrivals so each tenant's prefix is donated before its next request is
routed) through a 2-replica cluster of prefix-cached engines, once under
``policy="prefix"`` and once under ``policy="round_robin"``; value = the
prefix policy's trie hit rate, vs_baseline = prefix hit rate / round-robin
hit rate (>1.0 = affinity routing concentrates each tenant on its cache
holder instead of paying a cold prefill per replica per tenant), detail
carries both policies' hit rates and mean TTFT (`tools/bench_gate.py`
treats the ttft detail keys as lower-is-better via its name hints).

``BENCH_SERVE_WORKLOAD=tiered`` measures the host-RAM KV tier
(`serving/kv_tier.py`, `docs/serving.md` "KV tiering & hibernation"): the
SAME all-at-once ragged trace through two engines with an identical,
deliberately small device block pool — tier off, then
``kv_tier=KVTierConfig(...)`` — tracking peak concurrent in-flight streams
(active slots + hibernated host records) per step. The JSON line carries
metric "serving_tiered_peak_streams" with value = the tier-on peak,
vs_baseline = tier-on / tier-off peak (the PR-16 acceptance bar is
strictly > 1, target >= 2 at a pool the ragged extents saturate), and
detail carries the tier-off ceiling, page-in p99 wall seconds
(``host_tier_page_in_p99_s``), and the page/hibernate/wake counters. The
probe raises the thrash-guard threshold out of reach: spill churn IS the
mechanism under measurement, freezing it would measure the guard instead.

``BENCH_SERVE_WORKLOAD=quant`` measures quantized serving
(`docs/serving.md` "Quantized serving") in TWO rows.
"serving_quant_kv_bytes_per_token": exact nbytes of the paged block pool
(every cache-tree leaf keyed by block index — the KV tier's own sizing
rule) amortized over its token capacity, probed per mode at identical
block geometry; value = the int8 store's bytes/token (int8 payload + fp32
absmax scale planes), vs_baseline = int8 / bf16 (asserted <= 0.55 in the
bench: the scales amortize over block_tokens), detail carries the
fp32/bf16/int8 payload-vs-scale split. `tools/bench_gate.py` treats any
``kv_bytes_per_token`` name as lower-is-better. "serving_quant_peak_streams":
the fp32 pool's byte budget re-spent on int8 blocks — the SAME all-at-once
ragged trace through a tier-off fp32-KV engine and an int8-KV engine whose
pool holds the byte-equal number of int8 blocks (compute dtype fp32 on both
sides, so KV storage is the only variable), tracking peak concurrent
in-flight streams per step; value = the int8 peak, vs_baseline = int8 /
fp32 peak (asserted >= 1.8: quantization is admission capacity).

``BENCH_SERVE_WORKLOAD=surge`` measures the elastic fleet
(`serving/autoscaler.py`, `docs/reliability.md` "Elastic fleet"): a
three-phase trace — baseline load, a ``BENCH_SERVE_SURGE_MULT``× (default
4×) arrival-rate step, then baseline again — runs twice through a
journaled `ServingCluster`: once pinned at 1 replica (the fixed control),
once with a `FleetAutoscaler` allowed up to ``BENCH_SERVE_MAX_REPLICAS``.
Rates and the SLO self-calibrate from a warm measurement pass (offered
baseline ~ a third of the measured single-replica service rate; TTFT SLO =
3x the measured cold-start TTFT floor — what the first request into a
freshly built replica pays for prefill, pipelined delivery, and
per-replica program warmup, a cost both runs' young fleets and every
mid-trace spawn inherit), so the surge genuinely saturates one replica —
and the SLO genuinely binds on its queue — on any host. On
cpu-host the in-process replicas are stepped serially on one CPU, so
scale-out cannot add throughput and ``vs_baseline`` may sit below 1: like
the cluster weak-scaling row, the honest claim here is control behavior —
the fleet scales at the load step, drain-and-retires mid-bench, and loses
nothing — not a single-host goodput win (real fleets give each replica its
own accelerator).
The JSON line carries metric "serving_surge_goodput_under_slo" with value =
the autoscaled run's goodput tokens/sec under SLO, vs_baseline = autoscaled
/ fixed goodput (>1.0 = scaling out absorbs the surge), and detail carries
TTFT p99 + SLO attainment for both runs, scale-up/retire/spawn-retry
counters, and ``lost_requests`` (asserted 0: the trailing baseline phase
makes the drain-and-retire happen MID-BENCH, so zero-loss across retire is
part of the measurement, not a separate test). The fleet must converge back
to ``min_replicas`` after the trace drains before the row prints.
`tools/bench_gate.py` carries the row candidate-only (reported under
``new``, never a regression): goodput under a self-calibrated SLO is too
host-load-sensitive to pin in BENCH_BEST.json, and the stable invariants
(zero lost, convergence, scale-up ≥ 1) are asserted inside the bench run
itself.

Every traced request carries an `SLOSpec`: the short interactive replies get
TTFT + ITL-p99 bounds (class "interactive"), the heavy-tail requests only
need a clean finish (class "batch") — so each engine run's detail carries a
goodput row (`docs/observability.md`): goodput_tokens_per_sec, overall SLO
attainment, and per-class attainment fractions. ``BENCH_SERVE_TRACE=path``
additionally attaches a `serving.Tracer` to the pipelined timed run and
exports its Perfetto-loadable trace-event JSON there (summarize with
``python tools/trace_report.py path``); the BENCH detail then carries the
trace's event/drop/malformed counts. Tracing is off (the zero-overhead
`NULL_TRACER`) unless the knob is set, so the headline numbers are untouched.

Env knobs (defaults saturate an 8-slot engine on the host CPU in ~a minute):
  BENCH_FORCE_CPU=1        run the labelled "cpu-host" rows on the host CPU;
                           without it the bench needs a TPU and exits
                           non-zero when JAX finds none
  BENCH_SERVE_REQUESTS     trace length (default 32; cluster mode: requests
                           PER REPLICA for the weak-scaling row, default 12)
  BENCH_SERVE_CONCURRENCY  engine slots == lockstep batch size (default 8)
  BENCH_SERVE_RATE         Poisson arrival rate, req/s (default 200: saturating;
                           prefix mode defaults to 8 — unsaturated, see above)
  BENCH_SERVE_SEED         trace rng seed (default 0)
  BENCH_SERVE_DEPTH        pipelined run's pipeline_depth (default 2)
  BENCH_SERVE_ADMIT        admit_batch for both engine runs (default 4)
  BENCH_SERVE_WORKLOAD     "ragged" (default) | "prefix" (shared system
                           prompt) | "cluster" (multi-replica router rows) |
                           "tiered" (host-RAM KV tier) | "quant" (int8 KV
                           capacity rows) | "surge" (elastic fleet under a
                           load step)
  BENCH_SERVE_QUANT_BLOCKS quant mode: fp32 pool blocks setting the shared
                           HBM byte budget (default 12)
  BENCH_SERVE_QUANT_SLOTS  quant mode: slot count for both engines, high so
                           the pool, not the slots, binds (default 32)
  BENCH_SERVE_MAX_REPLICAS surge mode: autoscaler ceiling (default 3)
  BENCH_SERVE_SURGE_MULT   surge mode: arrival-rate multiplier for the
                           middle third of the trace (default 4.0)
  BENCH_SERVE_SYNC         comma list of tokens_per_sync values for the fused
                           decode row (default "1,4"; "" skips the row)
  BENCH_SERVE_FUSED_BATCHES  comma list of engine batch sizes for the fused
                           decode row (default: BENCH_SERVE_CONCURRENCY)
  BENCH_SERVE_FUSED_REQUESTS  trace head length for the fused decode row
                           (default 12: interpret-mode Pallas is slow on CPU)
  BENCH_SERVE_SPEC         comma list of speculation draft depths k for the
                           speculation row; 0 = spec-off baseline geometry
                           (default "0,4"; "" skips the row)
  BENCH_SERVE_SPEC_BATCHES comma list of engine batch sizes for the
                           speculation row (default: BENCH_SERVE_CONCURRENCY)
  BENCH_SERVE_SPEC_DRAFTERS  comma list of drafters for the speculation row:
                           "ngram" (prompt lookup, default) and/or "model"
                           (tiny same-vocab draft model)
  BENCH_SERVE_SPEC_REQUESTS  speculation-row trace length (default 12)
  BENCH_SERVE_PREFIX_LEN   prefix-mode shared prompt length (default 64;
                           cluster mode reuses it for the tenant prompts)
  BENCH_SERVE_MISS_FRAC    prefix-mode fraction of cold-prefix requests (0.25)
  BENCH_SERVE_REPLICAS     cluster mode: comma list of replica counts for the
                           scaling row (default "1,2,4")
  BENCH_SERVE_TENANTS      cluster mode: distinct shared prefixes in the
                           routing-policy row's trace (default 5 — odd, so
                           round-robin placement doesn't alias tenants onto
                           fixed replicas on the 2-replica cluster)
  BENCH_SERVE_CLUSTER_DIR  cluster mode: workdir root for the replicas'
                           journals (default: a fresh temp dir, removed after)
  BENCH_SERVE_MESH         mesh sweep instead: comma-separated (data, model)
                           shapes, e.g. "1x1,2x1,1x2,2x2" — the ragged trace
                           runs once per shape through `ServingEngine(mesh=...)`
                           and each shape prints its own machine-readable row
                           (tokens/sec, ITL p50/p99, compile stats) before
                           the final summary
                           line; on CPU the needed virtual devices are forced
  BENCH_SERVE_TRACE        path: export the pipelined timed run's trace-event
                           JSON here (default: tracing off entirely)
  BENCH_SERVE_TELEMETRY    path: attach a `serving.telemetry.TelemetryExporter`
                           to the pipelined timed run — per-step JSONL
                           time-series here, Prometheus text at path + ".prom"
                           (view with `python tools/serve_top.py path`;
                           default: telemetry off entirely)

Run: JAX_PLATFORMS=cpu python benchmarks/bench_serving.py
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from accelerate_tpu.models.generation import generate
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.serving import (
    Request,
    SamplingParams,
    ServingEngine,
    SLOSpec,
    Tracer,
)

BUCKETS = (16, 32, 48)

# SLO classes for the goodput row: short interactive replies carry latency
# bounds (generous enough that a healthy warm engine attains them on the host
# CPU — the row exists to surface regressions, not to fail by construction);
# the heavy-tail batch requests only need to finish cleanly.
SLO_INTERACTIVE = SLOSpec(ttft_s=30.0, itl_p99_s=5.0, name="interactive")
SLO_BATCH = SLOSpec(name="batch")


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _platform_or_exit(n_devices: int | None = None) -> None:
    """This bench times a server, so it runs on the TPU or not at all: without
    a chip it exits non-zero. ``BENCH_FORCE_CPU=1`` asks by name for the
    "cpu-host" rows (counts and control flow; their seconds are the host's),
    on ``n_devices`` forced host devices."""
    from accelerate_tpu.utils.environment import configure_compile_cache, require_tpu

    if os.environ.get("BENCH_FORCE_CPU", "0") == "1":
        from accelerate_tpu.test_utils.platform import force_cpu_platform

        force_cpu_platform(n_devices)
    else:
        require_tpu("benchmarks/bench_serving.py",
                    rehearse="BENCH_FORCE_CPU=1 (the labelled cpu-host rows)")
    configure_compile_cache()


def _host_platform() -> str:
    """Explicit platform stamp for the BENCH rows: the honest label for
    CPU-produced numbers is "cpu-host" (these rows were measured on the host,
    not an accelerator — ROADMAP.md's perf-record caveat), anything else is
    the backend's real platform name."""
    platform = jax.devices()[0].platform
    return "cpu-host" if platform == "cpu" else platform


def _trace(n: int, rate: float, seed: int, vocab: int) -> list[Request]:
    """Poisson arrivals, ragged prompts (4..48), skewed decode lengths: mostly
    short replies with a heavy tail (the distribution continuous batching is
    for — a uniform one would understate the lockstep waste)."""
    r = np.random.default_rng(seed)
    t, reqs = 0.0, []
    for _ in range(n):
        t += float(r.exponential(1.0 / rate))
        prompt_len = int(r.integers(4, BUCKETS[-1] + 1))
        short = r.random() < 0.75
        max_new = int(r.integers(2, 7)) if short else int(r.integers(32, 49))
        reqs.append(Request(
            prompt=r.integers(0, vocab, (prompt_len,)).astype(np.int32).tolist(),
            params=SamplingParams(max_new_tokens=max_new),
            arrival_time=t,
            slo=SLO_INTERACTIVE if short else SLO_BATCH,
        ))
    return reqs


def _run_engine(engine, trace) -> tuple[float, float, dict]:
    engine.metrics.reset_rate_window()  # this run's phase only
    t0 = time.perf_counter()
    pending = list(trace)
    done = 0
    while pending or engine.has_work:
        now = time.perf_counter() - t0
        while pending and pending[0].arrival_time <= now:
            req = pending.pop(0)
            engine.submit(Request(req.prompt, req.params, slo=req.slo))
        done += len(engine.step())
        if not engine.has_work and pending:
            # idle until the next arrival (sub-ms at a saturating rate)
            time.sleep(max(0.0, pending[0].arrival_time - (time.perf_counter() - t0)))
    dt = time.perf_counter() - t0
    tokens = sum(r.params.max_new_tokens for r in trace)
    assert done == len(trace)
    m = engine.metrics
    steps = max(m.steps.value, 1)
    gp = m.goodput()
    return tokens / dt, dt, {
        "ttft_p50_s": round(m.ttft_s.quantile(0.5), 4),
        "itl_p50_s": round(m.inter_token_s.quantile(0.5), 5),
        "itl_p99_s": round(m.inter_token_s.quantile(0.99), 5),
        # THE pipelining number: seconds/step the host spent stalled in
        # device_get (total blocked time normalized by decode steps, so
        # depth-1 and depth-2 runs compare directly)
        "host_blocked_per_step_s": round(m.host_blocked_s.sum / steps, 6),
        "slot_occupancy_mean": round(m.slot_occupancy.mean, 3),
        "steps": m.steps.value,
        "goodput_tokens_per_sec": round(gp["goodput_tokens_per_sec"], 2),
        "slo_attainment": round(gp["slo_attainment"], 4),
        "slo_classes": {name: round(c["attainment"], 4)
                        for name, c in gp["classes"].items()},
    }


def _run_lockstep(module, params, trace, concurrency) -> tuple[float, float, dict]:
    """Arrival-order batches of `concurrency`; prompts right-padded to the
    batch bucket (generate's equal-length contract), everyone decodes until the
    batch's longest request finishes. Arrival gaps are ignored — strictly
    favorable to the baseline."""
    t0 = time.perf_counter()
    decoded = 0
    for i in range(0, len(trace), concurrency):
        batch = trace[i:i + concurrency]
        bucket = next(b for b in BUCKETS if max(len(r.prompt) for r in batch) <= b)
        ids = np.zeros((len(batch), bucket), np.int32)
        for row, r in enumerate(batch):
            ids[row, :len(r.prompt)] = r.prompt
        steps = max(r.params.max_new_tokens for r in batch)
        out = generate(module, params, jnp.asarray(ids), max_new_tokens=steps)
        jax.block_until_ready(out)
        decoded += out.size
    dt = time.perf_counter() - t0
    tokens = sum(r.params.max_new_tokens for r in trace)
    return tokens / dt, dt, {"decoded_tokens": decoded, "requested_tokens": tokens}


def _frontend_row(module, params, trace, concurrency, depth, admit) -> None:
    """The front-door rows (docs/serving.md "Front door"): the SAME ragged
    trace at the SAME fixed offered load as the headline row, but submitted
    through a `ServingFrontend` over a journaled, fair-scheduled engine with
    every request STREAMED (`submit_stream` + a per-step `pump()`). Interactive
    requests ride priority class 1, batch class 0, tenants alternating — so
    the row exercises the class scheduler under load, not just the transport.

    Two machine-readable rows. "serving_goodput_under_slo": goodput tokens/sec
    over the streamed run, vs_baseline = goodput over raw delivered throughput
    (the SLO-weighted fraction; 1.0 = every token came from an attaining
    request), detail carries attainment, per-class attainment, and predictive
    shed counts. "serving_streamed_ttft_p99_s": submit -> first streamed token
    AT THE CALLER — the engine's own TTFT plus journal append + tailer
    delivery — with p50 and the stream-lag quantiles in detail
    (`tools/bench_gate.py` treats both the metric and the detail keys as
    lower-is-better via its ttft/_s name hints).

    The streamed bytes are asserted identical to the engine's completed
    outputs — the bit-for-bit contract the front door keeps."""
    from accelerate_tpu.serving import (
        FairScheduler,
        ServingFrontend,
        ServingMetrics,
        SubmitOptions,
    )

    workdir = tempfile.mkdtemp(prefix="bench_frontend_")
    try:
        engine = ServingEngine(
            module, params, max_concurrency=concurrency,
            prompt_buckets=BUCKETS, max_queue=len(trace) + 1,
            pipeline_depth=depth, admit_batch=admit,
            scheduler=FairScheduler(),
            journal=os.path.join(workdir, "journal.bin"))
        _run_engine(engine, trace)  # warm pass: every compile lands here
        engine.metrics = ServingMetrics()
        if engine.journal is not None:
            engine.journal.metrics = engine.metrics

        frontend = ServingFrontend(engine)
        t0 = time.perf_counter()
        pending = list(trace)
        streams = []
        shed = 0
        completed: dict[int, list[int]] = {}
        while pending or engine.has_work or frontend.open_streams():
            now = time.perf_counter() - t0
            while pending and pending[0].arrival_time <= now:
                req = pending.pop(0)
                interactive = req.slo is SLO_INTERACTIVE
                stream = frontend.submit_stream(
                    list(req.prompt), req.params,
                    SubmitOptions(priority=1 if interactive else 0,
                                  tenant=f"t{len(streams) % 2}", slo=req.slo))
                if stream.result.accepted:
                    streams.append(stream)
                else:
                    # generous trace SLOs make predictive sheds rare here,
                    # but they are part of the row's story, not an error
                    assert stream.result.reason == "predicted_ttft", \
                        (stream.result.reason, stream.result.detail)
                    shed += 1
            for out in engine.step():
                completed[out.request_id] = list(out.tokens)
            frontend.pump()
            if not engine.has_work and pending:
                time.sleep(max(0.0, pending[0].arrival_time
                               - (time.perf_counter() - t0)))
        dt = time.perf_counter() - t0

        m = engine.metrics
        for stream in streams:  # bit-for-bit: streamed == completed-output
            assert stream.finished, stream.request_id
            assert stream.delivered == completed[stream.request_id], \
                stream.request_id
        delivered_tokens = sum(len(s.delivered) for s in streams)
        tps = delivered_tokens / dt
        gp = m.goodput()
        print(json.dumps({
            "metric": "serving_goodput_under_slo",
            "value": round(gp["goodput_tokens_per_sec"], 2),
            "unit": "tokens/s",
            "vs_baseline": round(gp["goodput_tokens_per_sec"]
                                 / max(tps, 1e-9), 3),
            "detail": {
                "platform": _host_platform(),
                "requests": len(trace),
                "offered_rate_req_per_s": float(
                    os.environ.get("BENCH_SERVE_RATE", 200.0)),
                "concurrency": concurrency,
                "pipeline_depth": depth,
                "admit_batch": admit,
                "scheduler": "fair",
                "streams": len(streams),
                "shed_predicted": shed,
                "tokens_per_sec": round(tps, 2),
                "wall_s": round(dt, 3),
                "slo_attainment": round(gp["slo_attainment"], 4),
                "slo_classes": {name: round(c["attainment"], 4)
                                for name, c in gp["classes"].items()},
                "stream_events": m.stream_events.value,
            },
        }), flush=True)
        print(json.dumps({
            "metric": "serving_streamed_ttft_p99_s",
            "value": round(m.streamed_ttft_s.quantile(0.99), 4),
            "unit": "s",
            "detail": {
                "platform": _host_platform(),
                "streams": len(streams),
                "streamed_ttft_p50_s": round(m.streamed_ttft_s.quantile(0.5), 4),
                "engine_ttft_p50_s": round(m.ttft_s.quantile(0.5), 4),
                "engine_ttft_p99_s": round(m.ttft_s.quantile(0.99), 4),
                "stream_lag_p50_s": round(m.stream_lag_s.quantile(0.5), 5),
                "stream_lag_p99_s": round(m.stream_lag_s.quantile(0.99), 5),
                "byte_identical_streams": len(streams),
            },
        }), flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _fused_decode_row(module, params, cfg, trace, concurrency, depth,
                      admit) -> None:
    """The fused-decode amortization rows: the SAME trace head through paged
    engines across (batch, tokens_per_sync, gather|fused). The number under
    test is dispatches-per-token — decode fetches over generated tokens —
    which ``tokens_per_sync=k`` must divide by ~k (one jitted `lax.scan` runs
    k decode iterations per host sync); ITL p50/p99 ride along so the scan's
    latency cost is visible next to its dispatch win. Warm pass first per
    engine, timed pass on fresh metrics (same contract as the headline row)."""
    from accelerate_tpu.serving import PagedKVConfig, ServingMetrics

    syncs = tuple(int(s) for s in
                  os.environ.get("BENCH_SERVE_SYNC", "1,4").split(",") if s)
    if not syncs:
        return
    batches = tuple(int(b) for b in os.environ.get(
        "BENCH_SERVE_FUSED_BATCHES", str(concurrency)).split(",") if b)
    head = trace[:_env_int("BENCH_SERVE_FUSED_REQUESTS", 12)]
    block_tokens = 16
    rows: dict[str, dict] = {}
    for batch in batches:
        for sync in syncs:
            for pa in ("gather", "fused"):
                engine = ServingEngine(
                    module, params, max_concurrency=batch,
                    prompt_buckets=BUCKETS, max_queue=len(head) + 1,
                    pipeline_depth=depth, admit_batch=admit,
                    paged_kv=PagedKVConfig(
                        block_tokens=block_tokens,
                        num_blocks=batch * cfg.n_positions // block_tokens),
                    tokens_per_sync=sync, paged_attention=pa)
                _run_engine(engine, head)  # warm: compiles land here
                engine.metrics = ServingMetrics()
                tps, dt, detail = _run_engine(engine, head)
                m = engine.metrics
                tokens = max(m.tokens_generated.value, 1)
                row = {
                    "row": "serving_fused_decode",
                    "batch": batch,
                    "tokens_per_sync": sync,
                    "paged_attention": pa,
                    "tokens_per_sec": round(tps, 2),
                    "wall_s": round(dt, 3),
                    "itl_p50_s": detail["itl_p50_s"],
                    "itl_p99_s": detail["itl_p99_s"],
                    "dispatches_per_token": round(
                        m.tokens_per_dispatch.count / tokens, 4),
                    "tokens_per_dispatch_mean": round(
                        m.tokens_per_dispatch.mean, 3),
                    "steps": detail["steps"],
                }
                rows[f"b{batch}_sync{sync}_{pa}"] = row
                print(json.dumps(row), flush=True)
    base = rows[f"b{batches[0]}_sync{syncs[0]}_gather"]
    headline = rows[f"b{batches[0]}_sync{max(syncs)}_fused"]
    print(json.dumps({
        "metric": "serving_decode_dispatches_per_token",
        "value": headline["dispatches_per_token"],
        "unit": "dispatches/token",
        "vs_baseline": round(base["dispatches_per_token"]
                             / max(headline["dispatches_per_token"], 1e-9), 3),
        "detail": {
            "platform": _host_platform(),
            "requests": len(head),
            "admit_batch": admit,
            "pipeline_depth": depth,
            "itl_p50_gather_sync1_s": base["itl_p50_s"],
            "itl_p50_fused_max_sync_s": headline["itl_p50_s"],
            "rows": rows,
        },
    }), flush=True)


def _spec_trace(n: int, rate: float, seed: int, vocab: int) -> list[Request]:
    """Prompt-lookup-friendly workload: each prompt is a short random motif
    repeated a few times, so the n-gram drafter's suffix match keeps finding
    the continuation inside the request's own history — the self-similar
    regime (templated replies, code edits, summarization) speculation is for.
    Greedy throughout: sampled slots draft nothing by design, so a sampled
    trace would measure the drafter's idle path, not its win."""
    r = np.random.default_rng(seed)
    t, reqs = 0.0, []
    for _ in range(n):
        t += float(r.exponential(1.0 / rate))
        motif = r.integers(0, vocab, (int(r.integers(3, 7)),)).astype(np.int32).tolist()
        prompt = (motif * int(r.integers(3, 6)))[:BUCKETS[-1]]
        reqs.append(Request(
            prompt=prompt,
            params=SamplingParams(max_new_tokens=int(r.integers(16, 33))),
            arrival_time=t,
        ))
    return reqs


def _speculation_row(module, params, cfg, concurrency, depth, admit) -> None:
    """The speculative-decoding rows: the SAME prompt-lookup-friendly trace
    through paged engines (block-table rollback is the production path —
    docs/serving.md "Speculative decoding") across every (batch, draft_k,
    drafter) combination. The number under test is forwards-per-accepted-token
    PER SLOT SEQUENCE — how many verify forwards one request costs per emitted
    token — which drafting must push BELOW the 1.0 one-forward-one-token floor
    of plain decode. The floor is exact by construction (spec off, a slot
    emits exactly one token per dispatch it participates in), and the spec
    rows measure it as emitted tokens over per-slot verify participations
    (`spec_accept_len`'s observation count — every healthy greedy slot in a
    spec dispatch observes exactly once, and this trace is all-greedy).
    Batch-level ``accepted_tokens_per_dispatch`` (the snapshot's
    ``serving/accepted_tokens_per_forward`` view, where one dispatch batches
    all slots) rides along, with accept rate and ITL p50/p99 (a rejected deep
    draft shows up as latency, never as drift: verification is exact). Warm
    pass first per engine, timed pass on fresh metrics (same contract as the
    headline row)."""
    from accelerate_tpu.serving import (
        ModelDrafter,
        PagedKVConfig,
        ServingMetrics,
        SpeculationConfig,
    )

    ks = tuple(int(s) for s in
               os.environ.get("BENCH_SERVE_SPEC", "0,4").split(",") if s)
    if not ks:
        return
    batches = tuple(int(b) for b in os.environ.get(
        "BENCH_SERVE_SPEC_BATCHES", str(concurrency)).split(",") if b)
    drafters = tuple(d.strip() for d in os.environ.get(
        "BENCH_SERVE_SPEC_DRAFTERS", "ngram").split(",") if d.strip())
    trace = _spec_trace(_env_int("BENCH_SERVE_SPEC_REQUESTS", 12),
                        float(os.environ.get("BENCH_SERVE_RATE", 200.0)),
                        _env_int("BENCH_SERVE_SEED", 0), cfg.vocab_size)
    block_tokens = 16
    draft_pair = None

    def speculation_arg(k: int, name: str):
        if name == "model":
            # tiny same-vocab draft model: the point is the mechanism's cost
            # accounting (two models, one verify), not a trained drafter's
            # accept rate — untrained draft/target pairs agree rarely
            nonlocal draft_pair
            if draft_pair is None:
                dcfg = GPT2Config(
                    vocab_size=cfg.vocab_size, n_positions=cfg.n_positions,
                    n_embd=128, n_layer=2, n_head=4,
                    dtype=jnp.float32, param_dtype=jnp.float32)
                dmod = GPT2LMHead(dcfg)
                draft_pair = (dmod, dmod.init_params(jax.random.key(1)))
            return SpeculationConfig(draft_tokens=k, drafter=ModelDrafter(
                draft_pair[0], draft_pair[1], draft_tokens=k))
        return k

    rows: dict[str, dict] = {}
    for batch in batches:
        for k in ks:
            for name in (drafters if k else ("off",)):
                engine = ServingEngine(
                    module, params, max_concurrency=batch,
                    prompt_buckets=BUCKETS, max_queue=len(trace) + 1,
                    pipeline_depth=depth, admit_batch=admit,
                    paged_kv=PagedKVConfig(
                        block_tokens=block_tokens,
                        num_blocks=batch * cfg.n_positions // block_tokens),
                    speculation=speculation_arg(k, name) if k else None)
                _run_engine(engine, trace)  # warm: compiles land here
                engine.metrics = ServingMetrics()
                tps, dt, detail = _run_engine(engine, trace)
                m = engine.metrics
                if k:
                    # per-slot: one verify participation per healthy greedy
                    # slot per dispatch (== one spec_accept_len observation)
                    slot_forwards = m.spec_accept_len.count
                    fpt = slot_forwards / max(m.spec_tokens.value, 1)
                    per_dispatch = m.spec_tokens.value / max(
                        m.spec_forwards.value, 1)
                else:
                    # spec off with tokens_per_sync=1: a slot emits exactly
                    # one token per dispatch it joins — the floor is exact
                    fpt = 1.0
                    per_dispatch = m.tokens_per_dispatch.mean
                row = {
                    "row": "serving_speculation",
                    "batch": batch,
                    "draft_k": k,
                    "drafter": name,
                    "tokens_per_sec": round(tps, 2),
                    "wall_s": round(dt, 3),
                    "itl_p50_s": detail["itl_p50_s"],
                    "itl_p99_s": detail["itl_p99_s"],
                    "accept_rate": round(
                        m.spec_accepted.value / max(m.spec_proposed.value, 1), 4)
                        if k else None,
                    "spec_accept_len_mean": round(m.spec_accept_len.mean, 3)
                        if k else None,
                    "forwards_per_accepted_token": round(fpt, 4),
                    "accepted_tokens_per_dispatch": round(per_dispatch, 3),
                    "steps": detail["steps"],
                }
                rows[f"b{batch}_k{k}_{name}"] = row
                print(json.dumps(row), flush=True)

    spec_ks = [k for k in ks if k]
    if not spec_ks:
        return
    headline = rows[f"b{batches[0]}_k{max(spec_ks)}_{drafters[0]}"]
    base = rows.get(f"b{batches[0]}_k0_off")
    print(json.dumps({
        "metric": "serving_spec_forwards_per_accepted",
        "value": headline["forwards_per_accepted_token"],
        "unit": "forwards/token",
        # >1.0 = speculation amortizes: the spec-off engine spends this many
        # times more verify forwards per emitted token than the drafted one
        "vs_baseline": round(
            base["forwards_per_accepted_token"]
            / max(headline["forwards_per_accepted_token"], 1e-9), 3)
            if base else None,
        "detail": {
            "platform": _host_platform(),
            "requests": len(trace),
            "admit_batch": admit,
            "pipeline_depth": depth,
            "accept_rate": headline["accept_rate"],
            "spec_accept_len_mean": headline["spec_accept_len_mean"],
            "accepted_tokens_per_dispatch":
                headline["accepted_tokens_per_dispatch"],
            "itl_p50_spec_s": headline["itl_p50_s"],
            "itl_p50_off_s": base["itl_p50_s"] if base else None,
            "rows": rows,
        },
    }), flush=True)


def _prefix_trace(n: int, rate: float, seed: int, vocab: int, prefix_len: int,
                  miss_frac: float) -> list[Request]:
    """Shared-system-prompt workload: every hot request is one common
    ``prefix_len``-token prefix plus a 4..12-token unique tail; a
    ``miss_frac`` fraction carries a unique cold prefix instead (so hit and
    miss TTFT populations both exist in one measured window)."""
    r = np.random.default_rng(seed)
    shared = r.integers(0, vocab, (prefix_len,)).astype(np.int32).tolist()
    t, reqs = 0.0, []
    for i in range(n):
        t += float(r.exponential(1.0 / rate))
        tail = r.integers(0, vocab, (int(r.integers(4, 13)),)).astype(np.int32).tolist()
        if r.random() < miss_frac:
            head = r.integers(0, vocab, (prefix_len,)).astype(np.int32).tolist()
        else:
            head = shared
        reqs.append(Request(
            prompt=head + tail,
            params=SamplingParams(max_new_tokens=int(r.integers(8, 17))),
            arrival_time=t,
        ))
    return reqs


def main_prefix() -> None:
    from accelerate_tpu.serving import ServingMetrics

    n_requests = _env_int("BENCH_SERVE_REQUESTS", 32)
    concurrency = _env_int("BENCH_SERVE_CONCURRENCY", 8)
    # unsaturated on purpose (vs the ragged workload's 200/s): at saturation
    # TTFT is queue wait, which buries the prefill-latency delta prefix reuse
    # exists to shrink — the hit/miss split is only meaningful off-saturation
    rate = float(os.environ.get("BENCH_SERVE_RATE", 8.0))
    seed = _env_int("BENCH_SERVE_SEED", 0)
    depth = _env_int("BENCH_SERVE_DEPTH", 2)
    admit = _env_int("BENCH_SERVE_ADMIT", 4)
    prefix_len = _env_int("BENCH_SERVE_PREFIX_LEN", 64)
    miss_frac = float(os.environ.get("BENCH_SERVE_MISS_FRAC", 0.25))

    cfg = GPT2Config(vocab_size=2048, n_positions=128, n_embd=512, n_layer=6,
                     n_head=8, dtype=jnp.float32, param_dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    buckets = (16, prefix_len + 16)  # hit suffixes vs full/cold prompts
    trace = _prefix_trace(n_requests, rate, seed, cfg.vocab_size, prefix_len,
                          miss_frac)
    # warm trace: same shared prefix, DIFFERENT cold prefixes and tails — it
    # compiles every (suffix_bucket, batch_bucket) program and warms the trie
    # with the shared prefix, without pre-caching the timed trace's cold heads
    warm = _prefix_trace(n_requests, rate, seed + 1, cfg.vocab_size, prefix_len,
                         miss_frac)

    def timed(prefix_cache):
        engine = ServingEngine(
            module, params, max_concurrency=concurrency,
            prompt_buckets=buckets, max_queue=len(trace) + 1,
            pipeline_depth=depth, admit_batch=admit, prefix_cache=prefix_cache,
        )
        _run_engine(engine, warm)
        engine.metrics = ServingMetrics()
        if engine.prefix_cache is not None:
            engine.prefix_cache.metrics = engine.metrics
        tps, dt, detail = _run_engine(engine, trace)
        return tps, dt, detail, engine.metrics

    off_tps, off_dt, off_detail, off_m = timed(False)
    on_tps, on_dt, on_detail, on_m = timed(True)
    skipped = off_m.prefill_tokens.value - on_m.prefill_tokens.value
    reduction = skipped / max(off_m.prefill_tokens.value, 1)

    print(json.dumps({
        "metric": "serving_prefix_cache",
        "value": round(reduction, 4),
        "unit": "prefill_tokens_skipped_frac",
        "vs_baseline": round(on_tps / off_tps, 3),
        "detail": {
            "platform": _host_platform(),
            "requests": n_requests,
            "concurrency": concurrency,
            "prefix_len": prefix_len,
            "miss_frac": miss_frac,
            "pipeline_depth": depth,
            "admit_batch": admit,
            "prefill_tokens_cache_off": off_m.prefill_tokens.value,
            "prefill_tokens_cache_on": on_m.prefill_tokens.value,
            "prefill_tokens_skipped": skipped,
            "prefix_hits": on_m.prefix_hits.value,
            "prefix_misses": on_m.prefix_misses.value,
            "prefix_tokens_reused": on_m.prefix_tokens_reused.value,
            "prefix_blocks_donated": on_m.prefix_blocks_donated.value,
            "prefix_evictions": on_m.prefix_evictions.value,
            "ttft_hit_p50_s": round(on_m.ttft_hit_s.quantile(0.5), 5),
            "ttft_hit_p99_s": round(on_m.ttft_hit_s.quantile(0.99), 5),
            "ttft_miss_p50_s": round(on_m.ttft_miss_s.quantile(0.5), 5),
            "ttft_miss_p99_s": round(on_m.ttft_miss_s.quantile(0.99), 5),
            "ttft_p50_cache_off_s": round(off_m.ttft_s.quantile(0.5), 5),
            "cache_on": {"tokens_per_sec": round(on_tps, 2),
                         "wall_s": round(on_dt, 3), **on_detail},
            "cache_off": {"tokens_per_sec": round(off_tps, 2),
                          "wall_s": round(off_dt, 3), **off_detail},
        },
    }), flush=True)


def _run_cluster(cluster, trace) -> tuple[float, float, dict]:
    """`_run_engine` at the cluster surface: same arrival pacing, same
    accounting, but TTFT/occupancy come from the cluster's aggregated
    snapshot (`serving/metrics.py` aggregate_snapshots) instead of one
    engine's metrics object."""
    for rep in cluster.replicas:
        rep.metrics.reset_rate_window()
    t0 = time.perf_counter()
    pending = list(trace)
    done = 0
    while pending or cluster.has_work:
        now = time.perf_counter() - t0
        while pending and pending[0].arrival_time <= now:
            req = pending.pop(0)
            res = cluster.submit(Request(req.prompt, req.params, slo=req.slo))
            assert res.accepted, (res.reason, res.detail)
        done += len(cluster.step())
        if not cluster.has_work and pending:
            time.sleep(max(0.0, pending[0].arrival_time - (time.perf_counter() - t0)))
    dt = time.perf_counter() - t0
    tokens = sum(r.params.max_new_tokens for r in trace)
    assert done == len(trace)
    snap = cluster.metrics.snapshot()
    return tokens / dt, dt, {
        "ttft_mean_s": round(snap.get("serving/ttft_s/mean", 0.0), 4),
        "ttft_p50_s": round(snap.get("serving/ttft_s/p50", 0.0), 4),
        "ttft_p99_s": round(snap.get("serving/ttft_s/p99", 0.0), 4),
        "itl_p50_s": round(snap.get("serving/inter_token_s/p50", 0.0), 5),
        "prefix_hits": int(snap.get("serving/prefix_hits", 0)),
        "prefix_misses": int(snap.get("serving/prefix_misses", 0)),
        "routed_prefix": int(snap.get("cluster/routed_prefix", 0)),
        "routed_round_robin": int(snap.get("cluster/routed_round_robin", 0)),
        "route_match_tokens": int(snap.get("cluster/route_match_tokens", 0)),
        "steps": int(snap.get("serving/steps", 0)),
    }


def _tenant_trace(n: int, rate: float, seed: int, vocab: int, prefix_len: int,
                  tenants: int) -> list[Request]:
    """Multi-tenant `_prefix_trace`: ``tenants`` distinct shared prefixes,
    requests round-robining over them. Prefix-aware placement keeps each
    tenant's stream on the replica whose trie holds its prefix; round-robin
    placement scatters every tenant across all replicas, so each replica
    pays its own cold prefill per tenant — the hit-rate delta this row
    measures. Arrivals are FIXED-interval (1/rate apart), not Poisson: the
    row needs "a tenant's prefix is donated before that tenant returns" to
    hold by construction, and an exponential gap puts a fat left tail on
    exactly that precondition."""
    r = np.random.default_rng(seed)
    prefixes = [r.integers(0, vocab, (prefix_len,)).astype(np.int32).tolist()
                for _ in range(tenants)]
    t, reqs = 0.0, []
    for i in range(n):
        t += 1.0 / rate
        tail = r.integers(0, vocab, (int(r.integers(4, 13)),)).astype(np.int32).tolist()
        reqs.append(Request(
            prompt=prefixes[i % tenants] + tail,
            params=SamplingParams(max_new_tokens=int(r.integers(8, 17))),
            arrival_time=t,
        ))
    return reqs


def main_cluster() -> None:
    from accelerate_tpu.serving import (
        ClusterConfig,
        ServingCluster,
    )

    # requests PER REPLICA: the scaling row is a weak-scaling sweep, so the
    # trace grows with the count and every replica carries the same load
    n_requests = _env_int("BENCH_SERVE_REQUESTS", 12)
    concurrency = _env_int("BENCH_SERVE_CONCURRENCY", 4)
    rate = float(os.environ.get("BENCH_SERVE_RATE", 200.0))
    seed = _env_int("BENCH_SERVE_SEED", 0)
    depth = _env_int("BENCH_SERVE_DEPTH", 2)
    admit = _env_int("BENCH_SERVE_ADMIT", 4)
    prefix_len = _env_int("BENCH_SERVE_PREFIX_LEN", 64)
    # odd on purpose: with 2 replicas an even tenant count aliases every
    # tenant onto one fixed replica under round-robin (i % tenants and
    # i % 2 never decouple), hiding the miss cost affinity routing avoids
    tenants = _env_int("BENCH_SERVE_TENANTS", 5)
    counts = [int(tok) for tok in
              os.environ.get("BENCH_SERVE_REPLICAS", "1,2,4").split(",") if tok]

    cfg = GPT2Config(vocab_size=2048, n_positions=128, n_embd=512, n_layer=6,
                     n_head=8, dtype=jnp.float32, param_dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))

    base_dir = os.environ.get("BENCH_SERVE_CLUSTER_DIR")
    tmp_dir = None
    if base_dir is None:
        tmp_dir = base_dir = tempfile.mkdtemp(prefix="bench_cluster_")

    def timed_cluster(tag, n_reps, warm, trace, factory, policy):
        # warm cluster compiles every program (replicas share module/params,
        # so the process jit cache carries over); the timed cluster starts
        # with clean metrics, clean tries, and a fresh journal workdir
        results = None
        for phase, tr in (("warm", warm), ("timed", trace)):
            cluster = ServingCluster(
                factory, os.path.join(base_dir, f"{tag}-{phase}"),
                replicas=n_reps, config=ClusterConfig(policy=policy))
            try:
                results = _run_cluster(cluster, tr)
            finally:
                cluster.close()
        return results

    try:
        # --- row 1: weak-scaling sweep on the ragged trace ----------------
        # trace size grows with the count so per-replica load is constant;
        # on one shared-CPU host the replicas split the same device, so the
        # honest claim is throughput CONSERVATION (vs_baseline ~ 1.0 = the
        # router adds no overhead), not compute scaling. The per-count trace
        # TILES one base trace (fresh arrival clock, same prompts/budgets)
        # so every count serves the identical request mix — independent
        # draws at small n skew the short/heavy split and fake a scaling
        # win or loss
        max_queue = n_requests * max(counts) + 1
        base = _trace(n_requests, rate, seed, cfg.vocab_size)
        warm_base = _trace(n_requests, rate, seed + 1, cfg.vocab_size)

        def tiled(breqs, n_copies, arrival_seed):
            r = np.random.default_rng(arrival_seed)
            t, out = 0.0, []
            for _ in range(n_copies):
                for req in breqs:
                    t += float(r.exponential(1.0 / rate))
                    out.append(Request(req.prompt, req.params,
                                       arrival_time=t, slo=req.slo))
            return out

        def slot_factory(**kw):
            return ServingEngine(
                module, params, max_concurrency=concurrency,
                prompt_buckets=BUCKETS, max_queue=max_queue,
                pipeline_depth=depth, admit_batch=admit, **kw)

        scale_rows: dict[str, dict] = {}
        for n_reps in counts:
            trace = tiled(base, n_reps, seed)
            warm = tiled(warm_base, n_reps, seed + 1)
            tps, dt, detail = timed_cluster(
                f"scale{n_reps}", n_reps, warm, trace, slot_factory,
                ClusterConfig().policy)
            scale_rows[str(n_reps)] = {
                "tokens_per_sec": round(tps, 2), "wall_s": round(dt, 3),
                "requests": len(trace), **detail}
        first = scale_rows[str(counts[0])]["tokens_per_sec"]
        last = scale_rows[str(counts[-1])]
        print(json.dumps({
            "metric": "serving_cluster_tokens_per_sec",
            "value": last["tokens_per_sec"],
            "unit": "tokens/s",
            "vs_baseline": round(last["tokens_per_sec"] / max(first, 1e-9), 3),
            "detail": {
                "platform": _host_platform(),
                "requests_per_replica": n_requests,
                "concurrency_per_replica": concurrency,
                "poisson_rate": rate,
                "pipeline_depth": depth,
                "admit_batch": admit,
                "replica_counts": counts,
                "ttft_mean_1r_s": scale_rows[str(counts[0])]["ttft_mean_s"],
                "ttft_mean_max_s": last["ttft_mean_s"],
                "replicas": scale_rows,
            },
        }), flush=True)

        # --- row 2: prefix routing vs round-robin, 2 replicas -------------
        # slow arrivals on purpose, twice over: (a) unsaturated (same
        # reasoning as main_prefix) so TTFT is prefill latency, not queue
        # wait; (b) a tenant's next request must arrive AFTER its previous
        # one finished and donated its prefix, or the router probes empty
        # tries and every policy degenerates to load placement. 0.5 req/s
        # with 5 tenants = one same-tenant return every 10 s, comfortably
        # past a cold request's few-second CPU service time
        route_rate = 0.5
        route_requests = n_requests * 2
        buckets = (16, prefix_len + 16)
        rtrace = _tenant_trace(route_requests, route_rate, seed,
                               cfg.vocab_size, prefix_len, tenants)
        # different seed -> different tenant prefixes: warms programs, not
        # the timed trace's tries (the timed cluster is fresh anyway); high
        # rate because the warm pass only exists to compile
        rwarm = _tenant_trace(route_requests, 200.0, seed + 1,
                              cfg.vocab_size, prefix_len, tenants)

        def cached_factory(**kw):
            return ServingEngine(
                module, params, max_concurrency=concurrency,
                prompt_buckets=buckets, max_queue=len(rtrace) + 1,
                pipeline_depth=depth, admit_batch=admit,
                prefix_cache=True, **kw)

        policy_rows: dict[str, dict] = {}
        for policy in ("prefix", "round_robin"):
            tps, dt, detail = timed_cluster(
                f"route-{policy}", 2, rwarm, rtrace, cached_factory, policy)
            hits, misses = detail["prefix_hits"], detail["prefix_misses"]
            policy_rows[policy] = {
                "tokens_per_sec": round(tps, 2), "wall_s": round(dt, 3),
                "hit_rate": round(hits / max(hits + misses, 1), 4),
                **detail}
        pfx, rr = policy_rows["prefix"], policy_rows["round_robin"]
        print(json.dumps({
            "metric": "serving_cluster_prefix_routing_hit_rate",
            "value": pfx["hit_rate"],
            "unit": "trie_hit_frac",
            "vs_baseline": round(pfx["hit_rate"] / max(rr["hit_rate"], 1e-9),
                                 3),
            "detail": {
                "platform": _host_platform(),
                "requests": route_requests,
                "replicas": 2,
                "tenants": tenants,
                "prefix_len": prefix_len,
                "arrival_rate": route_rate,
                "hit_rate_round_robin": rr["hit_rate"],
                "ttft_mean_prefix_s": pfx["ttft_mean_s"],
                "ttft_mean_round_robin_s": rr["ttft_mean_s"],
                "prefix": pfx,
                "round_robin": rr,
            },
        }), flush=True)
    finally:
        if tmp_dir is not None:
            shutil.rmtree(tmp_dir, ignore_errors=True)


def main_mesh() -> None:
    """Per-mesh-shape serving rows: the SAME ragged trace through
    ``ServingEngine(mesh=(d, m))`` for every requested shape. One JSON row per
    shape (tokens/sec, ITL p50/p99, compile count + per-program compile
    seconds), then one summary line (value = the
    LAST shape's tokens/sec, vs_baseline = last / first — order the shapes so
    the first is the 1x1 reference)."""
    shapes: list[tuple[int, int]] = []
    for tok in os.environ["BENCH_SERVE_MESH"].replace(" ", "").split(","):
        if tok:
            d, m = tok.lower().split("x")
            shapes.append((int(d), int(m)))
    if not shapes:
        raise SystemExit("BENCH_SERVE_MESH set but no DxM shapes parsed")
    # mesh shapes need devices; the cpu-host rows multiplex them BEFORE the
    # backend initializes
    _platform_or_exit(max(d * m for d, m in shapes))

    from accelerate_tpu.serving import ServingMetrics

    n_requests = _env_int("BENCH_SERVE_REQUESTS", 32)
    concurrency = _env_int("BENCH_SERVE_CONCURRENCY", 8)
    rate = float(os.environ.get("BENCH_SERVE_RATE", 200.0))
    seed = _env_int("BENCH_SERVE_SEED", 0)
    depth = _env_int("BENCH_SERVE_DEPTH", 2)
    admit = _env_int("BENCH_SERVE_ADMIT", 4)

    cfg = GPT2Config(vocab_size=2048, n_positions=128, n_embd=512, n_layer=6,
                     n_head=8, dtype=jnp.float32, param_dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    trace = _trace(n_requests, rate, seed, cfg.vocab_size)

    rows: dict[str, dict] = {}
    for d, m in shapes:
        engine = ServingEngine(
            module, params, max_concurrency=concurrency,
            prompt_buckets=BUCKETS, max_queue=len(trace) + 1,
            pipeline_depth=depth, admit_batch=admit, mesh=(d, m),
        )
        _run_engine(engine, trace)  # warm pass: every compile lands here
        compiles = dict(engine.metrics.compiles)
        compile_count = engine.metrics.compile_count.value
        engine.metrics = ServingMetrics()  # timed pass starts clean
        tps, dt, detail = _run_engine(engine, trace)
        row = {
            "row": "serving_mesh",
            "mesh": f"{d}x{m}",
            "tokens_per_sec": round(tps, 2),
            "wall_s": round(dt, 3),
            "itl_p50_s": detail["itl_p50_s"],
            "itl_p99_s": detail["itl_p99_s"],
            "compile_count": compile_count,
            "compile_s": compiles,
            "ttft_p50_s": detail["ttft_p50_s"],
            "host_blocked_per_step_s": detail["host_blocked_per_step_s"],
            "slot_occupancy_mean": detail["slot_occupancy_mean"],
            "steps": detail["steps"],
        }
        rows[row["mesh"]] = row
        print(json.dumps(row), flush=True)

    first = rows[f"{shapes[0][0]}x{shapes[0][1]}"]["tokens_per_sec"]
    last = rows[f"{shapes[-1][0]}x{shapes[-1][1]}"]
    print(json.dumps({
        "metric": "serving_mesh_tokens_per_sec",
        "value": last["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": round(last["tokens_per_sec"] / max(first, 1e-9), 3),
        "detail": {
            "platform": _host_platform(),
            "requests": n_requests,
            "concurrency": concurrency,
            "poisson_rate": rate,
            "pipeline_depth": depth,
            "admit_batch": admit,
            "shapes": rows,
        },
    }), flush=True)


def _tiered_probe(engine, trace) -> dict:
    """Submit the whole trace up front and drain, sampling peak concurrent
    in-flight streams per step: active slots plus hibernated host records —
    a parked stream is still an admitted tenant (it resumes and finishes),
    exactly like a swapped-out process counts against load."""
    from accelerate_tpu.serving import ServingMetrics

    engine.metrics = ServingMetrics()
    for req in trace:
        engine.submit(Request(req.prompt, req.params))
    t0 = time.perf_counter()
    done = 0
    peak = 0
    while engine.has_work:
        done += len(engine.step())
        mem = engine.memory_stats()
        inflight = (int(mem["slots_active"])
                    + int(mem.get("host_tier/hibernated", 0)))
        peak = max(peak, inflight)
    dt = time.perf_counter() - t0
    assert done == len(trace)
    return {"peak_streams": peak, "wall_s": round(dt, 3),
            "steps": engine.metrics.steps.value}


def main_tiered() -> None:
    from accelerate_tpu.serving import KVTierConfig, PagedKVConfig

    n_requests = _env_int("BENCH_SERVE_REQUESTS", 32)
    seed = _env_int("BENCH_SERVE_SEED", 0)
    depth = _env_int("BENCH_SERVE_DEPTH", 2)
    admit = _env_int("BENCH_SERVE_ADMIT", 4)
    cfg = GPT2Config(vocab_size=2048, n_positions=128, n_embd=512, n_layer=6,
                     n_head=8, dtype=jnp.float32, param_dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    trace = _trace(n_requests, 200.0, seed, cfg.vocab_size)

    # the fixed-HBM premise: a device pool the ragged extents saturate —
    # 12 blocks is 1.5 worst-case rows (the engine floor is one full row),
    # so the pool, not the slot count, is the binding admission constraint
    block_tokens = 16
    num_blocks = _env_int("BENCH_SERVE_TIER_BLOCKS", 12)
    slots = _env_int("BENCH_SERVE_TIER_SLOTS", 16)

    def build(tier):
        return ServingEngine(
            module, params, max_concurrency=slots, prompt_buckets=BUCKETS,
            max_queue=len(trace) + 1, pipeline_depth=depth,
            admit_batch=admit,
            paged_kv=PagedKVConfig(block_tokens=block_tokens,
                                   num_blocks=num_blocks),
            kv_tier=tier)

    # warm one engine's jit caches (shared per module), then measure both
    _tiered_probe(build(None), trace[: min(8, len(trace))])
    off = _tiered_probe(build(None), trace)
    tier_cfg = KVTierConfig(min_resident_slots=1,
                            thrash_enter_events=1_000_000)
    on_engine = build(tier_cfg)
    on = _tiered_probe(on_engine, trace)
    m = on_engine.metrics
    pool_bytes = int(on_engine.memory_stats()["block_pool/pool_bytes"])

    print(json.dumps({
        "metric": "serving_tiered_peak_streams",
        "value": on["peak_streams"],
        "unit": "concurrent_streams",
        "vs_baseline": round(on["peak_streams"]
                             / max(off["peak_streams"], 1), 3),
        "detail": {
            "platform": _host_platform(),
            "requests": n_requests,
            "max_concurrency": slots,
            "block_tokens": block_tokens,
            "num_blocks": num_blocks,
            "pool_bytes": pool_bytes,
            "pipeline_depth": depth,
            "admit_batch": admit,
            "tier_off": off,
            "tier_on": on,
            "host_tier_page_in_p99_s": round(
                m.host_page_in_s.quantile(0.99), 5),
            "host_tier_page_out_p99_s": round(
                m.host_page_out_s.quantile(0.99), 5),
            "page_ins": int(m.host_page_ins.value),
            "page_outs": int(m.host_page_outs.value),
            "hibernated": int(m.host_hibernated.value),
            "wakeups": int(m.host_wakeups.value),
        },
    }), flush=True)


def _pool_bytes_by_dtype(engine, num_blocks: int) -> dict[str, int]:
    """Exact nbytes of the paged block pool, split by storage dtype: every
    cache-tree leaf keyed by block index (leading dim == ``num_blocks``), the
    same rule the KV tier uses to size host copies
    (`serving/kv_tier.py` ``block_bytes``). Under ``kv_cache_dtype=int8``
    this is the int8 payload plus the fp32 absmax scale planes; at full
    precision it is a single compute-dtype entry."""
    out: dict[str, int] = {}
    for leaf in jax.tree.leaves(engine._cache):
        shape = getattr(leaf, "shape", ())
        if shape and shape[0] == num_blocks:
            key = str(leaf.dtype)
            out[key] = out.get(key, 0) + int(leaf.nbytes)
    return out


def main_quant() -> None:
    from accelerate_tpu.serving import PagedKVConfig

    n_requests = _env_int("BENCH_SERVE_REQUESTS", 32)
    seed = _env_int("BENCH_SERVE_SEED", 0)
    depth = _env_int("BENCH_SERVE_DEPTH", 2)
    admit = _env_int("BENCH_SERVE_ADMIT", 4)
    block_tokens = 16
    num_blocks = _env_int("BENCH_SERVE_QUANT_BLOCKS", 12)
    slots = _env_int("BENCH_SERVE_QUANT_SLOTS", 32)

    def build(dtype, kv_dtype, blocks, max_conc):
        cfg = GPT2Config(vocab_size=2048, n_positions=128, n_embd=512,
                         n_layer=6, n_head=8, dtype=dtype, param_dtype=dtype,
                         kv_cache_dtype=kv_dtype)
        module = GPT2LMHead(cfg)
        params = module.init_params(jax.random.key(0))
        return ServingEngine(
            module, params, max_concurrency=max_conc,
            prompt_buckets=BUCKETS, max_queue=n_requests + 1,
            pipeline_depth=depth, admit_batch=admit,
            paged_kv=PagedKVConfig(block_tokens=block_tokens,
                                   num_blocks=blocks))

    # --- row 1: exact KV bytes per token of pool capacity, per mode -------
    # construction-only probes (the pool is allocated eagerly; nbytes are
    # allocation-time constants) at identical block geometry
    cap_tokens = num_blocks * block_tokens
    per_mode: dict[str, dict] = {}
    pool_totals: dict[str, int] = {}
    for mode, dtype, kv_dtype in (("fp32", jnp.float32, None),
                                  ("bf16", jnp.bfloat16, None),
                                  ("int8", jnp.bfloat16, jnp.int8)):
        by_dtype = _pool_bytes_by_dtype(build(dtype, kv_dtype, num_blocks, 8),
                                        num_blocks)
        total = sum(by_dtype.values())
        pool_totals[mode] = total
        per_mode[mode] = {
            "kv_bytes_per_token": round(total / cap_tokens, 2),
            "payload_bytes_per_token":
                round(by_dtype.get("int8", total) / cap_tokens, 2),
            "scale_bytes_per_token":
                round(by_dtype.get("float32", 0) / cap_tokens, 2)
                if kv_dtype is not None else 0.0,
        }
    int8_bpt = per_mode["int8"]["kv_bytes_per_token"]
    bf16_bpt = per_mode["bf16"]["kv_bytes_per_token"]
    ratio = int8_bpt / bf16_bpt
    # the headline capacity claim: int8 payload + fp32 scales must cost at
    # most 0.55x the bf16 store (scales amortize over block_tokens)
    assert ratio <= 0.55, (int8_bpt, bf16_bpt, ratio)
    print(json.dumps({
        "metric": "serving_quant_kv_bytes_per_token",
        "value": int8_bpt,
        "unit": "bytes/token",
        "vs_baseline": round(ratio, 4),
        "detail": {
            "platform": _host_platform(),
            "block_tokens": block_tokens,
            "num_blocks": num_blocks,
            "int8_over_bf16": round(ratio, 4),
            "modes": per_mode,
        },
    }), flush=True)

    # --- row 2: peak concurrent streams at EQUAL HBM budget ---------------
    # the fp32 pool's byte budget, re-spent on int8 blocks: quantization is
    # admission capacity, not just smaller numbers. Compute dtype stays fp32
    # on both sides so KV storage is the only variable.
    # per-block bytes from the row-1 probes (pool bytes are independent of
    # the compute dtype: int8 payload + fp32 scale planes either way)
    fp32_block_bytes = pool_totals["fp32"] // num_blocks
    int8_block_bytes = pool_totals["int8"] // num_blocks
    budget = num_blocks * fp32_block_bytes
    int8_blocks = budget // int8_block_bytes
    trace = _trace(n_requests, 1e9, seed, 2048)

    fp_engine = build(jnp.float32, None, num_blocks, slots)
    _tiered_probe(fp_engine, trace[: min(6, len(trace))])  # warm the jits
    fp = _tiered_probe(fp_engine, trace)
    q_engine = build(jnp.float32, jnp.int8, int8_blocks, slots)
    _tiered_probe(q_engine, trace[: min(6, len(trace))])
    q = _tiered_probe(q_engine, trace)
    vs = q["peak_streams"] / max(fp["peak_streams"], 1)
    assert vs >= 1.8, (q["peak_streams"], fp["peak_streams"], vs)
    print(json.dumps({
        "metric": "serving_quant_peak_streams",
        "value": q["peak_streams"],
        "unit": "concurrent_streams",
        "vs_baseline": round(vs, 3),
        "detail": {
            "platform": _host_platform(),
            "requests": n_requests,
            "max_concurrency": slots,
            "block_tokens": block_tokens,
            "hbm_budget_bytes": int(budget),
            "fp32_blocks": num_blocks,
            "int8_blocks": int(int8_blocks),
            "fp32_block_bytes": int(fp32_block_bytes),
            "int8_block_bytes": int(int8_block_bytes),
            "pipeline_depth": depth,
            "admit_batch": admit,
            "fp32": fp,
            "int8": q,
        },
    }), flush=True)


def _surge_requests(n: int, seed: int, vocab: int) -> list[Request]:
    """The ragged mix with its decode length floored at 8 tokens: the raw
    mix averages ~4 decode tokens per request, so prefill dominates service
    time and the warm pass's per-step estimate (decode-heavy at saturation)
    would not transfer to the paced run. Decode-dominated requests make the
    measured capacity and step time hold at both load levels."""
    base = _trace(n, 1e9, seed, vocab)
    return [Request(req.prompt, dataclasses.replace(
        req.params, max_new_tokens=max(8, req.params.max_new_tokens)))
        for req in base]


def _surge_trace(reqs: list[Request], base_rate: float, surge_mult: float,
                 seed: int, slo: SLOSpec) -> list[Request]:
    """Three-phase load step over the request mix: the middle third arrives
    ``surge_mult`` times faster than the outer thirds. The final baseline
    third is what makes the autoscaled run's RETIRE happen MID-BENCH —
    requests are still arriving while the idle windows accumulate and the
    fleet drains back down."""
    r = np.random.default_rng(seed + 17)
    third = max(1, len(reqs) // 3)
    t, out = 0.0, []
    for i, req in enumerate(reqs):
        rate = base_rate * (surge_mult if third <= i < 2 * third else 1.0)
        t += float(r.exponential(1.0 / rate))
        out.append(Request(req.prompt, req.params, arrival_time=t, slo=slo))
    return out


def main_surge() -> None:
    from accelerate_tpu.serving import (
        AutoscalerConfig,
        FleetAutoscaler,
        ServingCluster,
        predict_ttft,
    )

    n_requests = _env_int("BENCH_SERVE_REQUESTS", 24)
    concurrency = _env_int("BENCH_SERVE_CONCURRENCY", 2)
    seed = _env_int("BENCH_SERVE_SEED", 0)
    depth = _env_int("BENCH_SERVE_DEPTH", 2)
    admit = _env_int("BENCH_SERVE_ADMIT", 4)
    max_replicas = _env_int("BENCH_SERVE_MAX_REPLICAS", 3)
    surge_mult = float(os.environ.get("BENCH_SERVE_SURGE_MULT", 4.0))

    cfg = GPT2Config(vocab_size=2048, n_positions=128, n_embd=512, n_layer=6,
                     n_head=8, dtype=jnp.float32, param_dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))

    base_dir = os.environ.get("BENCH_SERVE_CLUSTER_DIR")
    tmp_dir = None
    if base_dir is None:
        tmp_dir = base_dir = tempfile.mkdtemp(prefix="bench_surge_")

    def factory(**kw):
        return ServingEngine(
            module, params, max_concurrency=concurrency,
            prompt_buckets=BUCKETS, max_queue=n_requests + 1,
            pipeline_depth=depth, admit_batch=admit, **kw)

    try:
        # calibration pass: compile every program AND measure what one warm
        # replica actually sustains on this host — the surge's baseline
        # arrival rate, the SLO bound, and the autoscaler's TTFT target are
        # all sized off measurements, not wall-clock guesses that would
        # flake across hosts
        warm_trace = _surge_requests(n_requests, seed + 1, cfg.vocab_size)
        warm = ServingCluster(factory, os.path.join(base_dir, "warm"),
                              replicas=1)
        t0 = time.perf_counter()
        for req in warm_trace:
            assert warm.submit(Request(req.prompt, req.params,
                                       slo=req.slo)).accepted
        done, warm_steps = 0, 0
        while warm.has_work:
            done += len(warm.step())
            warm_steps += 1
        warm_dt = time.perf_counter() - t0
        assert done == len(warm_trace)
        service_rate = len(warm_trace) / warm_dt  # req/s, saturated + warm
        warm_step_s = warm_dt / max(1, warm_steps)
        rep0 = warm.replicas[0]
        idle_pred = predict_ttft(
            warm.capacity_headroom(),
            getattr(rep0.engine, "last_step_timings", None) or {},
            max_concurrency=rep0.engine.max_concurrency) or 0.0
        warm.close()

        # cold-start TTFT floor probe: ONE request through a FRESH idle
        # replica after the warm pass. A fresh engine pays per-replica
        # program warmup on top of prefill + pipelined delivery, and that
        # cost is real for this row — the control and candidate clusters
        # are both freshly built, and every mid-trace spawn inherits it —
        # so the floor is measured with it included. With a single sample
        # the p50 IS the probe's TTFT, and the SLO must sit ABOVE it or
        # nothing attains even at zero load.
        probe_cluster = ServingCluster(factory, os.path.join(base_dir, "probe"),
                                       replicas=1)
        probe = warm_trace[0]
        assert probe_cluster.submit(Request(probe.prompt,
                                            probe.params)).accepted
        while probe_cluster.has_work:
            probe_cluster.step()
        ttft_floor = float(
            probe_cluster.metrics.snapshot().get("serving/ttft_s/p50", 0.0))
        probe_cluster.close()

        # baseline at about a THIRD of the measured service rate: the warm
        # pass measures capacity at perfect batching (slots always full), so
        # one-at-a-time paced arrivals sustain less — 0.35 keeps the outer
        # thirds comfortably under one replica. The middle third arrives
        # surge_mult times faster (overload by construction). The SLO sits
        # at 3x the measured cold-start TTFT floor: above what admission
        # into a young fleet costs (so light-load requests attain even
        # while replicas warm), below the deep queue waits the surge
        # backlog builds past it (so sustained queueing misses) —
        # calibrating off the saturated warm TTFT instead would place it
        # past every queue wait and the goodput row would degenerate to
        # raw throughput.
        base_rate = 0.35 * service_rate
        slo = SLOSpec(ttft_s=max(3.0 * ttft_floor, 10.0 * warm_step_s, 0.25),
                      name="surge")
        trace = _surge_trace(
            _surge_requests(n_requests, seed, cfg.vocab_size),
            base_rate, surge_mult, seed, slo)

        # control: fixed single replica, no autoscaler
        control = ServingCluster(factory, os.path.join(base_dir, "control"),
                                 replicas=1)
        ctl_tps, ctl_dt, ctl_detail = _run_cluster(control, trace)
        ctl_snap = control.metrics.snapshot()
        control.close()

        # candidate: same trace, same starting fleet, autoscaler on
        auto = ServingCluster(factory, os.path.join(base_dir, "auto"),
                              replicas=1)
        scaler = FleetAutoscaler(auto, AutoscalerConfig(
            min_replicas=1, max_replicas=max_replicas,
            target_ttft_s=max(6.0 * idle_pred, 0.02),
            scale_up_windows=2,
            idle_slots_fraction=0.5, scale_down_idle_windows=8,
            dwell_s=2.0 * warm_step_s, drain_grace_evals=8,
            thrash_enter_events=64,
        ))
        # _run_cluster's done == len(trace) assert IS the zero-lost bar —
        # it holds across every mid-bench spawn, drain, and retire
        auto_tps, auto_dt, auto_detail = _run_cluster(auto, trace)
        retires_during_trace = scaler.retires
        auto_snap = auto.metrics.snapshot()
        for _ in range(300):  # post-trace: converge back to the floor
            auto.step()
            if (sum(1 for r in auto.replicas if r.accepting) == 1
                    and not any(r.draining for r in auto.replicas
                                if not r.retired)):
                break
        converged = sum(1 for r in auto.replicas if r.accepting)
        gauges = scaler.gauges()
        auto.close()

        ctl_goodput = float(ctl_snap.get("serving/goodput_tokens_per_sec", 0.0))
        auto_goodput = float(auto_snap.get("serving/goodput_tokens_per_sec", 0.0))
        print(json.dumps({
            "metric": "serving_surge_goodput_under_slo",
            "value": round(auto_goodput, 2),
            "unit": "tokens/s",
            "vs_baseline": round(auto_goodput / max(ctl_goodput, 1e-9), 3),
            "detail": {
                "platform": _host_platform(),
                "requests": n_requests,
                "concurrency_per_replica": concurrency,
                "pipeline_depth": depth,
                "admit_batch": admit,
                "surge_mult": surge_mult,
                "note": ("in-process replicas share one host CPU and are "
                         "stepped serially, so scale-out cannot add "
                         "throughput here — this row demonstrates the "
                         "control loop (scale-up at the load step, "
                         "mid-bench drain-and-retire, zero lost); real "
                         "fleets give each replica its own accelerator"),
                "service_rate_req_per_s": round(service_rate, 3),
                "baseline_rate_req_per_s": round(base_rate, 3),
                "warm_step_s": round(warm_step_s, 4),
                "ttft_floor_s": round(ttft_floor, 4),
                "slo_ttft_s": round(slo.ttft_s, 4),
                "max_replicas": max_replicas,
                "scale_ups": scaler.scale_ups,
                "retires": scaler.retires,
                "retires_during_trace": retires_during_trace,
                "spawn_retries": scaler.spawn_retries,
                "scale_frozen": gauges["autoscaler/scale_frozen"],
                "replicas_ever": auto.n_replicas,
                "converged_replicas": converged,
                "lost_requests": 0,  # _run_cluster asserted the count
                "ttft_p99_fixed_s": round(
                    float(ctl_snap.get("serving/ttft_s/p99", 0.0)), 4),
                "ttft_p99_autoscaled_s": round(
                    float(auto_snap.get("serving/ttft_s/p99", 0.0)), 4),
                "slo_attainment_fixed": round(
                    float(ctl_snap.get("serving/slo_attainment", 1.0)), 4),
                "slo_attainment_autoscaled": round(
                    float(auto_snap.get("serving/slo_attainment", 1.0)), 4),
                "fixed": {"tokens_per_sec": round(ctl_tps, 2),
                          "wall_s": round(ctl_dt, 3), **ctl_detail},
                "autoscaled": {"tokens_per_sec": round(auto_tps, 2),
                               "wall_s": round(auto_dt, 3), **auto_detail},
            },
        }), flush=True)
    finally:
        if tmp_dir is not None:
            shutil.rmtree(tmp_dir, ignore_errors=True)


def main() -> None:
    if os.environ.get("BENCH_SERVE_MESH"):
        main_mesh()
        return
    _platform_or_exit()
    workload = os.environ.get("BENCH_SERVE_WORKLOAD", "ragged")
    if workload == "prefix":
        main_prefix()
        return
    if workload == "cluster":
        main_cluster()
        return
    if workload == "tiered":
        main_tiered()
        return
    if workload == "quant":
        main_quant()
        return
    if workload == "surge":
        main_surge()
        return
    n_requests = _env_int("BENCH_SERVE_REQUESTS", 32)
    concurrency = _env_int("BENCH_SERVE_CONCURRENCY", 8)
    rate = float(os.environ.get("BENCH_SERVE_RATE", 200.0))
    seed = _env_int("BENCH_SERVE_SEED", 0)
    depth = _env_int("BENCH_SERVE_DEPTH", 2)
    admit = _env_int("BENCH_SERVE_ADMIT", 4)

    # mid-size on purpose: per-token compute must dominate per-call dispatch,
    # as it does for any real serving model — a toy config measures python
    # overhead instead of the lockstep waste
    cfg = GPT2Config(vocab_size=2048, n_positions=128, n_embd=512, n_layer=6,
                     n_head=8, dtype=jnp.float32, param_dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    trace = _trace(n_requests, rate, seed, cfg.vocab_size)

    from accelerate_tpu.serving import ServingMetrics

    def timed_engine(pipeline_depth, tracer=None, telemetry=None):
        # warm pass on the SAME engine/jit caches: compile every (prompt,
        # batch) bucket and the decode step outside the timed region
        engine = ServingEngine(module, params, max_concurrency=concurrency,
                               prompt_buckets=BUCKETS, max_queue=len(trace) + 1,
                               pipeline_depth=pipeline_depth, admit_batch=admit,
                               tracer=tracer)
        _run_engine(engine, trace)
        engine.metrics = ServingMetrics()  # drop the warm pass from the stats
        if tracer is not None:
            tracer.clear()  # the exported trace covers the timed window only
        if telemetry is not None:
            # attach AFTER the warm pass so the time-series covers only the
            # timed window (same contract as the tracer's clear())
            engine.telemetry = telemetry
        result = _run_engine(engine, trace)
        if telemetry is not None:
            telemetry.sample(engine)  # final settled point after the drain
        return result

    tracer = Tracer() if os.environ.get("BENCH_SERVE_TRACE") else None
    telemetry = None
    if os.environ.get("BENCH_SERVE_TELEMETRY"):
        from accelerate_tpu.serving import TelemetryConfig, TelemetryExporter

        telemetry = TelemetryExporter(TelemetryConfig(
            interval_s=0.0,  # every step: bench runs are short, files small
            jsonl_path=os.environ["BENCH_SERVE_TELEMETRY"],
            prometheus_path=os.environ["BENCH_SERVE_TELEMETRY"] + ".prom",
        ))
    sync_tps, sync_dt, sync_detail = timed_engine(1)
    pipe_tps, pipe_dt, pipe_detail = timed_engine(depth, tracer, telemetry)
    telemetry_summary = None
    if telemetry is not None:
        telemetry_summary = {
            "path": os.environ["BENCH_SERVE_TELEMETRY"],
            "prometheus_path": os.environ["BENCH_SERVE_TELEMETRY"] + ".prom",
            "points": len(telemetry.points()),
            "dropped": telemetry.dropped,
        }
        telemetry.close()
    trace_summary = None
    if tracer is not None:
        exported = tracer.export(os.environ["BENCH_SERVE_TRACE"])
        valid = tracer.validate()
        trace_summary = {
            "path": exported["path"],
            "events": exported["events"],
            "dropped": exported["dropped"],
            "malformed_spans": len(valid["anomalies"]),
        }
    # lockstep baseline (generate's jit cache is module-level and persists)
    _run_lockstep(module, params, trace, concurrency)
    lock_tps, lock_dt, lock_detail = _run_lockstep(module, params, trace, concurrency)

    print(json.dumps({
        "metric": "serving_tokens_per_sec",
        "value": round(pipe_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": round(pipe_tps / lock_tps, 3),
        "detail": {
            "platform": _host_platform(),
            "requests": n_requests,
            "concurrency": concurrency,
            "poisson_rate": rate,
            "pipeline_depth": depth,
            "admit_batch": admit,
            "goodput_tokens_per_sec": pipe_detail["goodput_tokens_per_sec"],
            "slo_attainment": pipe_detail["slo_attainment"],
            "slo_classes": pipe_detail["slo_classes"],
            "trace": trace_summary,
            "telemetry": telemetry_summary,
            "vs_depth1": round(pipe_tps / sync_tps, 3),
            "host_blocked_ratio_d2_over_d1": round(
                pipe_detail["host_blocked_per_step_s"]
                / max(sync_detail["host_blocked_per_step_s"], 1e-9), 3),
            "engine_depth1": {"tokens_per_sec": round(sync_tps, 2),
                              "wall_s": round(sync_dt, 3), **sync_detail},
            "engine_pipelined": {"tokens_per_sec": round(pipe_tps, 2),
                                 "wall_s": round(pipe_dt, 3), **pipe_detail},
            "lockstep": {"tokens_per_sec": round(lock_tps, 2),
                         "wall_s": round(lock_dt, 3), **lock_detail},
        },
    }), flush=True)
    _frontend_row(module, params, trace, concurrency, depth, admit)
    _fused_decode_row(module, params, cfg, trace, concurrency, depth, admit)
    _speculation_row(module, params, cfg, concurrency, depth, admit)


if __name__ == "__main__":
    main()
