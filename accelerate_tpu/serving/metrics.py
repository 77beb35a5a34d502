"""Serving observability: counters and histograms, exported through the
`tracking.py` tracker interface (`ServingMetrics.log_to(tracker)` emits one
flat scalar dict per call, so any `GeneralTracker` — JSONL, TensorBoard,
WandB... — records the serving telemetry without serving-specific hooks).

Everything here is host-side bookkeeping; nothing touches the device path.
"""

from __future__ import annotations

import bisect
import math
import time
from typing import Any

from .trace import nearest_rank


class Counter:
    """Monotonic event count."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Histogram:
    """Streaming histogram: exact count/sum/min/max plus a bounded,
    deterministically-strided sample reservoir for quantiles (no RNG — a
    metrics read must never perturb per-request seeding), plus exact counts
    over a fixed log-spaced bucket ladder so the Prometheus export can emit
    real cumulative ``le`` series (``_bucket``/``_sum``/``_count``)."""

    # 1-2-5 per decade, 1e-4 .. 5e4: spans sub-millisecond ITL gaps through
    # queue depths in the tens of thousands. One shared ladder keeps
    # cross-replica bucket counts addable key-by-key.
    BUCKETS: tuple[float, ...] = tuple(
        m * (10.0 ** e) for e in range(-4, 5) for m in (1.0, 2.0, 5.0))

    def __init__(self, max_samples: int = 4096):
        self.count = 0
        self.sum = 0.0
        self._min = math.inf
        self._max = -math.inf
        self._max_samples = int(max_samples)
        self._stride = 1
        self._samples: list[float] = []
        self._bucket_counts = [0] * len(self.BUCKETS)

    @property
    def min(self) -> float:
        """Smallest observed value; 0.0 before any observation — the inf/-inf
        sentinels must never escape into exports (JSONL/W&B reject them)."""
        return self._min if self.count else 0.0

    @property
    def max(self) -> float:
        return self._max if self.count else 0.0

    def observe(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.sum += value
        self._min = min(self._min, value)
        self._max = max(self._max, value)
        i = bisect.bisect_left(self.BUCKETS, value)
        if i < len(self._bucket_counts):
            self._bucket_counts[i] += 1
        # values past the last boundary land only in the implicit +Inf
        # bucket, whose cumulative count is `count` itself
        if self.count % self._stride == 0:
            self._samples.append(value)
            if len(self._samples) > self._max_samples:
                # decimate and double the stride: memory stays bounded while
                # the reservoir keeps spanning the whole stream
                self._samples = self._samples[::2]
                self._stride *= 2

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def buckets(self) -> list[tuple[float, int]]:
        """Cumulative ``(le, count)`` pairs, Prometheus classic-histogram
        semantics (count of observations ``<= le``). Boundaries whose
        cumulative count is still zero are omitted — absent key means zero,
        which keeps cross-replica aggregation a plain key-wise sum."""
        out: list[tuple[float, int]] = []
        cum = 0
        for le, n in zip(self.BUCKETS, self._bucket_counts):
            cum += n
            if cum:
                out.append((le, cum))
        return out

    def quantile(self, q: float) -> float:
        """Nearest-rank quantile: ``ordered[ceil(q*n) - 1]`` (inverse CDF).
        The obvious ``ordered[int(q*n)]`` is off by one — it returns the
        element *above* the nearest rank, so p50 of two samples would report
        the larger of the two."""
        return nearest_rank(sorted(self._samples), q)

    def summary(self) -> dict[str, float]:
        if not self.count:
            return {"count": 0}
        return {
            "count": self.count,
            "mean": self.mean,
            "min": self.min,
            "max": self.max,
            "p50": self.quantile(0.50),
            "p90": self.quantile(0.90),
            "p99": self.quantile(0.99),
        }


class ServingMetrics:
    """The engine's counters and histograms in one bag.

    Latency histograms are in seconds: ``ttft_s`` (submit -> first token),
    ``inter_token_s`` (gap between consecutive tokens of one request),
    ``request_latency_s`` (submit -> finish), ``host_blocked_s`` (time the
    host spent blocked in ``device_get`` per pipelined fetch — THE number the
    pipelined dispatch exists to shrink). ``queue_depth`` and
    ``slot_occupancy`` are sampled once per engine step; ``dispatch_depth``
    (in-flight dispatches at each decode dispatch, 1 = synchronous) and
    ``admit_batch_size`` (requests per batched prefill call) are sampled at
    each dispatch/admission. ``tokens_per_dispatch`` (tokens one decode fetch
    appended across all slots) is sampled at each decode fetch — its mean
    over batch size is the dispatches-per-token amortization the engine's
    ``tokens_per_sync`` scan buys, and under multi-token dispatch each
    ``inter_token_s`` sample is the fetch gap split evenly over that slot's
    appended tokens so p50/p99 stay per-token honest.
    """

    def __init__(self):
        self.requests_submitted = Counter()
        self.requests_rejected = Counter()
        self.requests_finished = Counter()
        # reliability counters (docs/reliability.md): queued past deadline,
        # cancelled via cancel()/abort_all(), re-prefilled by the watchdog,
        # and decode steps in which >= 1 slot produced poisoned output
        self.requests_expired = Counter()
        self.requests_cancelled = Counter()
        self.requests_retried = Counter()
        self.steps_poisoned = Counter()
        self.tokens_generated = Counter()
        self.prefill_tokens = Counter()
        # prefix-cache telemetry (serving/prefix_cache.py): admissions that
        # reused >= 1 cached block vs. those that matched nothing, prompt
        # tokens whose prefill was skipped, blocks donated on retirement, and
        # blocks LRU-evicted under pool pressure
        self.prefix_hits = Counter()
        self.prefix_misses = Counter()
        self.prefix_tokens_reused = Counter()
        self.prefix_blocks_donated = Counter()
        self.prefix_evictions = Counter()
        # host-RAM KV tier (serving/kv_tier.py — docs/serving.md "KV tiering
        # & hibernation"): blocks paged device->host / host->device, whole
        # requests hibernated and woken, thrash-guard freezes, and the
        # per-transfer wall-second histograms the wake cost model feeds on
        self.host_page_ins = Counter()
        self.host_page_outs = Counter()
        self.host_hibernated = Counter()
        self.host_wakeups = Counter()
        self.host_thrash_events = Counter()
        self.host_page_in_s = Histogram()
        self.host_page_out_s = Histogram()
        self.steps = Counter()
        # durability / recovery telemetry (serving/journal.py + engine
        # snapshot/resume — docs/reliability.md "Serving recovery"): journal
        # records and bytes appended by this engine; requests a `resume()`
        # re-admitted MID-STREAM (continuation prefill from journal/snapshot
        # tokens) vs. re-enqueued from the queue; and prompt+stream tokens
        # re-prefilled solely because of the restart (the replay cost a
        # tighter progress cadence would shrink)
        self.journal_records = Counter()
        self.journal_bytes = Counter()
        self.journal_compactions = Counter()
        self.requests_resumed = Counter()
        self.requests_restored = Counter()
        self.replayed_tokens = Counter()
        # self-healing supervisor telemetry (serving/supervisor.py —
        # docs/reliability.md "Self-healing"): engine rebuilds performed by
        # the restart ladder, stalls/NaN-storms the watchdog classified,
        # admissions shed (brownout REJECT_OVERLOAD + unhealthy
        # REJECT_UNHEALTHY + fail-loud aborts), brownout episodes entered,
        # whether a brownout is active right now (0/1 gauge), and cumulative
        # wall seconds spent browned out
        self.supervisor_restarts = Counter()
        self.supervisor_stalls = Counter()
        self.supervisor_storms = Counter()
        self.supervisor_shed = Counter()
        self.supervisor_brownouts = Counter()
        self.supervisor_brownout_active = 0
        self.supervisor_time_in_brownout_s = 0.0
        # mesh-sharded serving telemetry (engine ``mesh=``): per-replica slot
        # occupancy (one observation per data-axis replica per step, so
        # imbalance between the disjoint slot ranges is visible as
        # p50-vs-min spread)
        self.replica_occupancy = Histogram()
        # compile telemetry: every first dispatch of a jitted serving program
        # — decode step, plain/cached admission per (prompt_bucket,
        # batch_bucket) — counts once, with its wall seconds recorded both in
        # the histogram and per-key in ``compiles`` (key format
        # ``kind[pb{N}b{M}]@mesh{D}x{T}``), so a bucket-explosion regression
        # shows up as compile_count growth in bench output and chaos replays
        self.compile_count = Counter()
        self.compile_s = Histogram()
        self.compiles: dict[str, float] = {}
        # counters a model sums on the device inside its decode step and the
        # engine fetches with the step's tokens (`CacheContract.step_counters`
        # — e.g. `moe_picks_held`, `moe_experts_touched`), summed over every
        # fetched step; `counted_steps` is how many steps brought them. Empty
        # for a model that declares none (snapshot then has no such key).
        self.step_counters: dict[str, int] = {}
        self.counted_steps = Counter()
        # which branch of the sampling tail (`engine._sample_rows`) each
        # dispatched decode step was sent to, by the HOST's view at dispatch:
        # the params of the slots it holds. The device decides from its own
        # `finished` mask, so for a turn or two this can read one branch
        # heavier than the device ran (a slot finished on the device and not
        # yet retired here) or lighter (a cancelled slot burns out its budget
        # on the device after the host released it).
        self.sample_tail_greedy_steps = Counter()
        self.sample_tail_draw_steps = Counter()
        self.sample_tail_top_k_steps = Counter()
        # what the fused paged-decode kernel reads against what its table
        # spans (`ops/flash_attention.paged_decode_attention` fetches a row's
        # live blocks): at each dispatched decode step the keys and values the
        # held slots have, by the host's view (prompt + delivered tokens, a
        # turn or two behind the device), and held slots x n_positions
        self.paged_decode_live_tokens = Counter()
        self.paged_decode_span_tokens = Counter()
        self.ttft_s = Histogram()
        # TTFT split by prefix-cache outcome: the hit histogram is the
        # headline number prefix reuse exists to shrink
        self.ttft_hit_s = Histogram()
        self.ttft_miss_s = Histogram()
        self.inter_token_s = Histogram()
        self.request_latency_s = Histogram()
        self.host_blocked_s = Histogram()
        self.queue_depth = Histogram()
        self.slot_occupancy = Histogram()
        self.dispatch_depth = Histogram()
        self.admit_batch_size = Histogram()
        self.tokens_per_dispatch = Histogram()
        # SLO / goodput accounting (docs/observability.md): tokens from
        # requests that ATTAINED their SLO (requests without one attain
        # vacuously on a clean finish), plus per-class attainment counters
        # keyed by SLOSpec.name — {"requests", "attained", "ttft_miss",
        # "itl_miss", "goodput_tokens"} per class
        self.goodput_tokens = Counter()
        self.slo_classes: dict[str, dict[str, int]] = {}
        # speculative decoding (docs/serving.md "Speculative decoding"):
        # drafts proposed / accepted (their ratio is the drafter's accept
        # rate), verify dispatches, tokens emitted by verify dispatches, and
        # the per-slot accepted-draft-length distribution (0..k; mean + 1 is
        # tokens per verify forward). The headline derived rate is
        # ``serving/accepted_tokens_per_forward`` = spec_tokens /
        # spec_forwards — speculation pays off when it beats 1.0, i.e. its
        # inverse (forwards per accepted token, the bench column) drops
        # below the 1.0 a plain autoregressive step is pinned at
        self.spec_proposed = Counter()
        self.spec_accepted = Counter()
        self.spec_forwards = Counter()
        self.spec_tokens = Counter()
        self.spec_accept_len = Histogram()
        # step-phase attribution (docs/observability.md "Latency
        # attribution"): host wall seconds of each named phase of ONE
        # `ServingEngine.step()` call — scheduling/admission bookkeeping,
        # drafter proposal, jitted dispatch, device-blocked fetch
        # (`device_get`), detokenize/delivery, journal appends+fsync, and
        # telemetry export — plus the whole-step wall. One observation per
        # step; the per-step dict rides EV_DISPATCH/EV_FETCH as ``phases``.
        # front-door telemetry (serving/frontend.py — docs/serving.md "Front
        # door"): streamed requests opened / finished; stream events
        # delivered to callers (first-token + progress + finish);
        # ``streamed_ttft_s`` is TTFT as a STREAMING caller experiences it
        # (submit -> first StreamEvent delivered from the journal spine, so
        # it includes the tail-poll lag that completed-output TTFT hides);
        # ``stream_lag_s`` is that delivery lag alone (journal append ->
        # event yielded); ``predicted_ttft_s`` records every predictive-
        # admission estimate, and ``requests_shed_predicted`` counts the
        # submissions the front door rejected with REJECT_PREDICTED_TTFT
        # *before* a doomed SLO burned a slot (distinct from the
        # supervisor's reactive brownout shed)
        self.streams_opened = Counter()
        self.streams_finished = Counter()
        self.stream_events = Counter()
        self.streamed_ttft_s = Histogram()
        self.stream_lag_s = Histogram()
        self.predicted_ttft_s = Histogram()
        self.requests_shed_predicted = Counter()
        # sheds per priority class (``serving/class/<p>/shed``): which class
        # the predictive gate actually pushes back on
        self.class_shed: dict[int, int] = {}
        self.step_phase_schedule_s = Histogram()
        self.step_phase_draft_s = Histogram()
        self.step_phase_dispatch_s = Histogram()
        self.step_phase_fetch_blocked_s = Histogram()
        self.step_phase_deliver_s = Histogram()
        self.step_phase_journal_s = Histogram()
        self.step_phase_telemetry_s = Histogram()
        self.step_total_s = Histogram()
        self._start: float | None = None
        # rate window: tokens_per_sec()/goodput() measure from the later of
        # mark_start() and the last reset_rate_window(), so an engine that
        # idles between bursts doesn't report a forever-decayed rate
        self._win_t0: float | None = None
        self._win_tokens = 0
        self._win_goodput = 0

    def mark_start(self) -> None:
        """First-event clock for the aggregate tokens/sec rate."""
        if self._start is None:
            self._start = time.perf_counter()
            self._win_t0 = self._start

    def reset_rate_window(self) -> None:
        """Start a fresh rate window: tokens_per_sec() and
        goodput_tokens_per_sec count only tokens generated after this call.
        Call between workload phases (bench harnesses do) — cumulative
        counters and histograms are untouched."""
        self._win_t0 = time.perf_counter()
        self._win_tokens = self.tokens_generated.value
        self._win_goodput = self.goodput_tokens.value

    def observe_slo(
        self,
        slo: Any,
        *,
        clean: bool,
        ttft_ok: bool,
        itl_ok: bool,
        tokens: int,
    ) -> bool:
        """Record one terminal request's SLO outcome; returns attainment.

        ``slo`` is the request's `request.SLOSpec` or None (unconstrained —
        attains iff the finish was clean, tracked under no class).
        ``clean`` means FINISH_EOS/FINISH_LENGTH (expired / aborted /
        errored requests are misses by definition); ``ttft_ok``/``itl_ok``
        report each bound, and ``tokens`` is the request's generated-token
        count, credited to goodput only on attainment.
        """
        attained = clean and ttft_ok and itl_ok
        if slo is not None:
            cls = self.slo_classes.setdefault(
                slo.name,
                {"requests": 0, "attained": 0, "ttft_miss": 0,
                 "itl_miss": 0, "goodput_tokens": 0},
            )
            cls["requests"] += 1
            cls["attained"] += int(attained)
            cls["ttft_miss"] += int(not ttft_ok)
            cls["itl_miss"] += int(not itl_ok)
            cls["goodput_tokens"] += tokens if attained else 0
        if attained:
            self.goodput_tokens.inc(tokens)
        return attained

    def goodput(self) -> dict[str, Any]:
        """SLO-goodput summary over the current rate window: goodput
        tokens/sec (tokens from attaining requests), overall attainment
        fraction across SLO-carrying requests (1.0 when none carried one),
        and the per-class counter dicts."""
        slo_requests = sum(c["requests"] for c in self.slo_classes.values())
        slo_attained = sum(c["attained"] for c in self.slo_classes.values())
        win = self._win_t0 if self._win_t0 is not None else self._start
        dt = (time.perf_counter() - win) if win is not None else 0.0
        gp_tokens = self.goodput_tokens.value - self._win_goodput
        return {
            "goodput_tokens": self.goodput_tokens.value,
            "goodput_tokens_per_sec": gp_tokens / dt if dt > 0 else 0.0,
            "slo_requests": slo_requests,
            "slo_attainment": (slo_attained / slo_requests
                               if slo_requests else 1.0),
            "classes": {
                name: {**stats,
                       "attainment": (stats["attained"] / stats["requests"]
                                      if stats["requests"] else 1.0)}
                for name, stats in sorted(self.slo_classes.items())
            },
        }

    def observe_shed(self, priority: int) -> None:
        """One predictive-admission rejection (REJECT_PREDICTED_TTFT),
        attributed to its priority class."""
        self.requests_shed_predicted.inc()
        p = int(priority)
        self.class_shed[p] = self.class_shed.get(p, 0) + 1

    def observe_step(self, active: int, capacity: int, queue_depth: int) -> None:
        self.steps.inc()
        self.slot_occupancy.observe(active / capacity if capacity else 0.0)
        self.queue_depth.observe(queue_depth)

    def observe_replicas(self, active_per_replica: list[int], capacity: int) -> None:
        """Per-data-replica occupancy for one step (mesh-sharded slot state:
        replica ``i`` decodes its own contiguous slot range of ``capacity``)."""
        for active in active_per_replica:
            self.replica_occupancy.observe(active / capacity if capacity else 0.0)

    def observe_step_phases(self, t: Any) -> None:
        """Record one step's phase breakdown (a `StepTimings`, or any object
        with the phase attributes) into the per-phase histograms."""
        self.step_phase_schedule_s.observe(t.schedule_s)
        self.step_phase_draft_s.observe(t.draft_s)
        self.step_phase_dispatch_s.observe(t.dispatch_s)
        self.step_phase_fetch_blocked_s.observe(t.fetch_blocked_s)
        self.step_phase_deliver_s.observe(t.deliver_s)
        self.step_phase_journal_s.observe(t.journal_s)
        self.step_phase_telemetry_s.observe(t.telemetry_s)
        self.step_total_s.observe(t.total_s)

    def observe_step_counters(self, names, values) -> None:
        """One fetched decode step's device-side counters, in ``names``' order."""
        self.counted_steps.inc()
        for name, value in zip(names, values):
            self.step_counters[name] = self.step_counters.get(name, 0) + int(value)

    def observe_sample_tail(self, draw_slots: int, top_k_slots: int) -> None:
        """One dispatched decode step: ``draw_slots`` held slots sample
        (temperature > 0), ``top_k_slots`` of them with a top-k mask."""
        if top_k_slots:
            self.sample_tail_top_k_steps.inc()
        elif draw_slots:
            self.sample_tail_draw_steps.inc()
        else:
            self.sample_tail_greedy_steps.inc()

    def observe_paged_decode(self, live_tokens: int, span_tokens: int) -> None:
        """One dispatched decode step over rows that hold ``live_tokens`` of
        the ``span_tokens`` positions their block tables address."""
        self.paged_decode_live_tokens.inc(live_tokens)
        self.paged_decode_span_tokens.inc(span_tokens)

    def record_compile(self, key: str, seconds: float) -> None:
        """First dispatch of a jitted serving program: one compile, keyed by
        ``kind[pb{prompt_bucket}b{batch_bucket}]@mesh{data}x{model}``."""
        self.compile_count.inc()
        self.compile_s.observe(seconds)
        self.compiles[key] = round(float(seconds), 4)

    def tokens_per_sec(self) -> float:
        """Aggregate decode rate over the current window (see
        `reset_rate_window` — without resets this is the lifetime rate since
        `mark_start`)."""
        if self._start is None:
            return 0.0
        win = self._win_t0 if self._win_t0 is not None else self._start
        dt = time.perf_counter() - win
        n = self.tokens_generated.value - self._win_tokens
        return n / dt if dt > 0 else 0.0

    def snapshot(self) -> dict[str, Any]:
        """Flat scalar dict — the shape every tracker's ``log`` accepts."""
        out: dict[str, Any] = {
            "serving/requests_submitted": self.requests_submitted.value,
            "serving/requests_rejected": self.requests_rejected.value,
            "serving/requests_finished": self.requests_finished.value,
            "serving/requests_expired": self.requests_expired.value,
            "serving/requests_cancelled": self.requests_cancelled.value,
            "serving/requests_retried": self.requests_retried.value,
            "serving/steps_poisoned": self.steps_poisoned.value,
            "serving/tokens_generated": self.tokens_generated.value,
            "serving/prefill_tokens": self.prefill_tokens.value,
            "serving/prefix_hits": self.prefix_hits.value,
            "serving/prefix_misses": self.prefix_misses.value,
            "serving/prefix_tokens_reused": self.prefix_tokens_reused.value,
            "serving/prefix_blocks_donated": self.prefix_blocks_donated.value,
            "serving/prefix_evictions": self.prefix_evictions.value,
            "serving/host_tier/page_ins": self.host_page_ins.value,
            "serving/host_tier/page_outs": self.host_page_outs.value,
            "serving/host_tier/hibernated": self.host_hibernated.value,
            "serving/host_tier/wakeups": self.host_wakeups.value,
            "serving/host_tier/thrash_events": self.host_thrash_events.value,
            "serving/steps": self.steps.value,
            "serving/journal_records": self.journal_records.value,
            "serving/journal_bytes": self.journal_bytes.value,
            "serving/journal_compactions": self.journal_compactions.value,
            "serving/requests_resumed": self.requests_resumed.value,
            "serving/requests_restored": self.requests_restored.value,
            "serving/replayed_tokens": self.replayed_tokens.value,
            "serving/tokens_per_sec": self.tokens_per_sec(),
            "serving/compile_count": self.compile_count.value,
            "serving/spec_proposed": self.spec_proposed.value,
            "serving/spec_accepted": self.spec_accepted.value,
            "serving/spec_forwards": self.spec_forwards.value,
            "serving/spec_tokens": self.spec_tokens.value,
            "serving/accepted_tokens_per_forward": (
                self.spec_tokens.value / self.spec_forwards.value
                if self.spec_forwards.value else 0.0),
            "serving/sample_tail/greedy_steps": (
                self.sample_tail_greedy_steps.value),
            "serving/sample_tail/draw_steps": self.sample_tail_draw_steps.value,
            "serving/sample_tail/top_k_steps": (
                self.sample_tail_top_k_steps.value),
            "serving/paged_decode/live_tokens": (
                self.paged_decode_live_tokens.value),
            "serving/paged_decode/span_tokens": (
                self.paged_decode_span_tokens.value),
            "serving/streams_opened": self.streams_opened.value,
            "serving/streams_finished": self.streams_finished.value,
            "serving/stream_events": self.stream_events.value,
            "serving/requests_shed_predicted": (
                self.requests_shed_predicted.value),
            "supervisor/restarts": self.supervisor_restarts.value,
            "supervisor/stalls_detected": self.supervisor_stalls.value,
            "supervisor/storms_detected": self.supervisor_storms.value,
            "supervisor/shed_requests": self.supervisor_shed.value,
            "supervisor/brownouts": self.supervisor_brownouts.value,
            "supervisor/brownout_active": int(self.supervisor_brownout_active),
            "supervisor/time_in_brownout_s": round(
                float(self.supervisor_time_in_brownout_s), 6),
        }
        gp = self.goodput()
        out["serving/goodput_tokens"] = gp["goodput_tokens"]
        out["serving/goodput_tokens_per_sec"] = gp["goodput_tokens_per_sec"]
        out["serving/slo_attainment"] = gp["slo_attainment"]
        for name, stats in gp["classes"].items():
            for stat in ("requests", "attained", "attainment",
                         "ttft_miss", "itl_miss", "goodput_tokens"):
                out[f"serving/slo/{name}/{stat}"] = stats[stat]
        for key, seconds in self.compiles.items():
            out[f"serving/compile/{key}"] = seconds
        for name, total in self.step_counters.items():
            out[f"serving/step_counters/{name}"] = total
        if self.step_counters:
            out["serving/step_counters/steps"] = self.counted_steps.value
        for p, n in sorted(self.class_shed.items()):
            out[f"serving/class/{p}/shed"] = n
        for name, hist in (
            ("replica_occupancy", self.replica_occupancy),
            ("compile_s", self.compile_s),
            ("ttft_s", self.ttft_s),
            ("ttft_hit_s", self.ttft_hit_s),
            ("ttft_miss_s", self.ttft_miss_s),
            ("inter_token_s", self.inter_token_s),
            ("request_latency_s", self.request_latency_s),
            ("host_blocked_s", self.host_blocked_s),
            ("host_tier/page_in_s", self.host_page_in_s),
            ("host_tier/page_out_s", self.host_page_out_s),
            ("queue_depth", self.queue_depth),
            ("slot_occupancy", self.slot_occupancy),
            ("dispatch_depth", self.dispatch_depth),
            ("admit_batch_size", self.admit_batch_size),
            ("tokens_per_dispatch", self.tokens_per_dispatch),
            ("spec_accept_len", self.spec_accept_len),
            ("streamed_ttft_s", self.streamed_ttft_s),
            ("stream_lag_s", self.stream_lag_s),
            ("predicted_ttft_s", self.predicted_ttft_s),
            ("step_phase_schedule_s", self.step_phase_schedule_s),
            ("step_phase_draft_s", self.step_phase_draft_s),
            ("step_phase_dispatch_s", self.step_phase_dispatch_s),
            ("step_phase_fetch_blocked_s", self.step_phase_fetch_blocked_s),
            ("step_phase_deliver_s", self.step_phase_deliver_s),
            ("step_phase_journal_s", self.step_phase_journal_s),
            ("step_phase_telemetry_s", self.step_phase_telemetry_s),
            ("step_total_s", self.step_total_s),
        ):
            for stat, value in hist.summary().items():
                out[f"serving/{name}/{stat}"] = value
            if hist.count:
                # exact series for the Prometheus histogram exposition:
                # `<base>/sum` plus cumulative `<base>/bucket/<le>` counts
                # (absent bucket key == cumulative zero, so replica snapshots
                # aggregate by plain summation)
                out[f"serving/{name}/sum"] = hist.sum
                for le, cum in hist.buckets():
                    out[f"serving/{name}/bucket/{le:g}"] = cum
        return out

    def log_to(self, tracker: Any, step: int | None = None) -> None:
        """Emit the snapshot through a `tracking.GeneralTracker`."""
        tracker.log(self.snapshot(), step=step)


# Histogram-summary stat suffixes (`Histogram.summary`): naive summation is
# wrong for every one of these, so `aggregate_snapshots` special-cases them.
_HIST_WEIGHTED = ("mean", "p50", "p90", "p99")
_HIST_MIN = ("min",)
_HIST_MAX = ("max",)


def aggregate_snapshots(snapshots: list[dict[str, Any]]) -> dict[str, Any]:
    """Combine per-replica `ServingMetrics.snapshot` dicts into one
    cluster-total dict (`serving/cluster.py` metrics view).

    Counters and rates sum — a cluster's tokens/sec IS the sum of its
    replicas'. Histogram summaries can't: for each ``<base>/<stat>`` family,
    ``count`` sums, ``min``/``max`` take the extremes, and ``mean``/``p50``/
    ``p90``/``p99`` take the count-weighted average (exact for the mean; for
    quantiles an approximation — the per-replica reservoirs aren't merged —
    which is fine for the dashboards these feed). Ratio keys are recomputed
    from their summed numerators/denominators (``slo_attainment``,
    per-class ``attainment``, ``accepted_tokens_per_forward``) rather than
    averaged blind. Non-numeric values keep the first replica's entry.
    """
    present: dict[str, list[tuple[dict[str, Any], Any]]] = {}
    for snap in snapshots:
        for key, value in snap.items():
            present.setdefault(key, []).append((snap, value))
    out: dict[str, Any] = {}
    for key, entries in present.items():
        values = [v for _, v in entries]
        if not all(isinstance(v, (int, float)) and not isinstance(v, bool)
                   for v in values):
            out[key] = values[0]
            continue
        base, _, stat = key.rpartition("/")
        if stat in _HIST_WEIGHTED and base:
            weights = [snap.get(f"{base}/count", 0) for snap, _ in entries]
            total = sum(weights)
            out[key] = (sum(w * v for w, v in zip(weights, values)) / total
                        if total else 0.0)
        elif stat in _HIST_MIN and base:
            out[key] = min(values)
        elif stat in _HIST_MAX and base:
            out[key] = max(values)
        else:
            out[key] = sum(values)
    # ratio keys: recompute from the summed components now in `out`
    forwards = out.get("serving/spec_forwards", 0)
    if "serving/accepted_tokens_per_forward" in out:
        out["serving/accepted_tokens_per_forward"] = (
            out.get("serving/spec_tokens", 0) / forwards if forwards else 0.0)
    cls_requests = 0
    cls_attained = 0
    for key in list(out):
        if key.startswith("serving/slo/") and key.endswith("/attainment"):
            base = key[: -len("/attainment")]
            requests = out.get(f"{base}/requests", 0)
            attained = out.get(f"{base}/attained", 0)
            out[key] = attained / requests if requests else 1.0
            cls_requests += requests
            cls_attained += attained
    if "serving/slo_attainment" in out:
        out["serving/slo_attainment"] = (
            cls_attained / cls_requests if cls_requests else 1.0)
    return out
