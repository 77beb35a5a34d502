"""Request/response surface of the serving engine.

Per-request sampling params (temperature, top_k, seed) are applied *per slot*
inside the shared jitted decode step — they ride as ``[max_concurrency]``
arrays, so two requests with different settings share one compiled program.
"""

from __future__ import annotations

from dataclasses import dataclass, field

# finish reasons
FINISH_EOS = "eos"
FINISH_LENGTH = "length"
FINISH_ABORTED = "aborted"  # cancelled / drained / run() step budget exhausted
FINISH_ERROR = "error"  # watchdog: second poisoned step for the same request

# rejection reason codes (SubmitResult.reason); human detail rides separately
REJECT_QUEUE_FULL = "queue_full"
REJECT_PROMPT_TOO_LONG = "prompt_too_long"
REJECT_EMPTY_PROMPT = "empty_prompt"
REJECT_DEADLINE = "deadline"  # queued past its deadline, never admitted
REJECT_DRAINING = "draining"  # engine is draining toward shutdown
# supervisor rejections (serving/supervisor.py, docs/reliability.md
# "Self-healing"): the restart budget is exhausted and the engine is being
# failed loudly, or the overload brownout is shedding low-priority admissions
REJECT_UNHEALTHY = "unhealthy"
REJECT_OVERLOAD = "overload"
# front-door predictive admission (serving/frontend.py): the TTFT this request
# would see — estimated from capacity headroom, queue depth, and step-phase
# timing EMAs — already exceeds its SLOSpec.ttft_s bound, so it is shed BEFORE
# a slot and prefill are wasted on a reply the client will count as a miss.
# Distinct from REJECT_OVERLOAD, which is the supervisor's *reactive* brownout.
REJECT_PREDICTED_TTFT = "predicted_ttft"


@dataclass(frozen=True)
class SLOSpec:
    """A latency service-level objective one request is served under
    (`docs/observability.md` "SLO and goodput").

    ``ttft_s`` bounds time-to-first-token (arrival → first generated token on
    the host); ``itl_p99_s`` bounds the request's own p99 inter-token gap
    (nearest-rank over its observed decode gaps). Either bound may be None
    (unconstrained). ``name`` is the SLO *class* — per-class attainment
    counters aggregate under it in `ServingMetrics.goodput()`.

    A request **attains** its SLO iff it finishes cleanly (EOS or length —
    aborted/errored/expired requests are misses by definition) and every set
    bound holds. Tokens from attaining requests are *goodput*; the rest is
    throughput the client gave up on.
    """

    ttft_s: float | None = None
    itl_p99_s: float | None = None
    name: str = "default"


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decode settings (the `models/generation.generate` knobs plus
    a seed: temperature=0 is greedy, otherwise categorical with optional top-k;
    the seed makes a sampled request reproducible across runs and engines)."""

    temperature: float = 0.0
    top_k: int | None = None
    seed: int = 0
    max_new_tokens: int = 32


@dataclass
class Request:
    """One generation request: a token-id prompt plus its sampling params.

    ``request_id``/``arrival_time`` are stamped by `ServingEngine.submit`;
    supply ``arrival_time`` explicitly to replay a recorded trace.

    ``deadline_s`` is a queue-wait budget: a request still queued
    ``deadline_s`` seconds after arrival is expired with `REJECT_DEADLINE`
    instead of being admitted (serving a reply the client already gave up on
    wastes a slot). ``retries`` is stamped by the engine's step watchdog: a
    poisoned decode step re-prefills the request once from its prompt, a
    second poisoning retires it with `FINISH_ERROR`.

    ``cache_prefix`` opts this request out of prefix KV reuse when False: its
    prompt is always prefilled from token 0 and its KV is never donated to
    the shared pool (`serving/prefix_cache.py` — opt out for privacy-scoped
    prompts or A/B measurement; tokens are identical either way).

    ``slo`` optionally attaches an `SLOSpec`: the engine evaluates TTFT /
    per-request ITL-p99 bounds at retirement and feeds the per-class
    attainment + goodput counters in `metrics.ServingMetrics` (requests
    without an SLO are unconstrained and always count as goodput). The SLO is
    host-side accounting only — it never affects scheduling, and it is not
    journaled (a restart re-serves the work; the client re-attaches its SLO
    if it still cares).

    ``resume_tokens`` is the crash-recovery handle (`docs/reliability.md`
    "Serving recovery"): tokens this request had ALREADY emitted before an
    engine restart. Admission then prefills ``prompt + resume_tokens`` in one
    pass and fast-forwards the request's rng chain by ``len(resume_tokens)``
    splits, so decode continues mid-stream bit-for-bit with an uninterrupted
    run. Stamped by `ServingEngine.resume` — normal submissions leave it
    empty.
    """

    prompt: list[int]
    params: SamplingParams = field(default_factory=SamplingParams)
    request_id: int | None = None
    arrival_time: float | None = None
    deadline_s: float | None = None
    retries: int = 0
    cache_prefix: bool = True
    slo: SLOSpec | None = None
    resume_tokens: list[int] = field(default_factory=list)
    # admission priority class (higher = more important; default 0 = lowest).
    # Read in two places: the supervisor's overload BROWNOUT sheds new
    # admissions with priority < level (REJECT_OVERLOAD,
    # serving/supervisor.py), and the `FairScheduler` serves higher classes
    # first within its starvation bound. Under the default `FIFOScheduler`
    # scheduling order is unaffected — FIFO holds.
    priority: int = 0
    # fair-share accounting key (`FairScheduler`): requests with the same
    # tenant share one deficit-weighted budget, so one chatty client cannot
    # monopolize its priority class. Journaled and restored across crash
    # resume and replica migration. "" = the anonymous shared tenant.
    tenant: str = ""
    # when the scheduler last queued it (`time.perf_counter()`; None while
    # not waiting): the start of its `serve.queued` span (`utils/spans.py`)
    queued_time: float | None = None

    @property
    def prefill_len(self) -> int:
        """Tokens admission must fit in a prompt bucket: the prompt plus any
        resumed stream prefix (what actually gets prefilled)."""
        return len(self.prompt) + len(self.resume_tokens)

    def prefill_source(self) -> list[int]:
        """The token sequence admission prefills for this request."""
        return (self.prompt + self.resume_tokens if self.resume_tokens
                else self.prompt)


@dataclass
class RequestOutput:
    """Tokens generated for one request, with host-clock latency marks
    (`metrics.ServingMetrics` aggregates these into TTFT / inter-token
    histograms).

    ``token_times[i]`` is the ``time.perf_counter()`` stamp at which
    ``tokens[i]`` reached the host: the end of the ``serve.fetch`` span that
    brought it, so the tokens of one dispatch (``tokens_per_sync``,
    speculation) share a stamp. ``token_times[0] == first_token_time``, and
    for a request that ended on a token (`FINISH_EOS`, `FINISH_LENGTH`)
    ``token_times[-1] == finish_time``. Tokens that this process did not
    deliver carry ``nan``: the prefix a stream re-admitted through
    `Request.resume_tokens` starts from (crash recovery, replica migration, a
    hibernated stream woken by re-prefill). An output rebuilt from a journal
    alone has no stamps at all (``token_times == []``)."""

    request_id: int
    prompt_len: int
    tokens: list[int]
    finish_reason: str
    arrival_time: float | None = None
    first_token_time: float | None = None
    finish_time: float | None = None
    token_times: list[float] = field(default_factory=list)


@dataclass(frozen=True)
class SubmitOptions:
    """Per-request front-door knobs (`serving/frontend.py`): everything a
    caller chooses ABOUT a submission rather than IN it.

    ``priority`` picks the scheduling class (higher served first, subject to
    the `FairScheduler` starvation bound); ``tenant`` names the fair-share
    account the request bills against; ``slo`` attaches the latency objective
    that both predictive admission (reject with `REJECT_PREDICTED_TTFT` when
    the estimated TTFT already busts ``slo.ttft_s``) and retirement-time
    attainment accounting read; ``deadline_s`` is the queue-wait budget
    (`REJECT_DEADLINE`); ``cache_prefix`` opts out of prefix-KV reuse.
    ``admit_despite_slo`` submits even when predictive admission would reject
    (the caller prefers a late answer over no answer)."""

    priority: int = 0
    tenant: str = ""
    slo: SLOSpec | None = None
    deadline_s: float | None = None
    cache_prefix: bool = True
    admit_despite_slo: bool = False

    def apply(self, request: Request) -> Request:
        """Stamp these options onto ``request`` (mutates and returns it)."""
        request.priority = int(self.priority)
        request.tenant = str(self.tenant)
        if self.slo is not None:
            request.slo = self.slo
        if self.deadline_s is not None:
            request.deadline_s = float(self.deadline_s)
        request.cache_prefix = bool(self.cache_prefix)
        return request


@dataclass(frozen=True)
class SubmitResult:
    """Admission verdict: accepted into the queue, or rejected with a reason
    code (backpressure — the caller decides whether to retry or shed load)."""

    accepted: bool
    request_id: int | None = None
    reason: str | None = None
    detail: str | None = None
