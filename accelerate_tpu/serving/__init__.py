"""Continuous-batching serving (`docs/serving.md`).

`ServingEngine` keeps one jitted, static-shape decode step hot and multiplexes
independent requests through a fixed pool of KV-cache slots: slot-level
admission, per-request sampling params, FIFO queue with backpressure, and
counters/histograms exported through the `tracking.py` tracker interface.
"""

from .anomaly import (
    NULL_ANOMALY,
    AnomalyConfig,
    AnomalyMonitor,
    NullAnomalyMonitor,
)
from .autoscaler import DETECTOR_THRASH, AutoscalerConfig, FleetAutoscaler
from .cluster import (
    POLICY_PREFIX,
    POLICY_ROUND_ROBIN,
    ROLE_DECODE,
    ROLE_MIXED,
    ROLE_PREFILL,
    STATE_DEAD,
    STATE_DRAINING,
    STATE_OK,
    STATE_RETIRED,
    ClusterConfig,
    ReplicaHandle,
    ServingCluster,
)
from .engine import PagedKVConfig, RecoveryReport, ServingEngine, StepTimings
from .frontend import (
    EV_STREAM_DELTA,
    EV_STREAM_ERROR,
    EV_STREAM_FINISH,
    EV_STREAM_FIRST,
    ServingFrontend,
    StreamEvent,
    StreamStall,
    TokenStream,
    predict_ttft,
)
from .journal import JournalError, JournalScan, RequestJournal
from .kv_tier import KVTier, KVTierConfig, choose_wake
from .metrics import Counter, Histogram, ServingMetrics
from .prefix_cache import PrefixCache
from .request import (
    FINISH_ABORTED,
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_LENGTH,
    REJECT_DEADLINE,
    REJECT_DRAINING,
    REJECT_OVERLOAD,
    REJECT_PREDICTED_TTFT,
    REJECT_PROMPT_TOO_LONG,
    REJECT_QUEUE_FULL,
    REJECT_UNHEALTHY,
    Request,
    RequestOutput,
    SamplingParams,
    SLOSpec,
    SubmitOptions,
    SubmitResult,
)
from .scheduler import FairScheduler, FIFOScheduler
from .speculation import ModelDrafter, NGramDrafter, SpeculationConfig
from .supervisor import (
    EngineSupervisor,
    EngineUnhealthyError,
    RestartBudget,
    SupervisorConfig,
)
from .telemetry import (
    NULL_TELEMETRY,
    NullTelemetry,
    TelemetryConfig,
    TelemetryExporter,
)
from .trace import NULL_TRACER, NullTracer, TraceEvent, Tracer

__all__ = [
    "ServingEngine",
    "ServingCluster",
    "ClusterConfig",
    "ReplicaHandle",
    "FleetAutoscaler",
    "AutoscalerConfig",
    "DETECTOR_THRASH",
    "STATE_OK",
    "STATE_DRAINING",
    "STATE_DEAD",
    "STATE_RETIRED",
    "ROLE_PREFILL",
    "ROLE_DECODE",
    "ROLE_MIXED",
    "POLICY_PREFIX",
    "POLICY_ROUND_ROBIN",
    "PagedKVConfig",
    "RecoveryReport",
    "StepTimings",
    "AnomalyConfig",
    "AnomalyMonitor",
    "NullAnomalyMonitor",
    "NULL_ANOMALY",
    "RequestJournal",
    "JournalScan",
    "JournalError",
    "KVTier",
    "KVTierConfig",
    "choose_wake",
    "PrefixCache",
    "ServingMetrics",
    "Counter",
    "Histogram",
    "FIFOScheduler",
    "FairScheduler",
    "ServingFrontend",
    "TokenStream",
    "StreamEvent",
    "StreamStall",
    "predict_ttft",
    "EV_STREAM_FIRST",
    "EV_STREAM_DELTA",
    "EV_STREAM_FINISH",
    "EV_STREAM_ERROR",
    "SubmitOptions",
    "SpeculationConfig",
    "NGramDrafter",
    "ModelDrafter",
    "EngineSupervisor",
    "SupervisorConfig",
    "RestartBudget",
    "EngineUnhealthyError",
    "Request",
    "RequestOutput",
    "SamplingParams",
    "SLOSpec",
    "SubmitResult",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "TraceEvent",
    "TelemetryExporter",
    "TelemetryConfig",
    "NullTelemetry",
    "NULL_TELEMETRY",
    "FINISH_EOS",
    "FINISH_LENGTH",
    "FINISH_ABORTED",
    "FINISH_ERROR",
    "REJECT_QUEUE_FULL",
    "REJECT_PROMPT_TOO_LONG",
    "REJECT_DEADLINE",
    "REJECT_DRAINING",
    "REJECT_UNHEALTHY",
    "REJECT_OVERLOAD",
    "REJECT_PREDICTED_TTFT",
]
