"""Continuous-batching serving engine: one hot decode step, many requests,
pipelined host/device dispatch.

`models/generation.generate` runs a batch in lockstep — equal-length prompts,
every row decodes until the slowest finishes, nobody joins mid-flight. This
engine multiplexes independent requests through ONE jitted, static-shape
decode step instead (the serving half of the ROADMAP north star):

  - ONE pre-allocated KV store, the paged block pool: ``[num_blocks,
    block_tokens, kv_heads * head_dim]`` buffers (`models/kv_cache.py`; int8
    storage via the model config's ``kv_cache_dtype``) that every slot reaches
    through its row of a ``[max_concurrency, n_positions / block_tokens]``
    block table, sized by ``paged_kv=PagedKVConfig(...)``;
  - admission prefills up to ``admit_batch`` queued requests of one prompt
    bucket in a SINGLE jitted call (one compile per ``(prompt_bucket,
    batch_bucket)`` pair) into fresh contiguous rows, samples their first
    tokens, and scatters the rows into the slots' reserved blocks at once
    (`kv_cache.scatter_rows_to_blocks`);
  - with ``prefix_cache=True``, admission first reuses any cached prompt
    prefix (`serving/prefix_cache.py`): the matched blocks of the pool are
    aliased into the slot's block table and only the uncached suffix is
    prefilled (re-bucketed, so compiles stay bounded); retirement hands the
    finished prompt's blocks to the trie. Token streams are identical either
    way;
  - ``step()`` decodes ALL slots in one jitted call with donated cache
    buffers; per-slot positions, sampling params, rng keys, remaining budget,
    and the finished mask are DEVICE-RESIDENT ``[max_concurrency]`` arrays,
    written only by the jitted admission scatter — the decode hot loop uploads
    nothing per token.

The decode loop is **self-feeding and pipelined**: step N+1 dispatches
immediately from step N's on-device sampled tokens while the host fetch of
step N's results completes asynchronously, up to ``pipeline_depth`` dispatches
in flight (depth 1 reproduces fully synchronous dispatch bit-for-bit). An
on-device finished mask — EOS hit, token budget, context limit, or watchdog
health — freezes a slot inside the compiled step (token/position/cache writes
all stop, `kv_cache.paged_decode_update(write_mask=...)`), so host-side
retirement/backfill lagging by up to ``pipeline_depth`` steps can never
corrupt a stream: the host simply truncates the lagged tail at the finish
point, token-identical to a solo ``generate``. A per-slot generation counter
discards fetched results that postdate a retirement/cancel/quarantine.

Static-shape invariant (the whole point): the decode step's shapes depend only
on ``(max_concurrency, num_blocks, n_positions, model config)`` and admission's only on
``(prompt_bucket, batch_bucket)``. Everything request-specific is data, not
shape.

Sampling parity: the per-slot sampler value-matches `generation._sample` and
the per-slot rng chain matches `generate`'s split sequence for a batch-1 call,
so a request served here emits the SAME tokens as a solo ``generate`` with
``rng=jax.random.key(seed)`` — at every ``pipeline_depth`` and ``admit_batch``
(tests/test_serving.py proves it token-level).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import time
from collections import deque
from pathlib import Path
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..models.kv_cache import (
    BlockAllocator,
    _is_index_leaf,
    gather_block_rows,
    leaf_name,
    make_cache,
    rewind_frontier,
    scatter_rows_to_blocks,
    state_nbytes,
    tree_bytes_by_dtype,
    tree_nbytes,
)
from ..parallel.mesh import ParallelismConfig, serving_mesh
from ..parallel.sharding import (
    block_table_sharding,
    infer_cache_shardings,
    infer_param_shardings,
    kv_cache_sharding,
    shard_params,
)
from ..reliability.faults import ALL_SLOTS, active_injector
from ..utils import spans
from ..utils.environment import device_memory_stats
from ..utils.quantization import (
    QuantizationConfig,
    QuantizedModule,
    QuantizedTensor,
    quantize_params,
    quantized_nbytes,
)
from .anomaly import NULL_ANOMALY
from .journal import MAGIC as JOURNAL_MAGIC
from .journal import JournalScan, RequestJournal, request_record
from .kv_tier import KVTier, KVTierConfig
from .metrics import ServingMetrics
from .prefix_cache import NO_MATCH, PrefixCache, PrefixMatch
from .request import (
    FINISH_ABORTED,
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_LENGTH,
    REJECT_DEADLINE,
    REJECT_DRAINING,
    REJECT_QUEUE_FULL,
    Request,
    RequestOutput,
    SamplingParams,
    SubmitResult,
)
from .scheduler import FIFOScheduler
from .speculation import resolve_drafter
from .telemetry import NULL_TELEMETRY
from .trace import (
    EV_ADMIT,
    EV_DISPATCH,
    EV_FETCH,
    EV_FINISH,
    EV_QUARANTINE,
    EV_REJECT,
    EV_SUBMIT,
    NULL_TRACER,
    nearest_rank,
)


def _sample_slot(logits: jax.Array, key: jax.Array, temperature: jax.Array,
                 top_k: jax.Array, *, mask_top_k: bool = True) -> jax.Array:
    """Sample one slot's next token from ``[vocab]`` logits.

    Value-matches `models/generation._sample` on a single row with the same
    key (the parity contract), but temperature/top_k are DATA here — the
    static python branches become jnp.where so every slot can carry its own
    settings inside one compiled step. top_k == 0 disables the top-k mask.
    ``mask_top_k=False`` is the same body for rows known to carry
    ``top_k == 0``: the mask's condition holds ``top_k > 0``, so ``masked ==
    scaled`` and the vocabulary-wide sort that feeds it is left out.
    """
    greedy = jnp.argmax(logits, axis=-1)
    vocab = logits.shape[-1]
    safe_t = jnp.where(temperature > 0, temperature, jnp.ones_like(temperature))
    scaled = logits / safe_t
    if mask_top_k:
        ordered = jnp.sort(scaled, axis=-1)  # ascending, like _sample's kth lookup
        kth = jnp.take(ordered, vocab - jnp.clip(top_k, 1, vocab))
        scaled = jnp.where((top_k > 0) & (scaled < kth), -jnp.inf, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def _host_key_data(seed: Any) -> np.ndarray | None:
    """``jax.random.key_data(jax.random.key(seed))`` as a ``uint32`` row made
    on the host, or None where only the device gives it exactly.

    Under the default ``threefry2x32`` a key's data is the seed's high and
    low 32-bit words after `jax.random.key`'s conversion: a Python int is
    taken as an ``int64`` (outside it `jax.random.key` raises, so it keeps
    that path), and with ``jax_enable_x64`` off narrowed to ``int32``, which
    leaves the high word 0 and a negative seed its low word's bits. Another
    PRNG implementation, a seed offset or a seed that is no Python int
    returns None."""
    if (not isinstance(seed, int)
            or jax.config.jax_default_prng_impl != "threefry2x32"
            or jax.config.jax_random_seed_offset
            or not -(1 << 63) <= seed < (1 << 63)):
        return None
    bits = seed & ((1 << 64) - 1 if jax.config.jax_enable_x64 else (1 << 32) - 1)
    return np.array([bits >> 32, bits & 0xFFFFFFFF], np.uint32)


def _sample_rows(logits: jax.Array, keys: jax.Array, temperature: jax.Array,
                 top_k: jax.Array, live: jax.Array) -> jax.Array:
    """Sample every row's next token from ``[rows, vocab]`` logits, paying
    only for what the ``live`` rows ask for.

    `_sample_slot` under `jax.vmap` charges every row, every step, for the
    heaviest setting a row could carry: the vocabulary-wide sort whose k-th
    value is thrown away at ``top_k == 0`` and the random draw thrown away at
    ``temperature == 0``. Here ONE `lax.switch`, outside the vmap so XLA
    emits a real ``conditional`` (a per-row predicate inside a vmap turns
    back into a select that runs both sides), picks for the whole batch:

    0. no live row draws: ``argmax`` only;
    1. some live row draws, none with top-k: `_sample_slot` without the sort;
    2. some live row draws with top-k: `_sample_slot` for every row.

    Live rows' tokens are bit-equal to ``jax.vmap(_sample_slot)``'s in every
    case (tests/test_sample_tail.py); a row that is not ``live`` (a finished
    or vacant slot still carrying its last tenant's settings) may get a
    lighter branch's token, which its caller discards. The keys are split by
    the caller, outside the branches: the rng chain is one split a token
    whichever branch runs.
    """
    draws = live & (temperature > 0)
    branch = (jnp.any(draws).astype(jnp.int32)
              + jnp.any(draws & (top_k > 0)).astype(jnp.int32))
    return jax.lax.switch(
        branch,
        (lambda logits, *_: jnp.argmax(logits, axis=-1).astype(jnp.int32),
         jax.vmap(functools.partial(_sample_slot, mask_top_k=False)),
         jax.vmap(_sample_slot)),
        logits, keys, temperature, top_k)


def _decode_tail(last, live, tokens, pos, temps, top_ks, rng_data, finished,
                 remaining, poison, eos_id):
    """One decode turn after the model, shared by the step and the scan
    body: from the ``[b, vocab]`` last-position logits to ``(next tokens,
    positions, budgets, finished mask, rng key data, health)``."""
    # fault injection rides INSIDE the compiled step (poison is a [b] data
    # mask, all-False in production): NaN logits flow through the real
    # sampler so the watchdog sees exactly what a numerically poisoned model
    # step would produce
    last = jnp.where(poison[:, None], jnp.asarray(jnp.nan, last.dtype), last)
    # watchdog health flag: a non-finite logit row means this slot's sampled
    # token is garbage, whatever index it lands on
    ok = jnp.all(jnp.isfinite(last), axis=-1)
    rngs = jax.random.wrap_key_data(rng_data)
    split = jax.vmap(jax.random.split)(rngs)  # [b, 2] keys
    new_rngs, keys = split[:, 0], split[:, 1]
    sampled = _sample_rows(last, keys, temps, top_ks, live)
    healthy = live & ok
    nxt = jnp.where(healthy, sampled, tokens)
    new_pos = jnp.where(healthy, pos + 1, pos)
    new_remaining = jnp.where(healthy, remaining - 1, remaining)
    hit_eos = (eos_id >= 0) & (nxt == eos_id)
    # the on-device finish sources: EOS, token budget (which already encodes
    # the context limit), and watchdog health — a poisoned slot freezes
    # immediately so it stops mutating its cache while the host decides to
    # quarantine it
    new_finished = finished | (live & (~ok | hit_eos | (new_remaining <= 0)))
    return (nxt, new_pos, new_remaining, new_finished,
            jax.random.key_data(new_rngs), ok | finished)


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-unfetched device computation.

    ``arrays`` are the device outputs the host will need (tokens + finished
    mask, plus the health flag for decode steps); ``slots``/``gens`` pin each
    result to the slot GENERATION it was dispatched against, so a result that
    postdates a retirement, cancel, or quarantine is discarded instead of
    being attributed to the slot's next tenant.
    """

    kind: str  # "step" | "admit"
    arrays: tuple
    slots: tuple[int, ...]
    gens: tuple[int, ...]
    # pairing handle: the sequence number `_dispatch` drew for the program
    # whose results these are, carried by its `serve.dispatch` span and its
    # EV_DISPATCH event and echoed by `serve.fetch` / EV_FETCH
    seq: int = -1
    # decode iterations this dispatch ran (tokens_per_sync); the fetched
    # arrays are stacked [tokens, b] when > 1, plain [b] when 1
    tokens: int = 1


_STEP_PHASES = ("schedule_s", "draft_s", "dispatch_s", "fetch_blocked_s",
                "deliver_s", "journal_s", "telemetry_s", "total_s")


@dataclasses.dataclass
class StepTimings:
    """Host wall-time breakdown of ONE `ServingEngine.step()` call
    (docs/observability.md "Latency attribution").

    ``schedule_s`` is reap/admission bookkeeping net of everything measured
    elsewhere; ``draft_s`` the drafter proposal; ``dispatch_s`` every jitted
    call (compile or replay); ``fetch_blocked_s`` the host blocked in
    ``device_get``; ``deliver_s`` detokenize/retire/SLO accounting net of
    journal writes; ``journal_s`` journal appends incl. fsync; ``telemetry_s``
    the telemetry poll. The phases partition ``total_s`` up to clock jitter.
    ``total_s``, ``draft_s``, ``dispatch_s``, ``fetch_blocked_s`` and
    ``telemetry_s`` are the lengths of the step's ``serve.*`` spans in
    `utils.spans.RING`: one set of stamps feeds both. ``deliver_s`` is the
    ``serve.deliver`` spans net of the journal appends inside them, and
    ``schedule_s`` the step's stretch to the end of its ``serve.admit`` span
    net of the dispatch, fetch, deliver and journal spans inside it.
    """

    schedule_s: float = 0.0
    draft_s: float = 0.0
    dispatch_s: float = 0.0
    fetch_blocked_s: float = 0.0
    deliver_s: float = 0.0
    journal_s: float = 0.0
    telemetry_s: float = 0.0
    total_s: float = 0.0

    def reset(self) -> None:
        for name in _STEP_PHASES:
            setattr(self, name, 0.0)

    def as_dict(self) -> dict[str, float]:
        return {name: round(getattr(self, name), 6) for name in _STEP_PHASES}


# engine snapshot file format tag (docs/reliability.md "Serving recovery"):
# a JSON document written atomically (tmp + fsync + rename) by
# `ServingEngine.snapshot`, restorable by `ServingEngine.resume`
SNAPSHOT_FORMAT = "accelerate_tpu/serving-snapshot-v1"


@dataclasses.dataclass(frozen=True)
class PagedKVConfig:
    """The size of the engine's KV store, its ``paged_kv=`` argument
    (`docs/serving.md` "Paged KV").

    ``block_tokens`` is the allocation granularity, and the prefix cache's
    reuse granularity (its trie aliases these blocks): smaller blocks waste
    less of the last partially-filled block per request (internal
    fragmentation bounded by ``block_tokens - 1`` tokens) and reuse more of a
    shared prefix, but mean a bigger table, more trie nodes and more
    allocator work per admission. Must be a power of two dividing
    ``n_positions``. ``num_blocks`` sizes the shared pool; None derives
    ``max_concurrency * (n_positions / block_tokens)``: every slot can hold
    a full context at once, and what ragged requests leave free is the
    prefix trie's room."""

    block_tokens: int = 16
    num_blocks: int | None = None


@dataclasses.dataclass(frozen=True)
class WeightQuantConfig:
    """Knobs for the engine's ``weight_quant=`` argument (`docs/serving.md`
    "Quantized serving").

    ``mode`` picks the packed format: ``"int8"`` is per-channel absmax
    (`utils/quantization.QuantizationConfig(load_in_8bit=True)`), ``"nf4"``
    is blockwise 4-bit NormalFloat over ``block_size``-element groups. Both
    dequantize through XLA inside the trace: ``"nf4"`` shares the codebook of
    the Pallas kernel in `ops/nf4_matmul.py` but never runs it (inside a
    jitted program the payload is a tracer and that kernel needs a concrete
    one). Leaves smaller than ``min_weight_size``
    elements (embeddings' peers: LayerNorm scales, biases) stay dense — the
    same eligibility rule `quantize_params` applies everywhere else.

    The engine quantizes the param tree ONCE at load and the jitted
    step/admit/spec programs consume the packed leaves directly:
    `QuantizedModule.apply` dequantizes inside the trace, so XLA fuses
    unpack+scale into the consuming matmuls and HBM holds only payload +
    scales. fp streams are untouched — ``weight_quant=None`` (the default)
    changes no module, no params, and no trace."""

    mode: str = "int8"
    block_size: int = 64
    min_weight_size: int = 4096

    def __post_init__(self):
        if self.mode not in ("int8", "nf4"):
            raise ValueError(
                f"weight_quant mode must be 'int8' or 'nf4', got {self.mode!r}")

    def quantization_config(self, compute_dtype: Any) -> QuantizationConfig:
        """The `utils/quantization.QuantizationConfig` this mode maps onto.
        ``compute_dtype`` should be the module's param dtype so dequantized
        leaves re-enter the model at the precision the fp path used."""
        if self.mode == "int8":
            return QuantizationConfig(
                load_in_8bit=True,
                compute_dtype=compute_dtype,
                min_weight_size=self.min_weight_size,
            )
        return QuantizationConfig(
            load_in_4bit=True,
            quant_type="nf4",
            block_size=self.block_size,
            compute_dtype=compute_dtype,
            min_weight_size=self.min_weight_size,
        )


# Per-(module, mode) cache of `QuantizedModule` wrappers. The wrapper IS the
# `_SHARED_JITS` key for a quantized engine (entries key on id(module)), so
# caching it per mode does double duty: engines over the same base module and
# quant mode share every trace exactly like fp engines do, while different
# modes — and the fp path, which keeps the bare module — can never
# cross-contaminate a trace cache. Entries pin the wrapper (which pins the
# base module), so neither id() can be reused by a new object.
_QUANT_MODULES: dict[tuple[int, str], QuantizedModule] = {}


def _quantized_module(module: Any, mode: str) -> QuantizedModule:
    key = (id(module), mode)
    wrapper = _QUANT_MODULES.get(key)
    if wrapper is None or wrapper.module is not module:
        wrapper = _QUANT_MODULES[key] = QuantizedModule(module)
    return wrapper


# Process-level cache of the unsharded engines' jitted programs. An unsharded
# engine's step/admit closures depend only on the module (every per-engine
# quantity — slot count, buckets, sampling state — enters as a traced argument
# and specializes per shape under the one jit wrapper), so a fresh engine over
# the same module — a crash-recovery resume, an A/B replica, a test fixture —
# reuses every existing trace instead of recompiling it. Entries pin a strong
# module ref so the id() key can never be reused by a new object. Sharded
# engines keep per-instance jits: their shardings genuinely differ.
_SHARED_JITS: dict[int, tuple[Any, dict[str, Any]]] = {}


def _shared_jit(module: Any, kind: str, build):
    ref, fns = _SHARED_JITS.setdefault(id(module), (module, {}))
    if ref is not module:  # unreachable while entries pin their module
        ref, fns = _SHARED_JITS[id(module)] = (module, {})
    if kind not in fns:
        fns[kind] = build()
    return fns[kind]


@functools.partial(jax.jit, static_argnums=1)
def _cast_leaves(leaves: list, dtype: Any) -> list:
    return [leaf.astype(dtype) for leaf in leaves]


def _narrow_params(params: Any, declared: Callable[[tuple[str, ...]], bool],
                   dtype: Any) -> tuple[Any, int]:
    """Hold in ``dtype`` each floating leaf wider than it that ``declared``
    (`CacheContract.compute_dtype_params`, a predicate over the leaf's path of
    key names) names, cast in one jitted program; every other leaf comes back
    as given. Returns the tree and the bytes the narrowing saved."""
    dtype = jnp.dtype(dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [leaf for _, leaf in flat]
    picked = [i for i, (path, leaf) in enumerate(flat)
              if jnp.issubdtype(leaf.dtype, jnp.floating)
              and leaf.dtype.itemsize > dtype.itemsize
              and declared(tuple(str(getattr(k, "key", k)) for k in path))]
    if not picked:
        return params, 0
    saved = 0
    for i, leaf in zip(picked, _cast_leaves([leaves[i] for i in picked], dtype)):
        saved += int(leaves[i].nbytes) - int(leaf.nbytes)
        leaves[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, leaves), saved


@dataclasses.dataclass
class RecoveryReport:
    """What `ServingEngine.resume` reconstructed from a journal or snapshot.

    ``resumed`` requests were mid-decode at the crash and re-enter admission
    with their emitted tokens as a continuation prefill; ``restored`` were
    still queued and re-enter the queue in submit order. ``completed`` maps
    request id -> the terminal `RequestOutput` recovered from journal FINISH
    records (dedupe these against any results the dead process already
    delivered). ``expired`` are queued requests whose wall-clock
    ``deadline_s`` elapsed during the downtime — rejected at restore time
    with ``rejected:deadline``, reported here rather than silently dropped.
    """

    source: str
    resumed: list[int] = dataclasses.field(default_factory=list)
    restored: list[int] = dataclasses.field(default_factory=list)
    completed: dict[int, RequestOutput] = dataclasses.field(default_factory=dict)
    expired: list[RequestOutput] = dataclasses.field(default_factory=list)
    downtime_s: float = 0.0
    truncated_tail_bytes: int = 0


class ServingEngine:
    """Request-level continuous batching over a fixed pool of decode slots.

    ``module`` is any causal LM whose config declares a cache contract
    (``config.cache_contract()``, `models/kv_cache.CacheContract`: GPT-2's keys
    and values, keys and values beside per-slot recurrent state, or one
    latent leaf a layer with the value inside the key row, Ling 3.0's
    per-slot KDA state beside one latent leaf in one layer of six); the
    engine re-instantiates it with its cache switches on, so callers pass the
    same module they would hand to ``generate``. ``params`` is the matching
    param tree. The context length is the config's ``n_positions``. A model
    that declares recurrent state is refused ``prefix_cache``, ``kv_tier``,
    ``speculation`` and ``mesh`` at construction: each of them addresses or
    rewinds the cache by token position, which a recurrent state has not.

    ``pipeline_depth`` bounds how many decode dispatches may be in flight
    before the host blocks on the oldest fetch (1 = fully synchronous, the
    pre-pipelining behavior, bit-for-bit). ``admit_batch`` caps how many
    same-bucket queued requests one jitted prefill admits (batch buckets are
    the powers of two up to it, so compiles stay bounded).

    ``mesh`` shards the whole engine over a ``(data, model)`` device mesh
    (a `jax.sharding.Mesh`, a `ParallelismConfig`, or a ``(data, model)``
    tuple): params by the Megatron-style TP rules, the block pool on heads
    along the model axis (which must divide ``n_head``) with its blocks
    replicated over ``data``, and — when the data degree divides
    ``max_concurrency`` — the per-slot state and block tables across
    replicas, which then decode disjoint slot ranges. Token streams are
    bit-identical to ``mesh=None`` (tests/test_serving_sharded.py proves the
    matrix); the scheduler, pipelining, and all host-side bookkeeping are
    mesh-oblivious.

    ``tracer=`` attaches a `serving.trace.Tracer`: every request lifecycle
    edge and every jitted dispatch/fetch pair is recorded as a span event,
    exportable to Perfetto via ``tracer.export(path)`` and summarized by
    ``tools/trace_report.py`` (`docs/observability.md`). Default: no tracer,
    zero overhead. Requests carrying a `request.SLOSpec` additionally feed
    `ServingMetrics.goodput()` attainment accounting at retirement.

    Typical loop::

        engine = ServingEngine(module, params, max_concurrency=8)
        engine.submit(prompt_ids, SamplingParams(max_new_tokens=64))
        while engine.has_work:
            for out in engine.step():
                ...  # out.tokens, out.finish_reason

    or just ``outputs = engine.run(requests)``.
    """

    def __init__(
        self,
        module: Any,
        params: Any,
        *,
        max_concurrency: int = 8,
        prompt_buckets: tuple[int, ...] = (32, 128, 512),
        max_queue: int = 128,
        eos_token_id: int | None = None,
        pipeline_depth: int = 2,
        admit_batch: int = 4,
        prefix_cache: bool = False,
        paged_kv: PagedKVConfig | bool = True,
        tracker: Any = None,
        metrics_log_every: int = 0,
        metrics: ServingMetrics | None = None,
        mesh: Any = None,
        param_rules: Any = None,
        journal: Any = None,
        tracer: Any = None,
        telemetry: Any = None,
        tokens_per_sync: int = 1,
        paged_attention: str = "gather",
        speculation: Any = None,
        anomaly: Any = None,
        scheduler: Any = None,
        kv_tier: KVTierConfig | bool | None = None,
        weight_quant: WeightQuantConfig | str | None = None,
    ):
        cfg = getattr(module, "config", None)
        if cfg is None or not hasattr(cfg, "cache_contract"):
            raise TypeError(
                f"{type(module).__name__}'s config declares no cache contract; "
                "the serving engine needs `config.cache_contract()` and the "
                "per-slot cache switches it describes (models/kv_cache.py "
                "CacheContract) — GPT2LMHead, Qwen3NextForCausalLM, "
                "KimiK2ForCausalLM and Ling3ForCausalLM have them."
            )
        contract = self._contract = cfg.cache_contract()
        if contract.state_leaves:
            # per-slot recurrent state is no function of a token range: it can
            # be neither shared by prefix, nor spilled and restored by block,
            # nor rewound after a rejected draft. Refuse here, never answer
            # wrongly; a mesh layout for it (and the experts' exchange) is
            # not written either.
            for name, on in (("prefix_cache", prefix_cache), ("kv_tier", kv_tier),
                             ("speculation", speculation is not None),
                             ("mesh", mesh is not None)):
                if on:
                    raise ValueError(
                        f"{type(module).__name__} keeps per-slot recurrent state "
                        f"{contract.state_leaves}; {name} is not supported for it")
        if contract.value_dim is not None and mesh is not None:
            # one latent row a token is shared by all heads: the pool has no
            # head dim to split over the model axis, and such a model's
            # experts' exchange is not written. Refuse, never answer wrongly.
            raise ValueError(
                f"{type(module).__name__} keeps one latent cache leaf a layer "
                "(CacheContract.value_dim) shared by all heads; mesh is not "
                "supported for it")
        self.max_concurrency = int(max_concurrency)
        if self.max_concurrency < 1:
            raise ValueError(f"max_concurrency must be >= 1, got {max_concurrency}")
        # the KV store (docs/serving.md "Paged KV"): keys and values live ONLY
        # in a shared device-resident block pool — per-slot block tables
        # address it, admission reserves blocks on demand, and prefix-cache
        # hits are zero-copy table aliasing. ``paged_kv`` sizes it: True is
        # `PagedKVConfig()`. The bool form is what the benchmark's workload
        # files pass (ROADMAP.md D4).
        if isinstance(paged_kv, PagedKVConfig):
            pk = paged_kv
        elif paged_kv is True:
            pk = PagedKVConfig()
        else:
            raise ValueError(
                f"paged_kv={paged_kv!r}: the per-slot contiguous KV store was "
                "removed; the paged block pool is the engine's only store. "
                "Pass True (the default) or a PagedKVConfig that sizes it")
        bt = int(pk.block_tokens)
        n_pos = int(cfg.n_positions)
        if bt < 1 or (bt & (bt - 1)) or n_pos % bt:
            raise ValueError(
                f"paged_kv block_tokens must be a power of two dividing "
                f"n_positions={n_pos}, got {bt}"
            )
        # kv_cache_dtype=int8 composes with paging: the block pool stores
        # the int8 payload and carries the fp32 absmax scales as sibling
        # [num_blocks, block_tokens, kv_heads] pool leaves addressed
        # through the same block table (models/kv_cache.py
        # `paged_decode_write`) — no rejection, no special casing here.
        self._block_tokens = bt
        self._blocks_per_slot = n_pos // bt
        n_blocks = (int(pk.num_blocks) if pk.num_blocks is not None
                    else self.max_concurrency * self._blocks_per_slot)
        if n_blocks < self._blocks_per_slot:
            raise ValueError(
                f"num_blocks={n_blocks} cannot seat even one full-context "
                f"request ({self._blocks_per_slot} blocks of "
                f"{bt} tokens) — admission would backpressure forever"
            )
        self._allocator = BlockAllocator(n_blocks)
        # fused paged decode (docs/serving.md "Fused paged decode"): "fused"
        # makes decode attention read K/V blocks in place through the block
        # table (the Pallas kernel `ops.flash_attention.paged_decode_attention`)
        # instead of materializing pool[table] into a contiguous view per
        # layer per step. "gather" — the default — stays the parity oracle
        # and the bit-for-bit PR 9 decode program.
        self.paged_attention = str(paged_attention)
        if self.paged_attention not in ("gather", "fused"):
            raise ValueError(
                f"paged_attention must be 'gather' or 'fused', "
                f"got {paged_attention!r}"
            )
        if self.paged_attention == "fused" and not hasattr(cfg, "kv_paged_attention"):
            raise ValueError(
                f"{type(module).__name__} has no kv_paged_attention config "
                "flag; the fused paged decode path needs it (models/gpt2.py)"
            )
        # mesh-sharded serving (docs/serving.md "Sharded serving"): ``mesh`` is
        # a Mesh, a ParallelismConfig, or a (data, model) tuple. The model axis
        # is the standard ``tensor`` axis — params shard by the training-path
        # TP rules, the block pool shards on heads (blocks replicated over
        # ``data``), and (when divisible) the per-slot state and block tables
        # shard on ``data`` so replicas decode disjoint slot ranges. None
        # keeps the single-device engine bit-for-bit: no sharding objects are
        # created and every jit call below is exactly the unsharded one.
        self.mesh = self._resolve_mesh(mesh)
        self._mesh_data = self.mesh.shape.get("data", 1) if self.mesh is not None else 1
        self._mesh_model = self.mesh.shape.get("tensor", 1) if self.mesh is not None else 1
        self._slot_sharding = None    # KVCacheSharding for the block pool + cursor
        self._fresh_sharding = None   # head-only variant for admission's nb rows
        self._cache_shardings = None  # NamedSharding pytrees congruent with ...
        self._fresh_shardings = None  # ... the pool / fresh-rows trees
        self._param_shardings = None
        self._row_sharding = None     # [max_concurrency] per-slot state vectors
        self._rep_sharding = None     # replicated scalars / [nb] admission inputs
        self._table_sharding = None   # [max_concurrency, blocks_per_slot] tables
        if self.mesh is not None:
            extra = {n: s for n, s in self.mesh.shape.items()
                     if n not in ("data", "tensor") and s > 1}
            if extra:
                raise ValueError(
                    f"the serving engine shards over (data, tensor) only; "
                    f"mesh has extra non-trivial axes {extra}"
                )
            if self._mesh_model > 1 and contract.kv_heads % self._mesh_model:
                raise ValueError(
                    f"model-axis degree {self._mesh_model} must divide "
                    f"n_head={contract.kv_heads} (attention is sharded over heads)"
                )
            self._slot_sharding = kv_cache_sharding(
                self.mesh, slots=self.max_concurrency, paged=True
            )
            self._fresh_sharding = kv_cache_sharding(self.mesh, slots=None)
            self._row_sharding = self._slot_sharding.index
            self._rep_sharding = NamedSharding(self.mesh, PartitionSpec())
            # block tables follow the slot dim's layout (each replica
            # indexes the replicated pool through its own slots' rows)
            self._table_sharding = block_table_sharding(
                self.mesh, slots=self.max_concurrency
            )
        if self.paged_attention == "fused":
            # the kernel streams chunks of the folded row through VMEM; a row
            # too wide for it fails HERE with the sizes named — never at the
            # first decode step, and never by quietly serving through "gather"
            from ..ops.flash_attention import check_paged_decode_fits

            check_paged_decode_fits(
                contract.kv_heads // self._mesh_model, contract.head_dim,
                block_tokens=self._block_tokens, value_dim=contract.value_dim,
                value_head_dim=contract.value_head_dim,
            )
        # contiguous slot ranges per data replica (the slot dim shards like any
        # leading batch dim: replica i owns rows [i*b/d, (i+1)*b/d)) — 1 when
        # the slot dim is replicated (b % data != 0, or no mesh)
        self._slot_replicas = (
            self._mesh_data
            if self._mesh_data > 1 and self.max_concurrency % self._mesh_data == 0
            else 1
        )
        # the DECODE module owns the block pool; its cache collection is
        # the [num_blocks, block_tokens, ...] pool plus the per-slot
        # cursor, and every decode step attends through the block table
        updates: dict[str, Any] = {
            "kv_cache_per_slot": True,
            "kv_cache_paged": True,
            "kv_num_blocks": self._allocator.num_blocks,
            "kv_block_tokens": self._block_tokens,
        }
        # "gather" is the config default — adding nothing keeps the
        # gather engine's module (and its shared-jit entry) byte-identical
        if self.paged_attention == "fused":
            updates["kv_paged_attention"] = "fused"
        if self.mesh is not None and hasattr(cfg, "kv_cache_sharding"):
            updates["kv_cache_sharding"] = self._slot_sharding
        self.module = module = type(module)(dataclasses.replace(cfg, **updates))
        # admission prefills a FRESH nb-row cache (nb = batch bucket, not b):
        # its in-jit cache constraints must be the head-only layout — slot-dim
        # specs applied to nb rows would be a different (often indivisible)
        # partitioning, so admission traces a config carrying ``_fresh_sharding``.
        # Those rows are contiguous (the numerics of a solo ``generate``'s
        # prefill, the parity anchor) — only the post-prefill scatter targets
        # the block pool — so the admit module carries the contiguous
        # per-slot cache layout.
        admit_updates: dict[str, Any] = {"kv_cache_paged": False}
        if self.mesh is not None and hasattr(cfg, "kv_cache_sharding"):
            admit_updates["kv_cache_sharding"] = self._fresh_sharding
        self._admit_module = type(module)(dataclasses.replace(
            module.config, **admit_updates
        ))
        # quantized weights (docs/serving.md "Quantized serving"): quantize
        # the param tree ONCE here and hand every jitted program the packed
        # leaves directly — the `QuantizedModule` wrapper dequantizes inside
        # the trace. Off (None): module, params, and every trace below stay
        # byte-for-byte the fp engine's.
        if isinstance(weight_quant, str):
            weight_quant = WeightQuantConfig(mode=weight_quant)
        self.weight_quant = weight_quant
        self._dense_param_bytes = int(tree_nbytes(params))
        dense_shardings = None
        if self.mesh is not None:
            # Megatron-style TP placement via the training-path rules (callers
            # serving a non-GPT-2 model pass their own ``param_rules``);
            # unmatched / scalar / 1-D leaves come out replicated. Derived
            # over the DENSE tree — packed leaves re-derive below.
            rules = param_rules if param_rules is not None else contract.param_rules()
            dense_shardings = infer_param_shardings(
                params, self.mesh, rules=rules
            )
        self._param_saved_bytes = 0
        if weight_quant is not None:
            qcfg = weight_quant.quantization_config(
                getattr(module.config, "param_dtype", None) or jnp.float32)
            params = quantize_params(params, qcfg)
            raw_admit = self._admit_module
            self.module = module = _quantized_module(module, weight_quant.mode)
            self._admit_module = (
                module if raw_admit is module.module
                else _quantized_module(raw_admit, weight_quant.mode))
        elif contract.compute_dtype_params is not None:
            # parameter residency (docs/serving.md "Parameter residency"): the
            # leaves the model consumes only through a cast to its compute
            # dtype are held in it, cast once here and not every dispatch.
            # Shapes are unchanged, so are the shardings derived above.
            params, self._param_saved_bytes = _narrow_params(
                params, contract.compute_dtype_params, cfg.dtype)
        self.params = params
        if self.mesh is not None:
            if weight_quant is None:
                self._param_shardings = dense_shardings
                self.params = shard_params(params, self._param_shardings)
            else:
                # packed shapes can't take the dense TP rules: a
                # QuantizedTensor subtree replicates (its 1-D payload/scale
                # children follow — the `quantize_model` precedent), while
                # leaves that stayed dense keep their rule-matched placement
                rep = NamedSharding(self.mesh, PartitionSpec())
                is_qt = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
                self._param_shardings = jax.tree.map(
                    lambda q, s: rep if is_qt(q) else s,
                    params, dense_shardings, is_leaf=is_qt,
                )
                self.params = jax.device_put(params, self._param_shardings)
        self._param_bytes = int(quantized_nbytes(self.params))
        self.max_len = int(module.config.n_positions)
        self.pipeline_depth = int(pipeline_depth)
        if self.pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
        # multi-token decode (docs/serving.md "Fused paged decode"): run k
        # decode iterations inside ONE jitted lax.scan between host syncs —
        # the device-resident per-slot state and the on-device finished mask
        # already make the host optional per token. 1 (the default) keeps the
        # single-step program bit-for-bit what it was.
        self.tokens_per_sync = int(tokens_per_sync)
        if self.tokens_per_sync < 1:
            raise ValueError(
                f"tokens_per_sync must be >= 1, got {tokens_per_sync}")
        # speculative decoding (docs/serving.md "Speculative decoding"): a
        # host-side drafter proposes up to k tokens per slot and every decode
        # dispatch becomes ONE k+1-position verify forward with on-device
        # greedy accept/reject and per-slot frontier rollback. The drafter is
        # a performance hint only — greedy streams stay bit-identical to
        # speculation off (tests/test_speculation.py's parity matrix).
        self._drafter: Any = None
        self.draft_tokens = 0
        if speculation is not None:
            if self.tokens_per_sync > 1:
                raise ValueError(
                    "speculation requires tokens_per_sync == 1: the verify "
                    "step is itself the multi-token dispatch, and nesting it "
                    "in a scan would need host drafts mid-scan"
                )
            self._drafter, self.draft_tokens = resolve_drafter(speculation)
        if int(admit_batch) < 1:
            raise ValueError(f"admit_batch must be >= 1, got {admit_batch}")
        # batch buckets: powers of two up to admit_batch — each size is one
        # more admission compile per prompt bucket, so keep the set small
        self._admit_sizes = tuple(
            1 << i for i in range(int(admit_batch).bit_length())
            if 1 << i <= int(admit_batch)
        )
        buckets = tuple(sorted({int(b) for b in prompt_buckets if int(b) <= self.max_len}))
        if not buckets:
            raise ValueError(
                f"no prompt bucket fits n_positions={self.max_len}: {prompt_buckets}"
            )
        # cap admitted prompts one short of the context so every request can
        # emit at least one token. ``scheduler=`` swaps the ordering policy
        # (e.g. `FairScheduler` for the front door's priority classes) — the
        # engine re-stamps bucket/length limits so any policy sees the same
        # admission geometry as the default FIFO; ordering is the ONLY thing
        # a scheduler may change.
        if scheduler is not None:
            self.scheduler = scheduler
            self.scheduler.buckets = buckets
            self.scheduler.max_queue = int(max_queue)
            self.scheduler.max_prompt_len = min(buckets[-1], self.max_len - 1)
        else:
            self.scheduler = FIFOScheduler(
                prompt_buckets=buckets, max_queue=max_queue,
                max_prompt_len=min(buckets[-1], self.max_len - 1),
            )
        self.eos_token_id = eos_token_id
        self.metrics = metrics or ServingMetrics()
        self.tracker = tracker
        self.metrics_log_every = int(metrics_log_every)
        # request-level tracing (serving/trace.py, docs/observability.md):
        # ``tracer=`` takes a `trace.Tracer`; the default NULL_TRACER keeps
        # every emission site a single attribute check — zero-overhead off.
        # The scheduler shares the tracer so QUEUED edges are stamped where
        # the queue actually changes.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.scheduler.tracer = self.tracer
        # continuous telemetry (serving/telemetry.py): ``telemetry=`` takes a
        # `TelemetryExporter`; the default NULL_TELEMETRY keeps the one poll
        # site in `step` a single attribute check — zero-overhead off.
        self.telemetry = telemetry if telemetry is not None else NULL_TELEMETRY
        # anomaly detection + flight recorder (serving/anomaly.py): one
        # attribute check per step, NULL_ANOMALY default — zero-overhead off
        self.anomaly = anomaly if anomaly is not None else NULL_ANOMALY
        # the `serve.dispatch` span of the most recent jitted dispatch: its
        # `_Inflight` entry takes the sequence number from it, and EV_DISPATCH
        # its key, compile-vs-replay flag and wall time
        self._last_dispatch: spans.span | None = None
        # per-step host phase breakdown (docs/observability.md "Latency
        # attribution"): reset at each step() entry, folded into the
        # step_phase_* histograms at step exit
        self._timings = StepTimings()
        self._last_step_timings: dict[str, float] = {}

        b = self.max_concurrency
        # device state: the block pool (donated through every step) plus
        # ALL per-slot decode state — last token, position, sampling params,
        # rng chain (raw key data so slot updates are plain scatters), token
        # budget, and the finished mask. The decode loop never uploads any of
        # it; only the jitted admission scatter writes slots. With a mesh the
        # pool is allocated straight into its sharded placement (never
        # materialized whole on one device) and the per-slot vectors follow
        # the slot dim's layout.
        if self.mesh is not None:
            cache_shapes = jax.eval_shape(
                lambda: self.module.init(
                    jax.random.key(0), jnp.zeros((b, 1), jnp.int32), decode=True
                )["cache"]
            )
            self._cache_shardings = infer_cache_shardings(
                cache_shapes, self._slot_sharding
            )
        self._cache = make_cache(self.module, b, shardings=self._cache_shardings)
        kd = jax.random.key_data(jax.random.key(0))
        self._rng_data = jnp.zeros((b,) + kd.shape, kd.dtype)
        self._d_tokens = jnp.zeros((b,), jnp.int32)
        self._d_pos = jnp.zeros((b,), jnp.int32)
        self._d_temps = jnp.zeros((b,), jnp.float32)
        self._d_topks = jnp.zeros((b,), jnp.int32)
        self._d_remaining = jnp.zeros((b,), jnp.int32)
        self._d_finished = jnp.ones((b,), bool)  # empty slots stay frozen
        self._d_eos = jnp.int32(-1 if eos_token_id is None else int(eos_token_id))
        self._no_poison = jnp.zeros((b,), bool)  # reused when no injector is active
        if self.mesh is not None:
            row = self._row_sharding
            (self._rng_data, self._d_tokens, self._d_pos, self._d_temps,
             self._d_topks, self._d_remaining, self._d_finished,
             self._no_poison) = (
                jax.device_put(a, row) for a in
                (self._rng_data, self._d_tokens, self._d_pos, self._d_temps,
                 self._d_topks, self._d_remaining, self._d_finished,
                 self._no_poison)
            )
            self._d_eos = jax.device_put(self._d_eos, self._rep_sharding)
        # per-slot block tables, the ONLY indirection decode follows.
        # A never-held slot's row points at num_blocks (out of range), and a
        # cancelled tenant's is pointed there on release: a lagged step's
        # write for it DROPS instead of landing in a freed — possibly
        # re-allocated — block (see _release_slot)
        self._d_tables = jnp.full(
            (b, self._blocks_per_slot), self._allocator.num_blocks, jnp.int32)
        if self.mesh is not None:
            self._d_tables = jax.device_put(self._d_tables, self._table_sharding)
        # fresh-row shapes come from the ADMIT module: the decode module's
        # cache is the pool, not the contiguous per-row layout admission
        # prefills into
        self._fresh_shapes = jax.eval_shape(
            lambda: self._admit_module.init(
                jax.random.key(0), jnp.zeros((1, 1), jnp.int32), decode=True
            )["cache"]
        )
        if self.mesh is not None:
            self._fresh_shardings = infer_cache_shardings(
                self._fresh_shapes, self._fresh_sharding
            )
        # host-side slot bookkeeping: which request/output each slot serves,
        # and a per-slot generation counter that invalidates in-flight results
        # dispatched against a previous tenant
        self._active = np.zeros(b, bool)
        self._slot_gen = np.zeros(b, np.int64)
        self._slot_req: list[Request | None] = [None] * b
        self._slot_out: list[RequestOutput | None] = [None] * b
        self._slot_last_token_t = [0.0] * b
        # per-request inter-token gaps, collected ONLY while the slot's tenant
        # carries an SLO with an ITL bound (None otherwise — the common path
        # appends nothing); retired into per-class attainment via observe_slo
        self._slot_itl: list[list[float] | None] = [None] * b
        # held slots whose tenant samples (temperature > 0), and those of them
        # with a top-k mask: kept at admit and release, read at each decode
        # dispatch for the `serving/sample_tail/*` counters
        self._draw_slots = 0
        self._top_k_slots = 0
        # keys and values each held slot has in the pool, by the host's view
        # (prompt + delivered tokens), and their sum: kept at admit, delivery
        # and release, read at each decode dispatch for
        # `serving/paged_decode/*`
        self._slot_kv_tokens = [0] * b
        self._held_kv_tokens = 0
        self._free: deque[int] = deque(range(b))
        self._inflight: deque[_Inflight] = deque()
        self._next_id = 0
        self._step_count = 0
        self._vocab = int(getattr(module.config, "vocab_size", 0) or 0)
        self._draining = False
        # durable request journal (serving/journal.py): every accepted submit
        # is on disk before the caller sees accepted=True, progress/finish
        # records make the engine preemption-tolerant (ServingEngine.resume).
        # ``journal=`` accepts a path or a pre-built RequestJournal; None (the
        # default) keeps the engine fully journal-free.
        self.journal: RequestJournal | None = None
        if journal is not None:
            self.journal = (journal if isinstance(journal, RequestJournal)
                            else RequestJournal(journal, metrics=self.metrics))
            if self.journal.metrics is None:
                self.journal.metrics = self.metrics
        # tokens of the slot's CURRENT stream already journaled (progress
        # records are batched to the journal's ``progress_every`` cadence)
        self._slot_logged = np.zeros(b, np.int64)
        # prefix KV reuse (serving/prefix_cache.py): admission skips prefill
        # of prompt prefixes already resident in the block pool, retirement
        # hands finished prompts' blocks to the trie. Off by default — the
        # cache-off engine never builds the cached admission program.
        self.prefix_cache: PrefixCache | None = None
        self._slot_match: list[PrefixMatch | None] = [None] * b
        self._slot_hit = np.zeros(b, bool)
        # per-slot block bookkeeping: the host copy of the slot's block table
        # (what _retire donates from), the slot's PRIVATE block ids (freed at
        # release — aliased prefix blocks belong to the trie, pinned via
        # _slot_match), and how many leading table entries are aliased
        self._slot_priv: list[list[int]] = [[] for _ in range(b)]
        self._slot_table_host: list[np.ndarray | None] = [None] * b
        self._slot_aliased = np.zeros(b, np.int32)
        if prefix_cache:
            # the trie owns no device state: its entries pin blocks of the
            # engine's own pool (zero-copy hits, adopt-not-copy donation)
            self.prefix_cache = PrefixCache(
                self._allocator, max_len=self.max_len,
                block_tokens=self._block_tokens, metrics=self.metrics,
            )
            self.scheduler.prefill_len_fn = self._prefill_len
            self._cached_admit_fn = self._build_paged_cached_admit_fn()
        # admission is gated on BLOCKS, not just free slots: the scheduler
        # shrinks each front run to what the pool can actually seat
        self.scheduler.capacity_fn = self._paged_capacity
        self._step_fn = self._build_step_fn()
        self._admit_fn = self._build_paged_admit_fn()
        # host-RAM KV tier + request hibernation (serving/kv_tier.py,
        # docs/serving.md "KV tiering & hibernation"): a host-memory block
        # tier behind the block pool, so concurrency outgrows device HBM.
        # Default off — tier-off programs and host paths stay bit-for-bit.
        self.kv_tier: KVTier | None = None
        self._tier_wake_fn = None
        if kv_tier:
            if self.mesh is not None:
                raise ValueError(
                    "kv_tier does not support mesh-sharded serving yet")
            tcfg = (kv_tier if isinstance(kv_tier, KVTierConfig)
                    else KVTierConfig())
            self.kv_tier = KVTier(self, tcfg)
            if self.prefix_cache is not None:
                self.prefix_cache.tier = self.kv_tier
            self._tier_wake_fn = self._build_tier_wake_fn()
        # compile telemetry: every jitted serving program's first dispatch is
        # timed (the python call blocks through trace+compile; execution stays
        # async, so the first-call wall time is compile-dominated) under a
        # ``kind[pb{N}b{M}]@mesh{D}x{T}`` key — see ServingMetrics.record_compile
        self._compile_seen: dict[str, str] = {}  # compile key -> program kind

    # ------------------------------------------------------------------- mesh
    @staticmethod
    def _resolve_mesh(mesh: Any) -> Mesh | None:
        """Accept a Mesh as-is, a `ParallelismConfig` (data/tensor degrees), or
        a ``(data, model)`` tuple — the last two build a `serving_mesh` over
        the first ``data * model`` devices. None stays None (unsharded)."""
        if mesh is None or isinstance(mesh, Mesh):
            return mesh
        if isinstance(mesh, ParallelismConfig):
            if max(mesh.fsdp_size, mesh.stage_size, mesh.sequence_size) > 1:
                raise ValueError(
                    "serving shards over (data, tensor) only; fsdp/stage/"
                    "sequence degrees must be 1 in a serving ParallelismConfig"
                )
            data = 1 if mesh.data_parallel_size == -1 else mesh.data_parallel_size
            return serving_mesh(data=data, model=mesh.tensor_size)
        data, model = mesh
        return serving_mesh(data=int(data), model=int(model))

    @property
    def mesh_shape(self) -> tuple[int, int]:
        """(data, model) mesh degrees — (1, 1) when unsharded."""
        return (self._mesh_data, self._mesh_model)

    def _compile_key(self, kind: str, pb: int | None = None,
                     bb: int | None = None) -> str:
        tag = f"mesh{self._mesh_data}x{self._mesh_model}"
        return f"{kind}@{tag}" if pb is None else f"{kind}[pb{pb}b{bb}]@{tag}"

    def _dispatch(self, key: str, fn, *args):
        """Call a jitted serving program inside a ``serve.dispatch`` span
        (host time to enqueue; the program's sequence number, kind, compile
        key and compile-vs-replay flag ride on it), recording the first
        dispatch per key as one compile (count + wall seconds) in the
        metrics."""
        injector = active_injector()
        if injector is not None:
            # the serving.dispatch fault point: an active injector may wedge
            # this call (step_hang) or raise DeviceLostError (device_error) —
            # exactly what the supervisor's watchdog/restart ladder is proven
            # against. Production cost stays the one active_injector() load.
            injector.dispatch_faults()
        kind = self._compile_seen.get(key)
        compiled = kind is None
        if compiled:
            kind = key.partition("@")[0].partition("[")[0]
        with spans.span("serve.dispatch", seq=spans.next_seq(), kind=kind,
                        key=key, compiled=compiled) as sp:
            out = fn(*args)
        dt = sp.end - sp.start
        self._timings.dispatch_s += dt
        if compiled:
            self._compile_seen[key] = kind
            self.metrics.record_compile(key, dt)
        self._last_dispatch = sp
        return out

    def _trace_dispatch(self, entry: _Inflight, what: str, **extra) -> None:
        """Stamp a just-enqueued `_Inflight` with its program's dispatch
        sequence number and emit its EV_DISPATCH event, built from the
        program's ``serve.dispatch`` span: which jitted program ran (compile
        or replay), the pipeline depth it joined at, and every (slot, rid,
        gen) riding it — the handle `trace.validate` balances against
        EV_FETCH. ``extra`` attrs ride along verbatim (e.g. ``drafted`` on
        spec)."""
        sp = self._last_dispatch
        ran = sp.attrs
        entry.seq = ran["seq"]
        tr = self.tracer
        if not tr.enabled:
            return
        reqs = tuple(
            (int(slot), self._slot_req[slot].request_id, int(gen))
            for slot, gen in zip(entry.slots, entry.gens)
            if self._active[slot] and self._slot_req[slot] is not None
            and self._slot_gen[slot] == gen
        )
        tr.emit(EV_DISPATCH, None, seq=entry.seq, what=what, key=ran["key"],
                compiled=ran["compiled"], dispatch_s=round(sp.end - sp.start, 6),
                depth=len(self._inflight), step=self._step_count, reqs=reqs,
                tokens=entry.tokens, **extra)

    # ------------------------------------------------------------- jitted fns
    def _step_mutable(self):
        """``(mutable collections, counters(mutated) -> tuple)`` for the
        one-token decode programs: a model whose contract names step counters
        sows them into ``counters``, and the step returns them summed over
        layers as ONE int32 vector beside its tokens (no transfer of its
        own). A model without them gets ``["cache"]`` and ``()``: its program
        is what it was."""
        names = self._contract.step_counters
        if not names:
            return ["cache"], lambda mutated: ()

        def counters(mutated):
            flat = jax.tree_util.tree_flatten_with_path(mutated.get("counters", {}))[0]
            sums = {name: jnp.zeros((), jnp.int32) for name in names}
            for path, leaf in flat:
                if leaf_name(path) in sums:
                    sums[leaf_name(path)] += jnp.sum(leaf).astype(jnp.int32)
            return (jnp.stack([sums[name] for name in names]),)

        return ["cache", "counters"], counters

    def _build_step_fn(self):
        if self.draft_tokens:
            return self._build_spec_step_fn()
        if self.tokens_per_sync > 1:
            return self._build_scan_step_fn()
        return self._build_paged_step_fn()

    def _build_paged_step_fn(self):
        """Decode through the block table: the cache rides as the shared
        block pool and each row attends the span its table describes
        (`kv_cache.paged_decode_update` — the token layout and frontier mask
        of a contiguous per-row cache, so logits match a solo ``generate``)."""
        module = self.module
        mutable, counters = self._step_mutable()

        def step_fn(cache, params, tokens, pos, temps, top_ks, rng_data,
                    finished, remaining, poison, eos_id, tables):
            live = ~finished
            # finished slots are frozen INSIDE the compiled step: their blocks
            # are not written (write_mask), and `_decode_tail` carries their
            # token/pos/budget unchanged — so however far host retirement
            # lags, a finished slot's state is bit-stable until re-admission.
            # One more drop layer: a cancelled slot's table row points at
            # num_blocks, so even a stale dispatch's write cannot land
            logits, mutated = module.apply(
                {"params": params, "cache": cache}, tokens[:, None], decode=True,
                position_offset=pos, mutable=mutable, cache_write_mask=live,
                block_tables=tables,
            )
            return (mutated["cache"],) + _decode_tail(
                logits[:, -1], live, tokens, pos, temps, top_ks, rng_data,
                finished, remaining, poison, eos_id) + counters(mutated)

        if self.mesh is None:
            return _shared_jit(module, "step",
                               lambda: jax.jit(step_fn, donate_argnums=(0,)))
        # explicit shardings pin the hot loop's layout: the donated pool keeps
        # its placement through every step (in == out, no resharding) and
        # each [b] state vector rides the slot dim's layout
        row, rep = self._row_sharding, self._rep_sharding
        return jax.jit(
            step_fn, donate_argnums=(0,),
            in_shardings=(self._cache_shardings, self._param_shardings,
                          row, row, row, row, row, row, row, row, rep,
                          self._table_sharding),
            out_shardings=(self._cache_shardings, row, row, row, row, row, row),
        )

    def _build_scan_step_fn(self):
        """``tokens_per_sync`` = k > 1: k decode iterations inside ONE jitted
        `lax.scan` between host syncs. The scan body is token-for-token the
        single-step program — same apply, same `_decode_tail` — so iteration
        t of one scan is bit-identical to the t-th of k separate dispatches.
        The carry is exactly the device state the host round-trips today
        (cache/tokens/pos/remaining/finished/rng); the per-iteration ``(nxt,
        finished, healthy)`` triple stacks into ``[k, b]`` arrays the
        existing fetch path walks token-by-token. Finished (and poisoned —
        health is a finish source) slots freeze inside the scan, so
        EOS/budget/quarantine landing mid-scan just carries the row unchanged
        for the remaining iterations."""
        module = self.module
        k_iters = self.tokens_per_sync

        def step_fn(cache, params, tokens, pos, temps, top_ks, rng_data,
                    finished, remaining, poison, eos_id, tables):

            def body(carry, _):
                cache, tokens, pos, remaining, finished, rng_data = carry
                live = ~finished
                logits, mutated = module.apply(
                    {"params": params, "cache": cache}, tokens[:, None],
                    decode=True, position_offset=pos, mutable=["cache"],
                    cache_write_mask=live, block_tables=tables,
                )
                nxt, new_pos, new_remaining, new_finished, new_rng, ok = _decode_tail(
                    logits[:, -1], live, tokens, pos, temps, top_ks, rng_data,
                    finished, remaining, poison, eos_id)
                carry = (mutated["cache"], nxt, new_pos, new_remaining,
                         new_finished, new_rng)
                return carry, (nxt, new_finished, ok)

            carry = (cache, tokens, pos, remaining, finished, rng_data)
            carry, (toks, fins, oks) = jax.lax.scan(
                body, carry, None, length=k_iters)
            cache, tokens, pos, remaining, finished, rng_data = carry
            return (cache, tokens, pos, remaining, finished, rng_data,
                    toks, fins, oks)

        if self.mesh is None:
            return _shared_jit(module, f"step_x{k_iters}",
                               lambda: jax.jit(step_fn, donate_argnums=(0,)))
        row, rep = self._row_sharding, self._rep_sharding
        # stacked [k, b] per-iteration outputs: iteration dim replicated, the
        # slot dim keeps its layout
        srow = NamedSharding(self.mesh, PartitionSpec(None, *row.spec))
        return jax.jit(
            step_fn, donate_argnums=(0,),
            in_shardings=(self._cache_shardings, self._param_shardings,
                          row, row, row, row, row, row, row, row, rep,
                          self._table_sharding),
            out_shardings=(self._cache_shardings, row, row, row, row, row,
                           srow, srow, srow),
        )

    def _build_spec_step_fn(self):
        """Speculative decoding (`docs/serving.md` "Speculative decoding"):
        one dispatch verifies the slot's last sampled token plus its k
        host-proposed drafts in a single k+1-position forward, then accepts
        the longest draft prefix that matches the target's own greedy argmax.

        Correctness anchors, in order:

        - **Write bound.** The segment writes ``min(remaining + 1, s)`` KV
          entries per live slot (`cache_write_len`); since the admission
          budget guarantees ``pos + remaining + 1 <= extent <= max_len``,
          every written entry sits inside the slot's reservation. Positions
          past the clamp produce logits that are never consumed (the accept
          length ``n <= remaining`` never reaches them) and their writes are
          dropped at a sentinel row/block, so committed history is untouched.
        - **Rollback.** The model's frontier cursor lands at ``pos + s`` on
          write; `rewind_frontier` restamps it to the ACCEPTED frontier
          ``new_pos`` per slot — the unaccepted suffix becomes dead weight
          past the cursor that the next dispatch simply overwrites. Frozen
          and poisoned slots rewind to their untouched pre-step ``pos``.
        - **Parity.** Position 0 samples through the same `_sample_rows` and
          the same split chain as the plain step; positions 1..n-1 are the
          target's own greedy choices at exactly the logits a sequential
          decode would have produced (the drafts they extend matched those
          choices). The rng chain advances one split per EMITTED token, so a
          slot that advances n tokens lands on the key n single-token steps
          would leave — greedy spec-on == spec-off bit-for-bit, and sampled
          (temperature > 0) slots simply always take n = 1.
        - **Finish/truncation.** ``n`` is clipped at the first emitted EOS
          and at the remaining token budget, so finish semantics match the
          sequential step token-for-token; only position n-1 can finish.
        """
        module = self.module
        k_draft = self.draft_tokens
        s = k_draft + 1

        def step_fn(cache, params, tokens, pos, temps, top_ks, rng_data,
                    finished, remaining, poison, eos_id, drafts, tables):
            b = tokens.shape[0]
            rows = jnp.arange(b)
            live = ~finished
            seq = jnp.concatenate([tokens[:, None], drafts], axis=1)  # [b, s]
            write_len = jnp.clip(remaining + 1, 0, s) * live.astype(jnp.int32)
            logits, mutated = module.apply(
                {"params": params, "cache": cache}, seq, decode=True,
                position_offset=pos, mutable=["cache"], cache_write_mask=live,
                cache_write_len=write_len, block_tables=tables,
            )  # [b, s, vocab]
            logits = jnp.where(poison[:, None, None],
                               jnp.asarray(jnp.nan, logits.dtype), logits)
            # watchdog health over the WHOLE segment: any non-finite row
            # means accepted tokens may be garbage — the slot freezes with
            # ns = 0 (frontier already rewound) and the host quarantines it
            ok = jnp.all(jnp.isfinite(logits), axis=(1, 2))
            greedy = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
            # rng chain: precompute the key state after 1..s splits; the slot
            # keeps state n-1, i.e. exactly one split per emitted token (the
            # same chain the sequential step and journal fast-forward walk)
            states = []
            key0 = None
            cur = jax.random.wrap_key_data(rng_data)
            for t in range(s):
                sp = jax.vmap(jax.random.split)(cur)
                cur = sp[:, 0]
                if t == 0:
                    key0 = sp[:, 1]
                states.append(jax.random.key_data(cur))
            states = jnp.stack(states, axis=1)  # [b, s, *key]
            sampled0 = _sample_rows(logits[:, 0], key0, temps, top_ks, live)
            out_tokens = jnp.concatenate(
                [sampled0[:, None], greedy[:, 1:]], axis=1)  # [b, s]
            matches = drafts == greedy[:, :k_draft]
            acc = jnp.cumprod(matches.astype(jnp.int32), axis=1).sum(axis=1)
            # acceptance is an exact-match test against greedy argmax, so it
            # is only sound for greedy slots; sampled slots advance exactly
            # one (their position-0 token), same as the plain step
            n_cand = jnp.where(temps > 0, 1, acc + 1)
            hit = (eos_id >= 0) & (out_tokens == eos_id)  # [b, s]
            first_eos = jnp.where(hit.any(axis=1), jnp.argmax(hit, axis=1), s)
            n = jnp.minimum(n_cand, jnp.minimum(jnp.maximum(remaining, 1),
                                                first_eos + 1))  # >= 1
            healthy = live & ok
            ns = jnp.where(healthy, n, 0)
            new_tokens = jnp.where(healthy, out_tokens[rows, n - 1], tokens)
            new_pos = jnp.where(healthy, pos + n, pos)
            new_remaining = jnp.where(healthy, remaining - n, remaining)
            eos_last = hit[rows, n - 1]
            new_finished = finished | (live & ~ok) | (
                healthy & (eos_last | (new_remaining <= 0)))
            cond = healthy.reshape((b,) + (1,) * (rng_data.ndim - 1))
            new_rng = jnp.where(cond, states[rows, n - 1], rng_data)
            t_idx = jnp.arange(s)[None, :]
            emit = healthy[:, None] & (t_idx < n[:, None])  # [b, s]
            # budget exhaustion can only fire at t = n-1 (n <= remaining);
            # EOS inside the accepted prefix truncated n, so it too is last
            fins_bs = emit & (hit | (remaining[:, None] - (t_idx + 1) <= 0))
            new_cache = rewind_frontier(mutated["cache"], new_pos)
            return (new_cache, new_tokens, new_pos, new_remaining,
                    new_finished, new_rng, out_tokens.T, fins_bs.T,
                    ok | finished, ns)

        if self.mesh is None:
            return _shared_jit(module, f"spec_k{k_draft}",
                               lambda: jax.jit(step_fn, donate_argnums=(0,)))
        row, rep = self._row_sharding, self._rep_sharding
        # stacked [s, b] per-position outputs: position dim replicated, slot
        # dim keeps its layout; drafts [b, k] ride the slot layout with the
        # position dim replicated (trailing dims of a short spec replicate)
        srow = NamedSharding(self.mesh, PartitionSpec(None, *row.spec))
        return jax.jit(
            step_fn, donate_argnums=(0,),
            in_shardings=(self._cache_shardings, self._param_shardings,
                          row, row, row, row, row, row, row, row, rep, row,
                          self._table_sharding),
            out_shardings=(self._cache_shardings, row, row, row, row, row,
                           srow, srow, row, row),
        )

    def _build_admit_tail(self):
        """An admit program after its prefill, once for the plain and the
        cached program: each row's last real logits, the first token's draw,
        ONE scatter of the fresh rows' newly written blocks into the pool at
        the slots' reserved block ids (`kv_cache.scatter_rows_to_blocks`),
        and the per-slot writes. ``dest_blocks`` entries of ``num_blocks``
        (a hit's aliased prefix blocks, reserved-but-unwritten decode blocks)
        drop their write. ``last_lens`` is each row's length inside the
        prefilled bucket; ``cached_lens`` what it resumed after."""
        cache_shardings = self._cache_shardings
        bt = self._block_tokens
        state_leaves = self._contract.state_leaves

        def tail(pool_cache, fresh, logits, last_lens, slots, temps, top_ks,
                 rng_batch, budgets, dest_blocks, group_tables, d_tables,
                 d_tokens, d_pos, d_temps, d_topks, d_finished, d_remaining,
                 rng_data, eos_id, cached_lens=None):
            last = jax.vmap(
                lambda row, n: jax.lax.dynamic_slice(
                    row, (n - 1, 0), (1, row.shape[-1])
                )[0]
            )(logits, last_lens)
            rngs = jax.random.wrap_key_data(rng_batch)
            split = jax.vmap(jax.random.split)(rngs)  # [nb, 2] keys
            new_rngs, keys = split[:, 0], split[:, 1]
            first = _sample_rows(last, keys, temps, top_ks,
                                 jnp.ones_like(temps, bool))
            # decode resumes from the FULL prompt end: cached prefix + suffix.
            # The prefill advanced the rows' cursor to the padded bucket
            # length; the scatter stamps the true one, so decode overwrites
            # the pad entries and never attends them
            prompt_lens = (last_lens if cached_lens is None
                           else cached_lens + last_lens)
            new_pool = scatter_rows_to_blocks(
                pool_cache, fresh, slots, dest_blocks, prompt_lens, bt,
                shardings=cache_shardings, state_leaves=state_leaves,
            )
            d_tables = d_tables.at[slots].set(group_tables)
            # first token rides out of the prefill itself; budget-1 tokens
            # remain for the decode loop (a 1-token budget or first-token EOS
            # is finished on arrival)
            rem0 = budgets - 1
            fin0 = (rem0 <= 0) | ((eos_id >= 0) & (first == eos_id))
            d_tokens = d_tokens.at[slots].set(first)
            d_pos = d_pos.at[slots].set(prompt_lens)
            d_temps = d_temps.at[slots].set(temps)
            d_topks = d_topks.at[slots].set(top_ks)
            d_finished = d_finished.at[slots].set(fin0)
            d_remaining = d_remaining.at[slots].set(rem0)
            rng_data = rng_data.at[slots].set(jax.random.key_data(new_rngs))
            return (new_pool, first, fin0, d_tables, d_tokens, d_pos, d_temps,
                    d_topks, d_finished, d_remaining, rng_data)

        return tail

    def _build_paged_admit_fn(self):
        """Plain admission: prefill ALL nb (right-padded) rows of one prompt
        bucket in one pass into a FRESH contiguous nb-row cache — the
        numerics of a solo ``generate``'s prefill; the causal mask keeps pad
        positions from reaching each row's last real token's logits — then
        `_build_admit_tail` moves the rows into the pool's blocks."""
        module, fresh_shapes = self._admit_module, self._fresh_shapes
        state_leaves = self._contract.state_leaves
        tail = self._build_admit_tail()

        def admit_fn(pool_cache, params, prompt_rows, slots, prompt_lens,
                     temps, top_ks, rng_batch, budgets, dest_blocks,
                     group_tables, d_tables, d_tokens, d_pos, d_temps,
                     d_topks, d_finished, d_remaining, rng_data, eos_id):
            nb = prompt_rows.shape[0]
            fresh = jax.tree.map(
                lambda s: jnp.zeros((nb,) + s.shape[1:], s.dtype), fresh_shapes
            )
            logits, mutated = module.apply(
                {"params": params, "cache": fresh}, prompt_rows, decode=True,
                position_offset=0, mutable=["cache"],
                # a model with recurrent state must know each row's true
                # length inside the padded bucket: pad tokens leave the state
                # untouched. Keys-and-values models get no argument at all.
                **({"cache_write_len": prompt_lens} if state_leaves else {}),
            )
            return tail(pool_cache, mutated["cache"], logits, prompt_lens,
                        slots, temps, top_ks, rng_batch, budgets, dest_blocks,
                        group_tables, d_tables, d_tokens, d_pos, d_temps,
                        d_topks, d_finished, d_remaining, rng_data, eos_id)

        if self.mesh is None:
            return _shared_jit(module, "paged_admit",
                               lambda: jax.jit(admit_fn, donate_argnums=(0,)))
        # the [nb] admission inputs (padded prompts, lens, sampling params,
        # seeds) are replicated — nb is small and the prefill's activations
        # shard over heads via the param/TP rules; the [b] per-slot vectors
        # and the tables keep the slot layout through the scatter
        row, rep = self._row_sharding, self._rep_sharding
        tab = self._table_sharding
        return jax.jit(
            admit_fn, donate_argnums=(0,),
            in_shardings=(self._cache_shardings, self._param_shardings,
                          rep, rep, rep, rep, rep, rep, rep, rep, rep,
                          tab, row, row, row, row, row, row, row, rep),
            out_shardings=(self._cache_shardings, rep, rep, tab,
                           row, row, row, row, row, row, row),
        )

    def _build_paged_cached_admit_fn(self):
        """Admission with prefix reuse: the matched prefix is ALIASED, never
        copied — `gather_block_rows` assembles contiguous per-row views
        straight out of the engine's own pool as a compute transient, ONLY
        the uncached suffix prefills on top (each row resuming at its own
        ``cached_len`` via the [nb] ``position_offset`` vector), and the tail
        writes ONLY the suffix's blocks back (aliased entries carry dest id
        ``num_blocks`` — dropped). The slot's table then points at the trie's
        pinned blocks for the prefix and its own fresh blocks for the rest.
        One compile per ``(suffix_bucket, batch_bucket)`` pair — the same
        bounded set as plain admission, because the scheduler re-buckets the
        SUFFIX (`FIFOScheduler.prefill_bucket_for`)."""
        module, fresh_shapes = self._admit_module, self._fresh_shapes
        fresh_shardings = self._fresh_shardings
        tail = self._build_admit_tail()

        def admit_fn(pool_cache, params, gather_tables, cached_lens,
                     suffix_rows, suffix_lens, slots, temps, top_ks,
                     rng_batch, budgets, dest_blocks, group_tables, d_tables,
                     d_tokens, d_pos, d_temps, d_topks, d_finished,
                     d_remaining, rng_data, eos_id):
            # table entries past a row's real prefix (fresh private blocks,
            # or the num_blocks sentinel clamped by the gather) read garbage
            # the suffix write overwrites or the causal mask never admits
            fresh = gather_block_rows(pool_cache, gather_tables, cached_lens,
                                      shardings=fresh_shardings,
                                      like=fresh_shapes)
            logits, mutated = module.apply(
                {"params": params, "cache": fresh}, suffix_rows, decode=True,
                position_offset=cached_lens, mutable=["cache"],
            )
            return tail(pool_cache, mutated["cache"], logits, suffix_lens,
                        slots, temps, top_ks, rng_batch, budgets, dest_blocks,
                        group_tables, d_tables, d_tokens, d_pos, d_temps,
                        d_topks, d_finished, d_remaining, rng_data, eos_id,
                        cached_lens=cached_lens)

        if self.mesh is None:
            return _shared_jit(module, "paged_cached_admit",
                               lambda: jax.jit(admit_fn, donate_argnums=(0,)))
        row, rep = self._row_sharding, self._rep_sharding
        tab = self._table_sharding
        return jax.jit(
            admit_fn, donate_argnums=(0,),
            in_shardings=(self._cache_shardings, self._param_shardings,
                          rep, rep, rep, rep, rep, rep, rep, rep, rep, rep, rep,
                          tab, row, row, row, row, row, row, row, rep),
            out_shardings=(self._cache_shardings, rep, rep, tab,
                           row, row, row, row, row, row, row),
        )

    def _build_tier_wake_fn(self):
        """ONE jitted program for every host->device tier restore
        (`serving/kv_tier.py`): scatter host block copies into the paged pool
        at ``dest`` ids (sentinel entries drop) and rewrite one slot's entire
        per-slot decode state — block-table row, frontier cursor, last token,
        position, sampling params, rng chain, budget, finished=False.

        The trie page-in path reuses the same compiled program by passing
        ``slot = max_concurrency``: every per-slot ``.at[slot].set`` is then
        out of bounds, and JAX scatter semantics DROP out-of-bounds updates —
        only the pool-block writes land. One compile serves both paths."""

        def wake_fn(cache, host_blocks, dest, slot, index, table_row,
                    d_tables, token, pos, temp, topk, remaining, rng_row,
                    d_tokens, d_pos, d_temps, d_topks, d_finished,
                    d_remaining, rng_data):
            def put(path, leaf, host_leaf):
                if _is_index_leaf(path):
                    # the paged cursor leaf is [max_concurrency]: restamp the
                    # woken slot's append frontier (drops on the trie path)
                    return leaf.at[slot].set(index.astype(leaf.dtype))
                return leaf.at[dest].set(
                    host_leaf.astype(leaf.dtype), mode="drop")

            new_cache = jax.tree_util.tree_map_with_path(
                put, cache, host_blocks)
            d_tables = d_tables.at[slot].set(table_row)
            d_tokens = d_tokens.at[slot].set(token)
            d_pos = d_pos.at[slot].set(pos)
            d_temps = d_temps.at[slot].set(temp)
            d_topks = d_topks.at[slot].set(topk)
            d_finished = d_finished.at[slot].set(False)
            d_remaining = d_remaining.at[slot].set(remaining)
            rng_data = rng_data.at[slot].set(rng_row)
            return (new_cache, d_tables, d_tokens, d_pos, d_temps, d_topks,
                    d_finished, d_remaining, rng_data)

        return _shared_jit(self.module, "tier_wake",
                           lambda: jax.jit(wake_fn, donate_argnums=(0,)))

    def _tier_upload(self, dest: np.ndarray, host_tree: Any, *,
                     slot: int | None = None, index: int = 0,
                     table_row: np.ndarray | None = None, token: int = 0,
                     pos: int = 0, temp: float = 0.0, topk: int = 0,
                     remaining: int = 0, rng_row: np.ndarray | None = None
                     ) -> None:
        """Dispatch one ``tier_wake`` restore. Without ``slot`` this is a
        trie page-in: the per-slot half of the program aims at the
        out-of-bounds slot ``max_concurrency`` and drops, so only the pool
        blocks named by ``dest`` change."""
        if slot is None:
            slot = self.max_concurrency
        if table_row is None:
            table_row = np.full(self._blocks_per_slot,
                                self._allocator.num_blocks, np.int32)
        if rng_row is None:
            rng_row = np.asarray(jax.random.key_data(jax.random.key(0)))
        (self._cache, self._d_tables, self._d_tokens, self._d_pos,
         self._d_temps, self._d_topks, self._d_finished, self._d_remaining,
         self._rng_data) = self._dispatch(
            self._compile_key("tier_wake"), self._tier_wake_fn,
            self._cache, host_tree, jnp.asarray(dest),
            jnp.asarray(slot, jnp.int32), jnp.asarray(index, jnp.int32),
            jnp.asarray(table_row), self._d_tables,
            jnp.asarray(token, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(temp, jnp.float32), jnp.asarray(topk, jnp.int32),
            jnp.asarray(remaining, jnp.int32), jnp.asarray(rng_row),
            self._d_tokens, self._d_pos, self._d_temps, self._d_topks,
            self._d_finished, self._d_remaining, self._rng_data,
        )

    def _wake_hibernated_upload(self, rec: Any) -> bool:
        """Wake one hibernated stream by uploading its host KV blocks back
        into freshly reserved pool blocks (`KVTier.try_wakes`' cheap path).
        All or nothing: needs a free slot and the stream's FULL decode-extent
        block reservation up front (mid-decode writes must never find the
        pool empty — the same contract `_reserve_blocks` enforces), else
        False and nothing changed. Decode resumes at position
        ``prompt + emitted - 1`` with the rng chain fast-forwarded one split
        per emitted token — the state M uninterrupted steps would hold, so
        the continuation is bit-for-bit (tests/test_kv_tier.py parity)."""
        tier = self.kv_tier
        request = rec.request
        if not self._free:
            return False
        bt = self._block_tokens
        extent = FIFOScheduler.decode_extent(request, self.max_len)
        need = -(-extent // bt)
        ids = self._allocator.alloc(need)
        if ids is None:
            return False
        if KVTier._crcs(rec.blocks.tree) != rec.blocks.crcs:
            self._allocator.free(ids)
            raise RuntimeError(
                "host-tier content hash mismatch on hibernation wake "
                "(host buffer corrupted)")
        slot = self._free.popleft()
        sentinel = self._allocator.num_blocks
        table = np.full(self._blocks_per_slot, sentinel, np.int32)
        table[:need] = ids
        dest = np.full(self._blocks_per_slot, sentinel, np.int32)
        dest[:rec.n_content] = table[:rec.n_content]
        plen = len(request.prompt)
        m = len(rec.tokens)
        pos = plen + m - 1  # KV on host covers [0, pos - 1]; decode re-feeds
        sp = request.params
        remaining = min(int(sp.max_new_tokens), self.max_len - plen) - m
        key = jax.random.key(sp.seed)
        for _ in range(m):
            key = jax.random.split(key)[0]
        t0 = time.perf_counter()
        self._tier_upload(
            dest, tier._padded(rec.blocks, self._blocks_per_slot),
            slot=slot, index=pos, table_row=table,
            token=int(rec.tokens[-1]), pos=pos,
            temp=float(sp.temperature), topk=int(sp.top_k or 0),
            remaining=remaining,
            rng_row=np.asarray(jax.random.key_data(key)),
        )
        wall = max(time.perf_counter() - t0, 1e-9)
        now = time.perf_counter()
        # host mirrors, à la _finish_admit — but the output resumes with the
        # stream's full history and its ORIGINAL first-token time (wake is
        # not a new admission; TTFT was already paid)
        self._slot_gen[slot] += 1
        self._slot_req[slot] = request
        self._count_sample_tail(sp, 1)
        self._hold_kv_tokens(slot, plen + m)
        out = RequestOutput(
            request_id=request.request_id, prompt_len=plen,
            tokens=list(rec.tokens), finish_reason="",
            arrival_time=request.arrival_time,
            token_times=list(rec.token_times),
        )
        out.first_token_time = rec.first_token_time
        self._slot_out[slot] = out
        self._slot_logged[slot] = m  # journal was flushed at hibernate
        self._active[slot] = True
        slo = request.slo
        self._slot_itl[slot] = (
            [] if slo is not None and slo.itl_p99_s is not None else None)
        self._slot_match[slot] = None
        self._slot_hit[slot] = bool(rec.hit)
        self._slot_priv[slot] = list(ids)
        self._slot_table_host[slot] = table.copy()
        self._slot_aliased[slot] = 0
        self._slot_last_token_t[slot] = now
        self.metrics.host_page_ins.inc(rec.n_content)
        self.metrics.host_page_in_s.observe(wall)
        tier._xfer.update(rec.blocks.nbytes / wall)
        tier._record_page_events(rec.n_content)
        if self.tracer.enabled:
            self.tracer.emit(
                EV_ADMIT, request.request_id, slot=slot,
                gen=int(self._slot_gen[slot]), wake="upload", resumed=m,
                depth=len(self._inflight),
            )
        return True

    def _prefill_len(self, request: Request) -> int:
        """Scheduler probe: prompt tokens admission would actually prefill for
        this request right now (its uncached suffix) — the grouping key for
        suffix-bucketed batched admission. Probing never pins; the real match
        re-walks (and pins) at admission."""
        if not request.cache_prefix or request.resume_tokens:
            # a resumed stream prefills prompt + emitted tokens as one plain
            # continuation pass — it never rides the block-pool gather
            return request.prefill_len
        return len(request.prompt) - self.prefix_cache.match_len(request.prompt)

    # --------------------------------------------------------------- requests
    def submit(self, request: Request | Iterable[int],
               params: SamplingParams | None = None) -> SubmitResult:
        """Queue a request (a `Request` or a bare token-id sequence).

        Never blocks: a full queue or oversized prompt returns a rejection
        with a reason code instead (backpressure — shed or retry upstream).
        """
        if not isinstance(request, Request):
            request = Request(prompt=list(request), params=params or SamplingParams())
        request.request_id = self._next_id
        self._next_id += 1
        if request.arrival_time is None:
            request.arrival_time = time.perf_counter()
        self.metrics.mark_start()
        tr = self.tracer
        if tr.enabled:
            tr.emit(EV_SUBMIT, request.request_id,
                    prompt_len=len(request.prompt),
                    slo=request.slo.name if request.slo is not None else None)
        if self._draining:
            self.metrics.requests_rejected.inc()
            if tr.enabled:
                tr.emit(EV_REJECT, request.request_id, reason=REJECT_DRAINING)
            return SubmitResult(False, request.request_id, REJECT_DRAINING,
                                "engine is draining toward shutdown")
        result = self.scheduler.submit(request)
        if not result.accepted and tr.enabled:
            tr.emit(EV_REJECT, request.request_id, reason=result.reason)
        if result.accepted:
            # WRITE-AHEAD: the acceptance is durable before the caller sees
            # it — a crash after this line can lose the reply, never the
            # request (ServingEngine.resume replays it)
            if self.journal is not None:
                self.journal.log_submit(request)
            self.metrics.requests_submitted.inc()
        else:
            self.metrics.requests_rejected.inc()
        return result

    @property
    def has_work(self) -> bool:
        if self.kv_tier is not None and self.kv_tier.hibernated_count:
            # hibernated streams are admitted work parked on the host tier —
            # the step loop must keep running so the tier can wake them
            return True
        return bool(self._active.any()) or self.scheduler.queue_depth > 0

    @property
    def active_slots(self) -> int:
        return int(self._active.sum())

    # --------------------------------------------------------------- telemetry
    def memory_stats(self) -> dict[str, Any]:
        """Live memory/occupancy gauges (`docs/observability.md` "Continuous
        telemetry"). Host-side only: pool bytes are allocation-time constants
        (`kv_cache.tree_nbytes` — exact `leaf.nbytes` sums), occupancy comes
        from the host slot mirrors, and the per-device numbers use
        `device.memory_stats()` when the backend provides it (TPU/GPU; a CPU
        host simply omits them). Keys are unprefixed — the telemetry exporter
        namespaces them under ``serving/mem/``, except the ``quant/`` group
        (present only when a quantized mode is active — `quant_stats`), which
        it lifts to the top-level ``serving/quant/`` namespace.
        ``param_bytes`` (the param tree the programs read) and
        ``param_narrowed_saved_bytes`` (what holding the contract's
        ``compute_dtype_params`` in the compute dtype saved) are fixed at
        construction."""
        stats: dict[str, Any] = {
            "slot_pool_bytes": tree_nbytes(self._cache),
            "slots_total": self.max_concurrency,
            "slots_active": self.active_slots,
            "slots_free": len(self._free),
            "queue_depth": self.scheduler.queue_depth,
            "inflight_dispatches": len(self._inflight),
            "param_bytes": self._param_bytes,
            "param_narrowed_saved_bytes": self._param_saved_bytes,
        }
        for dtype, n in tree_bytes_by_dtype(self._cache).items():
            stats[f"slot_pool_bytes/{dtype}"] = n
        state_bytes = 0
        if self._contract.state_leaves:
            # per-slot recurrent state rides in the same cache tree: its
            # bytes are part of slot_pool_bytes and are broken out here
            state_bytes = state_nbytes(self._cache, self._contract.state_leaves)
            stats["slot_state_bytes"] = state_bytes
            stats["slot_state_bytes_per_slot"] = state_bytes // self.max_concurrency
        for k, v in self.quant_stats().items():
            stats[f"quant/{k}"] = v
        # ``slot_pool_bytes`` above IS the block pool (the engine's cache tree
        # holds it), so the block_pool/ gauges report the allocator's view.
        # Invariant: free + resident (trie) + private (slot-held) == total
        # (tests/test_paged_kv.py).
        alloc = self._allocator
        base = (self.prefix_cache.memory_stats()
                if self.prefix_cache is not None else {})
        resident = int(base.get("blocks_resident", 0))
        for k, v in {
            "pool_bytes": stats["slot_pool_bytes"] - state_bytes,
            "block_tokens": self._block_tokens,
            "blocks_total": alloc.num_blocks,
            "blocks_free": alloc.free_count,
            "blocks_resident": resident,
            "blocks_private": alloc.owned_count - resident,
            "blocks_pinned": int(base.get("blocks_pinned", 0)),
            "blocks_evictable": int(base.get("blocks_evictable", 0)),
            "blocks_stranded": int(base.get("blocks_stranded", 0)),
            "fragmentation": base.get("fragmentation", 0.0),
        }.items():
            stats[f"block_pool/{k}"] = v
        if self.kv_tier is not None:
            # host-tier ledger (docs/observability.md "host_tier"): host
            # bytes/blocks are CURRENT occupancy, the rest are lifetime
            # counters. The device invariant above is untouched by
            # tiering — spilled blocks leave the device ledger entirely.
            for k, v in self.kv_tier.memory_stats().items():
                stats[f"host_tier/{k}"] = v
        for i, dev in enumerate(jax.local_devices()):
            dm = device_memory_stats(dev)
            if dm is None:  # the CPU keeps no stats
                continue
            for key in ("bytes_in_use", "bytes_limit", "peak_bytes_in_use"):
                if key in dm:
                    stats[f"device{i}/{key}"] = int(dm[key])
        return stats

    def quant_stats(self) -> dict[str, Any]:
        """Quantized-serving gauges (`docs/observability.md` "serving/quant"),
        ``{}`` whenever no quantized mode is active — a full-precision
        engine's telemetry points carry no quant keys at all.

        Weight side (``weight_quant=``): exact packed+scale bytes
        (`quantized_nbytes` — what the jitted programs actually hold
        resident) against the dense-equivalent bytes captured at load, so
        headroom math and `tools/serve_top.py` see the freed HBM. KV side
        (``kv_cache_dtype=int8``): storage bits plus the exact split of the
        live cache tree into int8 payload and fp32 absmax-scale bytes."""
        stats: dict[str, Any] = {}
        if self.weight_quant is not None:
            packed = int(quantized_nbytes(self.params))
            stats["weight_bits"] = 8 if self.weight_quant.mode == "int8" else 4
            stats["weight_packed_bytes"] = packed
            stats["weight_dense_bytes"] = self._dense_param_bytes
            stats["weight_saved_bytes"] = self._dense_param_bytes - packed
        kv_dtype = getattr(self.module.config, "kv_cache_dtype", None)
        if kv_dtype is not None:
            by_dtype = tree_bytes_by_dtype(self._cache)
            stats["kv_bits"] = jnp.dtype(kv_dtype).itemsize * 8
            stats["kv_payload_bytes"] = int(by_dtype.get("int8", 0))
            stats["kv_scale_bytes"] = int(by_dtype.get("float32", 0))
        return stats

    def capacity_headroom(self) -> dict[str, Any]:
        """Admission-capacity estimate — the predicted-TTFT admission input
        (ROADMAP item 5). All host arithmetic over the slot mirrors:

        - ``slots_free`` / ``queue_depth`` — raw occupancy;
        - ``admissible_requests`` — requests admissible right now without
          queuing behind existing work: free slots minus the queue already
          waiting for them, floored at 0;
        - ``decode_tokens_remaining`` — decode tokens still owed across
          active slots at their current budgets;
        - ``token_capacity_remaining`` — that plus ``max_len - 1`` per free
          slot (the most any single admitted request can generate). Monotone
          non-increasing as slots fill: admission converts a free slot's
          ``max_len - 1`` into a budget that is never larger, and decode
          only drains it;
        - ``seconds_to_exhaustion`` — token capacity over the current decode
          rate (`metrics.tokens_per_sec`): how long until every position is
          consumed if nothing retires. None while the engine is idle (rate
          0) — exporters serialize that as null, never inf;
        - ``est_slot_free_s`` — predicted wait for the next free slot: 0
          when one is free, else the smallest per-slot remaining budget over
          the per-slot decode rate (aggregate rate / active slots). None
          when no rate is observable yet.
        """
        free = len(self._free)
        remaining: list[int] = []
        for slot in range(self.max_concurrency):
            if not self._active[slot]:
                continue
            request, out = self._slot_req[slot], self._slot_out[slot]
            if request is None or out is None:
                continue
            plen = len(request.prompt)
            budget = min(int(request.params.max_new_tokens),
                         self.max_len - plen)
            remaining.append(max(0, budget - len(out.tokens)))
        decode_remaining = sum(remaining)
        # a free slot is only worth what the block pool can back: the
        # optimistic free-slot term is capped by blocks_free * bt. Still
        # monotone non-increasing as slots fill — an admission moves
        # budget tokens into decode_remaining while shrinking BOTH cap
        # operands by at least that much (budget <= max_len - 1 and
        # budget <= reserved_blocks * bt).
        blocks_free = self._allocator.free_count
        capacity = decode_remaining + min(
            free * (self.max_len - 1),
            blocks_free * self._block_tokens,
        )
        if self.kv_tier is not None:
            # host-backed capacity counts at a discounted rate: those
            # tokens are servable, but only after a page-in that is
            # slower than device-resident decode
            capacity += int(self.kv_tier.cfg.headroom_discount
                            * self.kv_tier.host_blocks
                            * self._block_tokens)
        rate = self.metrics.tokens_per_sec()
        exhaustion = capacity / rate if rate > 0 else None
        if free > 0:
            slot_free_s: float | None = 0.0
        elif rate > 0 and remaining:
            slot_free_s = min(remaining) * len(remaining) / rate
        else:
            slot_free_s = None
        out = {
            "slots_free": free,
            "queue_depth": self.scheduler.queue_depth,
            "admissible_requests": max(0, free - self.scheduler.queue_depth),
            "decode_tokens_remaining": decode_remaining,
            "token_capacity_remaining": capacity,
            "decode_tokens_per_sec": rate,
            "seconds_to_exhaustion": exhaustion,
            "est_slot_free_s": slot_free_s,
        }
        # block headroom gauges (serve_top's block-pool occupancy bars): free
        # blocks, and the observed private-blocks-per-active-request — the
        # ragged workload's real per-request footprint, against the
        # full-context blocks_per_slot
        active = self.active_slots
        priv = sum(len(p) for p in self._slot_priv)
        out["blocks_free"] = blocks_free
        out["blocks_per_request_est"] = (
            priv / active if active else float(self._blocks_per_slot))
        if self.kv_tier is not None:
            out["host_blocks"] = self.kv_tier.host_blocks
        return out

    @property
    def last_step_timings(self) -> dict[str, float]:
        """Phase breakdown (`StepTimings.as_dict`) of the most recent
        `step()` call — {} before the first step. Supervisor heartbeats and
        flight-recorder bundles embed it."""
        return self._last_step_timings

    # ------------------------------------------------------------ engine loop
    def step(self) -> list[RequestOutput]:
        """Admit into free slots, dispatch one decode step for every active
        slot, fetch results lagging by up to ``pipeline_depth`` dispatches,
        and return the requests whose completion was OBSERVED during this
        call (at depth > 1 a finish surfaces when its fetch lands, up to
        ``pipeline_depth - 1`` calls after the device produced it).

        The call is one ``serve.step`` span in `utils.spans.RING`, numbered
        with the count `ServingMetrics.step_total_s` reports once the step is
        observed; its admission, dispatches, fetches, deliveries, draft,
        journal appends, telemetry poll, the queue waits of the requests it
        admits and any full collection inside it are spans that name it as
        their parent."""
        tm = self._timings
        tm.reset()
        with spans.span("serve.step", is_step=True,
                        step=self.metrics.step_total_s.count + 1) as sp:
            finished = self._step(sp.start, tm)
        tm.total_s = sp.end - sp.start
        self.metrics.observe_step_phases(tm)
        self._last_step_timings = tm.as_dict()
        if self.anomaly.enabled:
            self.anomaly.observe(self)
        return finished

    def _step(self, t_start: float, tm: StepTimings) -> list[RequestOutput]:
        """The body of `step`, entered at ``t_start``."""
        journal = self.journal
        j_start = journal.append_s if journal is not None else 0.0
        finished: list[RequestOutput] = []
        self._reap_ready(finished)
        admit = self._admit_pending(finished)
        # schedule = the step's stretch up to the end of its `serve.admit`
        # span net of the dispatch, fetch, deliver and journal spans inside it
        # (each already accumulated into its own phase)
        j_sched = (journal.append_s - j_start) if journal is not None else 0.0
        tm.schedule_s = max(0.0, (admit.end - t_start)
                            - tm.dispatch_s - tm.fetch_blocked_s
                            - tm.deliver_s - j_sched)
        n_active = self.active_slots
        self.metrics.observe_step(n_active, self.max_concurrency,
                                  self.scheduler.queue_depth)
        if self._slot_replicas > 1:
            per = self._active.reshape(self._slot_replicas, -1).sum(axis=1)
            self.metrics.observe_replicas(
                [int(x) for x in per],
                self.max_concurrency // self._slot_replicas,
            )
        self._step_count += 1
        if n_active:
            self.metrics.observe_sample_tail(self._draw_slots, self._top_k_slots)
            self.metrics.observe_paged_decode(
                self._held_kv_tokens, n_active * self.max_len)
            poison = self._poison_mask()
            step_args = (
                self._cache, self.params, self._d_tokens, self._d_pos,
                self._d_temps, self._d_topks, self._rng_data, self._d_finished,
                self._d_remaining,
                self._no_poison if poison is None else jnp.asarray(poison),
                self._d_eos,
            )
            if self.draft_tokens:
                # host drafting happens at dispatch time, from the host's
                # (possibly pipeline-lagged) view of each slot's tokens —
                # staleness costs acceptance only, verification is exact
                with spans.span("serve.draft") as sp:
                    drafts = jnp.asarray(self._propose_drafts())
                tm.draft_s = sp.end - sp.start
                step_args += (drafts,)
            # tables ride as data (not donated): decode reads through
            # them but only admission/release rewrites them
            step_args += (self._d_tables,)
            if self.draft_tokens:
                (self._cache, self._d_tokens, self._d_pos, self._d_remaining,
                 self._d_finished, self._rng_data, toks, fins, oks, ns
                 ) = self._dispatch(
                    self._compile_key(f"spec_k{self.draft_tokens}"),
                    self._step_fn, *step_args)
                arrays = (toks, fins, oks, ns)
                self.metrics.spec_forwards.inc()
                kind, tokens_attr = "spec", self.draft_tokens + 1
            elif self.tokens_per_sync == 1:
                # a model with step counters returns them as one more small
                # array, fetched with the tokens (`_step_mutable`)
                (self._cache, nxt, self._d_pos, self._d_remaining, fin,
                 self._rng_data, ok, *counted) = self._dispatch(
                    self._compile_key("step"), self._step_fn, *step_args)
                self._d_tokens, self._d_finished = nxt, fin
                arrays = (nxt, fin, ok, *counted)
                kind, tokens_attr = "step", 1
            else:
                # one scan dispatch advances the device state k iterations;
                # the stacked [k, b] outputs carry every intermediate token
                # for the fetch path
                (self._cache, self._d_tokens, self._d_pos, self._d_remaining,
                 self._d_finished, self._rng_data, toks, fins, oks
                 ) = self._dispatch(
                    self._compile_key(f"step_x{self.tokens_per_sync}"),
                    self._step_fn, *step_args)
                arrays = (toks, fins, oks)
                kind, tokens_attr = "step", self.tokens_per_sync
            self.metrics.dispatch_depth.observe(len(self._inflight) + 1)
            entry = _Inflight(
                kind, arrays,
                tuple(range(self.max_concurrency)), tuple(self._slot_gen),
                tokens=tokens_attr,
            )
            self._inflight.append(entry)
            if self.tracer.enabled:
                # the step's host-phase breakdown so far rides the dispatch
                # event — what explain_request charges this token batch with
                extra = {"phases": {"schedule_s": round(tm.schedule_s, 6),
                                    "draft_s": round(tm.draft_s, 6),
                                    "dispatch_s": round(tm.dispatch_s, 6)}}
            else:
                extra = {}
            if kind == "spec":
                self._trace_dispatch(entry, "spec", drafted=self.draft_tokens,
                                     **extra)
            else:
                self._trace_dispatch(entry, "step", **extra)
            self._drain_to(self.pipeline_depth - 1, finished)
        if not self._active.any():
            # nothing left to overlap with — flush the lagged tail so every
            # observed finish is returned before the caller sees has_work False
            self._drain_to(0, finished)
        if (self.tracker is not None and self.metrics_log_every
                and self._step_count % self.metrics_log_every == 0):
            self.metrics.log_to(self.tracker, step=self._step_count)
        if self.telemetry.enabled:
            with spans.span("serve.telemetry") as sp:
                self.telemetry.poll(self)
            tm.telemetry_s = sp.end - sp.start
        tm.journal_s = ((journal.append_s - j_start)
                        if journal is not None else 0.0)
        return finished

    def run(self, requests: Iterable[Request], max_steps: int | None = None
            ) -> list[RequestOutput]:
        """Serve a batch of requests to completion, respecting backpressure
        (a queue-full rejection just defers the submit until slots drain).
        Returns outputs in submission order; structurally rejected requests
        (e.g. oversized prompts) come back with ``finish_reason='rejected:…'``.
        Hitting ``max_steps`` aborts whatever is still active/queued with
        `FINISH_ABORTED` and returns the partial results — completed outputs
        are never discarded.
        """
        pending = deque(requests)
        outputs: dict[int, RequestOutput] = {}
        steps = 0
        while pending or self.has_work:
            while pending:
                result = self.submit(pending[0])
                if result.accepted:
                    pending.popleft()
                elif result.reason == REJECT_QUEUE_FULL:
                    break  # drain a step, then retry
                else:
                    req = pending.popleft()
                    outputs[result.request_id] = RequestOutput(
                        request_id=result.request_id, prompt_len=len(req.prompt),
                        tokens=[], finish_reason=f"rejected:{result.reason}",
                        arrival_time=req.arrival_time,
                    )
            for out in self.step():
                outputs[out.request_id] = out
            steps += 1
            if max_steps is not None and steps >= max_steps and (pending or self.has_work):
                for out in self.abort_all():
                    outputs[out.request_id] = out
                while pending:  # backpressure-deferred, never entered the queue
                    req = pending.popleft()
                    if req.request_id is None:
                        req.request_id = self._next_id
                        self._next_id += 1
                    outputs[req.request_id] = RequestOutput(
                        request_id=req.request_id, prompt_len=len(req.prompt),
                        tokens=[], finish_reason=FINISH_ABORTED,
                        arrival_time=req.arrival_time,
                    )
                break
        return [outputs[k] for k in sorted(outputs)]

    # --------------------------------------------------- lifecycle / shutdown
    def cancel(self, request_id: int) -> RequestOutput | None:
        """Abort one request wherever it is — queued (removed) or mid-decode
        (slot retired with `FINISH_ABORTED`, partial tokens returned; any
        in-flight device results for it are discarded by the slot's
        generation bump). None if the id is unknown or already finished."""
        now = time.perf_counter()
        queued = self.scheduler.cancel(request_id)
        if queued is not None:
            self.metrics.requests_cancelled.inc()
            self._slo_never_served(queued)
            if self.tracer.enabled:
                self.tracer.emit(EV_FINISH, request_id, reason=FINISH_ABORTED,
                                 tokens=0, depth=len(self._inflight),
                                 **self._slo_trace_attrs(queued.slo))
            if self.journal is not None:
                self.journal.log_finish(request_id, FINISH_ABORTED, [])
            return RequestOutput(
                request_id=request_id, prompt_len=len(queued.prompt), tokens=[],
                finish_reason=FINISH_ABORTED, arrival_time=queued.arrival_time,
                finish_time=now,
            )
        if self.kv_tier is not None:
            rec = self.kv_tier.pop_record(request_id)
            if rec is not None:
                # hibernated: no slot, no device state — drop the host record
                # and emit the terminal with the tokens parked at hibernation
                self.metrics.requests_cancelled.inc()
                if self.tracer.enabled:
                    self.tracer.emit(EV_FINISH, request_id,
                                     reason=FINISH_ABORTED,
                                     tokens=len(rec.tokens),
                                     depth=len(self._inflight),
                                     **self._slo_trace_attrs(rec.request.slo))
                if self.journal is not None:
                    self.journal.log_finish(request_id, FINISH_ABORTED,
                                            list(rec.tokens))
                return RequestOutput(
                    request_id=request_id, prompt_len=len(rec.request.prompt),
                    tokens=list(rec.tokens), finish_reason=FINISH_ABORTED,
                    token_times=list(rec.token_times),
                    arrival_time=rec.request.arrival_time, finish_time=now,
                )
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.request_id == request_id:
                finished: list[RequestOutput] = []
                self._retire(slot, FINISH_ABORTED, now, finished)
                self.metrics.requests_cancelled.inc()
                return finished[0]
        return None

    @property
    def draining(self) -> bool:
        """True between `begin_drain` and `end_drain` (or while `drain` runs):
        every new `submit` is rejected with `REJECT_DRAINING`."""
        return self._draining

    def begin_drain(self) -> None:
        """Stop admitting NEW submits (rejected with `REJECT_DRAINING`) while
        the caller serves out the backlog itself — the incremental half of
        `drain` for callers that interleave stepping with other shutdown
        work (e.g. the serving preemption handler's grace-window loop)."""
        self._draining = True

    def end_drain(self) -> None:
        """Re-open admission after a `begin_drain` (a cancelled shutdown)."""
        self._draining = False

    def drain(self, max_steps: int | None = None) -> list[RequestOutput]:
        """Graceful shutdown: stop admitting NEW submits (rejected with
        `REJECT_DRAINING`) and serve everything already queued/active to
        completion. ``max_steps`` bounds the wait; leftovers are aborted.
        Outputs are returned in COMPLETION order (the order `step` observed
        each finish), with any ``max_steps`` abort tail appended in
        queue-then-slot order (`abort_all`). Admission re-opens on return."""
        self.begin_drain()
        outputs: list[RequestOutput] = []
        steps = 0
        try:
            while self.has_work:
                outputs.extend(self.step())
                steps += 1
                if max_steps is not None and steps >= max_steps and self.has_work:
                    outputs.extend(self.abort_all())
                    break
        finally:
            self.end_drain()
        return outputs

    def abort_all(self, reason: str = FINISH_ABORTED) -> list[RequestOutput]:
        """Hard shutdown: abort every queued and active request (partial
        tokens kept for active ones). In-flight device results are discarded
        unfetched. Output order is the contract tests rely on: first the
        QUEUE in FIFO submit order, then active slots in ascending slot
        index. ``reason`` defaults to `FINISH_ABORTED`; the supervisor's
        fail-loud path passes its own terminal reason so every shed request
        is distinguishable from an ordinary drain in journal and trace."""
        now = time.perf_counter()
        aborted: list[RequestOutput] = []
        for req in self.scheduler.drain_queue():
            self.metrics.requests_cancelled.inc()
            self._slo_never_served(req)
            if self.tracer.enabled:
                self.tracer.emit(EV_FINISH, req.request_id,
                                 reason=reason,
                                 tokens=len(req.resume_tokens), depth=0,
                                 **self._slo_trace_attrs(req.slo))
            if self.journal is not None:
                self.journal.log_finish(req.request_id, reason,
                                        list(req.resume_tokens))
            aborted.append(RequestOutput(
                request_id=req.request_id, prompt_len=len(req.prompt),
                tokens=list(req.resume_tokens),  # a restored request's
                finish_reason=reason,            # recovered prefix is output
                arrival_time=req.arrival_time, finish_time=now,
            ))
        if self.kv_tier is not None:
            # hibernated streams abort after the queue, before active slots:
            # they are admitted work without device state, so they carry
            # their parked tokens like an active slot's partial output
            for rec in self.kv_tier.records():
                rid = rec.request.request_id
                self.kv_tier.pop_record(rid)
                self.metrics.requests_cancelled.inc()
                if self.tracer.enabled:
                    self.tracer.emit(EV_FINISH, rid, reason=reason,
                                     tokens=len(rec.tokens), depth=0,
                                     **self._slo_trace_attrs(rec.request.slo))
                if self.journal is not None:
                    self.journal.log_finish(rid, reason, list(rec.tokens))
                aborted.append(RequestOutput(
                    request_id=rid, prompt_len=len(rec.request.prompt),
                    tokens=list(rec.tokens), finish_reason=reason,
                    token_times=list(rec.token_times),
                    arrival_time=rec.request.arrival_time, finish_time=now,
                ))
        for slot in np.flatnonzero(self._active):
            self.metrics.requests_cancelled.inc()
            self._retire(int(slot), reason, now, aborted)
        if self.tracer.enabled:
            # the cleared entries are never fetched — emit their EV_FETCH as
            # discarded so dispatch/fetch stays balanced in the trace
            for i, entry in enumerate(self._inflight):
                self.tracer.emit(EV_FETCH, None, seq=entry.seq,
                                 what=entry.kind, discarded=True,
                                 depth=len(self._inflight) - i - 1,
                                 tokens=entry.tokens)
        self._inflight.clear()  # every entry now predates a generation bump
        return aborted

    # ------------------------------------------------------ snapshot / resume
    def _entry(self, request: Request, tokens: list[int], admitted: bool,
               now: float) -> dict[str, Any]:
        """One snapshot line: the request's journal identity plus its stream
        state — enough for `resume` to rebuild it exactly."""
        rec = request_record(request)
        rec.pop("rid", None)
        return {
            "rid": request.request_id,
            **rec,
            "toks": [int(t) for t in tokens],
            "retries": int(request.retries),
            "admitted": bool(admitted),
            "waited_s": (max(0.0, now - request.arrival_time)
                         if request.arrival_time is not None else 0.0),
        }

    def snapshot(self, path: str | os.PathLike) -> list[RequestOutput]:
        """Capture everything needed to continue this engine's work in a new
        process: queue order, per-slot emitted tokens, retry counts, and the
        id watermark (rng state and budgets are derivable — seeds plus token
        counts). Sampling seeds make the snapshot exact: `resume` in a fresh
        engine continues every stream bit-for-bit.

        The in-flight dispatch pipeline is drained first (fetches only — no
        new work is dispatched), so the snapshot is a CONSISTENT frontier;
        finishes observed during that drain are returned and must be
        delivered/recorded by the caller like any `step()` result. The file
        is written atomically (tmp + fsync + rename): a crash mid-snapshot
        leaves the previous snapshot (or none), never a torn one.
        """
        finished: list[RequestOutput] = []
        self._drain_to(0, finished)
        now = time.perf_counter()
        entries: list[dict[str, Any]] = []
        # slot order approximates admission order well enough for FIFO
        # fairness on restore; correctness never depends on it (each stream
        # is independently positioned by its own token count)
        for slot in range(self.max_concurrency):
            if not self._active[slot]:
                continue
            request, out = self._slot_req[slot], self._slot_out[slot]
            entries.append(self._entry(request, out.tokens, True, now))
        if self.kv_tier is not None:
            # hibernated streams snapshot like active slots (admitted, with
            # their parked tokens): resume re-admits them mid-stream via the
            # same continuation prefill a crashed slot gets
            for rec in self.kv_tier.records():
                entries.append(self._entry(rec.request, rec.tokens, True, now))
        for request in self.scheduler.snapshot_queue():
            entries.append(self._entry(
                request, request.resume_tokens,
                admitted=bool(request.resume_tokens), now=now,
            ))
        data = {
            "format": SNAPSHOT_FORMAT,
            "ts": time.time(),
            "next_id": self._next_id,
            "draining": self._draining,
            "entries": entries,
        }
        path = Path(path)
        tmp = path.with_suffix(path.suffix + ".tmp")
        with open(tmp, "wb") as f:
            f.write(json.dumps(data, separators=(",", ":")).encode())
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return finished

    def _load_recovery_source(self, path: Path) -> tuple[
            str, dict[int, RequestOutput], list[dict], float, int, int]:
        """Normalize a journal file or a snapshot file into (kind, completed
        outputs, pending entries, wall ts of the crash frontier, next_id
        floor, torn tail bytes)."""
        with open(path, "rb") as f:
            head = f.read(len(JOURNAL_MAGIC))
        if head == JOURNAL_MAGIC:
            scan: JournalScan = RequestJournal.scan(path)
            completed = {
                rid: RequestOutput(
                    request_id=rid,
                    prompt_len=len(scan.submits[rid]["prompt"]),
                    tokens=list(toks), finish_reason=reason,
                )
                for rid, (reason, toks) in scan.finishes.items()
            }
            entries = []
            admitted = set(scan.admit_order)
            for rid in scan.incomplete():
                rec = scan.submits[rid]
                entries.append({
                    "rid": rid,
                    "prompt": rec["prompt"],
                    "params": rec["params"],
                    "deadline_s": rec.get("deadline_s"),
                    "cache_prefix": rec.get("cache_prefix", True),
                    "toks": scan.tokens.get(rid, []),
                    "retries": 0,
                    "admitted": rid in admitted,
                    "waited_s": max(0.0, scan.last_ts - float(rec.get("ts", scan.last_ts))),
                })
            return ("journal", completed, entries, scan.last_ts,
                    max(scan.submits, default=-1) + 1,
                    scan.truncated_tail_bytes)
        data = json.loads(path.read_bytes())
        if data.get("format") != SNAPSHOT_FORMAT:
            raise ValueError(
                f"{path} is neither a request journal nor a "
                f"{SNAPSHOT_FORMAT} snapshot"
            )
        return ("snapshot", {}, list(data.get("entries", ())),
                float(data.get("ts", 0.0)), int(data.get("next_id", 0)), 0)

    def resume(self, path: str | os.PathLike | None = None) -> RecoveryReport:
        """Crash-exact recovery: rebuild this (idle, freshly constructed)
        engine's queue from a durable source — the engine's own journal
        (default), another journal, or a `snapshot` file.

        - requests with a FINISH record come back in ``report.completed``
          (token streams included) and are NOT replayed — dedupe them against
          whatever the dead process already delivered;
        - requests that were mid-decode are re-admitted FIRST (admission
          order), each carrying its emitted tokens as ``resume_tokens``: one
          continuation prefill + a fast-forwarded rng chain continues the
          stream bit-for-bit (an already-satisfied budget or an emitted EOS
          completes it right here instead). Their ``deadline_s`` is cleared —
          the queue-wait deadline was consumed by the pre-crash admission,
          so a restored in-flight request can never instantly expire;
        - still-queued requests re-enter the queue in submit order. One with
          a ``deadline_s`` whose WALL-CLOCK budget fully elapsed during the
          downtime is expired now with ``rejected:deadline`` (reported in
          ``report.expired``, journaled, counted — never silently dropped);
          survivors resume with only their pre-crash queue wait counted, so
          the downtime itself never eats the remaining deadline budget.
        """
        if path is None:
            if self.journal is None:
                raise ValueError("resume() needs a path when the engine has "
                                 "no journal configured")
            path = self.journal.path
        path = Path(path)
        if self._active.any() or self.scheduler.queue_depth or self._inflight:
            raise RuntimeError("resume() requires an idle engine — restore "
                               "into a freshly constructed one")
        kind, completed, entries, last_ts, next_id, tail = \
            self._load_recovery_source(path)
        wall_now = time.time()
        perf_now = time.perf_counter()
        downtime = max(0.0, wall_now - last_ts) if last_ts else 0.0
        report = RecoveryReport(source=kind, downtime_s=downtime,
                                truncated_tail_bytes=tail,
                                completed=completed)
        # replaying into our OWN journal would duplicate SUBMITs; a foreign
        # source (snapshot, or someone else's journal) must be copied in so
        # the new journal is self-contained for the NEXT crash
        foreign = (self.journal is not None
                   and Path(self.journal.path).resolve() != path.resolve())
        eos = self.eos_token_id
        for e in entries:
            rid = int(e["rid"])
            prompt = [int(t) for t in e["prompt"]]
            plen = len(prompt)
            toks = [int(t) for t in e.get("toks", ())]
            sp = SamplingParams(
                temperature=float(e["params"]["temperature"]),
                top_k=e["params"]["top_k"],
                seed=int(e["params"]["seed"]),
                max_new_tokens=int(e["params"]["max_new_tokens"]),
            )
            admitted = bool(e.get("admitted"))
            deadline = e.get("deadline_s")
            waited = float(e.get("waited_s", 0.0))
            budget = min(sp.max_new_tokens, self.max_len - plen)
            # a stream that already finished but whose FINISH record was lost
            # with the crash (or that snapshotted right at its end) completes
            # HERE — re-admitting it would overrun its budget
            done_reason = None
            if eos is not None and eos in toks:
                toks = toks[: toks.index(eos) + 1]
                done_reason = FINISH_EOS
            elif len(toks) >= budget:
                toks = toks[:budget]
                done_reason = FINISH_LENGTH
            if done_reason is not None:
                out = RequestOutput(request_id=rid, prompt_len=plen,
                                    tokens=toks, finish_reason=done_reason)
                report.completed[rid] = out
                if self.tracer.enabled:
                    self.tracer.emit(EV_SUBMIT, rid, prompt_len=plen,
                                     recovered=True)
                    self.tracer.emit(EV_FINISH, rid, reason=done_reason,
                                     tokens=len(toks), depth=0)
                if self.journal is not None:
                    if foreign:
                        req = Request(prompt=prompt, params=sp, request_id=rid)
                        self.journal.log_submit(req)
                    self.journal.log_finish(rid, done_reason, toks)
                continue
            if not admitted and deadline is not None \
                    and waited + downtime >= float(deadline):
                # the client's wall-clock patience ran out while we were
                # down: reject loudly, exactly as queue expiry would have
                self.metrics.requests_expired.inc()
                out = RequestOutput(
                    request_id=rid, prompt_len=plen, tokens=[],
                    finish_reason=f"rejected:{REJECT_DEADLINE}",
                    finish_time=perf_now,
                )
                report.expired.append(out)
                if self.tracer.enabled:
                    self.tracer.emit(EV_SUBMIT, rid, prompt_len=plen,
                                     recovered=True)
                    self.tracer.emit(EV_REJECT, rid, reason=REJECT_DEADLINE,
                                     expired=True)
                if self.journal is not None:
                    if foreign:
                        req = Request(prompt=prompt, params=sp, request_id=rid,
                                      deadline_s=deadline)
                        self.journal.log_submit(req)
                    self.journal.log_finish(
                        rid, f"rejected:{REJECT_DEADLINE}", [])
                continue
            # the resume point must fit a prompt bucket; a too-long stream
            # rewinds to the largest admissible prefix and re-decodes the
            # rest (deterministic, so the final stream is unchanged)
            keep = max(0, min(len(toks), self.scheduler.max_prompt_len - plen))
            request = Request(
                prompt=prompt, params=sp, request_id=rid,
                # an admitted request's queue-wait deadline was already
                # consumed pre-crash; keeping it would instantly expire the
                # restored stream
                deadline_s=None if admitted else deadline,
                cache_prefix=bool(e.get("cache_prefix", True)),
                retries=int(e.get("retries", 0)),
                resume_tokens=toks[:keep],
                arrival_time=perf_now - waited,
                priority=int(e.get("priority", 0)),
                tenant=str(e.get("tenant", "")),
            )
            if self.tracer.enabled:
                self.tracer.emit(EV_SUBMIT, rid, prompt_len=plen,
                                 recovered=True, resumed=len(request.resume_tokens))
            result = self.scheduler.submit(request)
            if not result.accepted:
                raise RuntimeError(
                    f"restored request {rid} rejected ({result.reason}): the "
                    f"resuming engine's scheduler is configured smaller than "
                    f"the crashed one's (queue/buckets must cover the "
                    f"recovered backlog)"
                )
            self.metrics.mark_start()
            self.metrics.requests_submitted.inc()
            if foreign and self.journal is not None:
                self.journal.log_submit(request)
                if request.resume_tokens:
                    self.journal.log_progress(
                        rid, request.resume_tokens, len(request.resume_tokens))
            if admitted:
                self.metrics.requests_resumed.inc()
                report.resumed.append(rid)
            else:
                self.metrics.requests_restored.inc()
                report.restored.append(rid)
        all_rids = ([e["rid"] for e in entries] + list(report.completed)
                    + [next_id - 1])
        self._next_id = max(self._next_id, max(all_rids, default=-1) + 1)
        return report

    # -------------------------------------------------------------- internals
    def _poison_mask(self) -> np.ndarray | None:
        """The [b] NaN-poison mask for this step — None in production (the
        cached all-False device array is reused, no upload); an active
        `reliability.FaultInjector` can mark slots for poisoning (its
        decode-step counter ticks once per dispatched decode step)."""
        injector = active_injector()
        if injector is None:
            return None
        mask = np.zeros(self.max_concurrency, bool)
        slots = injector.poison_slots()
        if slots is not None:
            if slots == ALL_SLOTS:
                mask[self._active] = True
            else:
                for s in slots:
                    if 0 <= s < self.max_concurrency and self._active[s]:
                        mask[s] = True
        return mask

    def _reap_ready(self, finished: list[RequestOutput]) -> None:
        """Process in-flight results the device has ALREADY finished, without
        blocking. Pipelining tolerates retirement lag, it doesn't require it:
        a finished slot whose result sits fetchable costs a frozen (wasted)
        decode step per step it waits, so reaping eagerly keeps occupancy at
        the synchronous level — lag then only happens when the device is
        genuinely still busy, which is exactly when overlap pays."""
        while self._inflight:
            head = self._inflight[0].arrays[0]
            is_ready = getattr(head, "is_ready", None)
            if is_ready is None or not is_ready():
                return
            self._process_oldest(finished)

    def _drain_to(self, limit: int, finished: list[RequestOutput]) -> None:
        """Block-fetch the oldest in-flight results until at most ``limit``
        dispatches remain in flight (limit 0 = fully synchronous)."""
        while len(self._inflight) > limit:
            self._process_oldest(finished)

    def _process_oldest(self, finished: list[RequestOutput]) -> None:
        entry = self._inflight.popleft()
        tm = self._timings
        journal = self.journal
        # the span ends when this program's result is on the host: `now`,
        # the delivery stamp of every token the entry brings
        with spans.span("serve.fetch", seq=entry.seq, kind=entry.kind) as sp:
            fetched = jax.device_get(entry.arrays)
        blocked = sp.end - sp.start
        tm.fetch_blocked_s += blocked
        self.metrics.host_blocked_s.observe(blocked)
        now = sp.end
        # retirement, tokens and SLO accounting, the journal's appends nested
        with spans.span("serve.deliver", seq=entry.seq, kind=entry.kind) as work:
            j0 = journal.append_s if journal is not None else 0.0
            if entry.kind == "admit":
                self._process_admit(entry, fetched, now, finished)
            elif entry.kind == "spec":
                self._process_spec(entry, fetched, now, finished)
            else:
                self._process_step(entry, fetched, now, finished)
            j1 = journal.append_s if journal is not None else 0.0
        deliver = max(0.0, (work.end - work.start) - (j1 - j0))
        tm.deliver_s += deliver
        if self.tracer.enabled:
            # emitted after delivery so the fetch event can attribute its own
            # host cost; consumers key on seq, not event order
            extra = ({"accepted": int(np.max(fetched[3]))}
                     if entry.kind == "spec" else {})
            self.tracer.emit(EV_FETCH, None, seq=entry.seq, what=entry.kind,
                             blocked_s=round(blocked, 6),
                             depth=len(self._inflight), tokens=entry.tokens,
                             phases={"blocked_s": round(blocked, 6),
                                     "deliver_s": round(deliver, 6),
                                     "journal_s": round(j1 - j0, 6)},
                             **extra)

    def _process_admit(self, entry: _Inflight, fetched: tuple, now: float,
                       finished: list[RequestOutput]) -> None:
        tokens, fins = (np.asarray(a) for a in fetched)
        for i, (slot, gen) in enumerate(zip(entry.slots, entry.gens)):
            if self._slot_gen[slot] != gen or self._slot_out[slot] is None:
                continue  # cancelled/aborted while the prefill was in flight
            out = self._slot_out[slot]
            request = self._slot_req[slot]
            out.first_token_time = now
            out.token_times.append(now)
            if request.arrival_time is not None:
                ttft = max(0.0, now - request.arrival_time)
                self.metrics.ttft_s.observe(ttft)
                if self.prefix_cache is not None and request.cache_prefix:
                    (self.metrics.ttft_hit_s if self._slot_hit[slot]
                     else self.metrics.ttft_miss_s).observe(ttft)
            token = int(tokens[i])
            out.tokens.append(token)
            self._hold_kv_tokens(slot, 1)
            self.metrics.tokens_generated.inc()
            self._slot_last_token_t[slot] = now
            if self.journal is not None:
                # durable first-token edge (n > 1 marks a resumed stream's
                # first NEW token — replay applies them uniformly)
                self.journal.log_first_token(
                    out.request_id, token, len(out.tokens)
                )
                self._slot_logged[slot] = len(out.tokens)
            if fins[i]:
                reason = (FINISH_EOS if self.eos_token_id is not None
                          and token == self.eos_token_id else FINISH_LENGTH)
                self._retire(slot, reason, now, finished, device_finished=True)

    def _process_step(self, entry: _Inflight, fetched: tuple, now: float,
                      finished: list[RequestOutput]) -> None:
        tokens, fins, healthy, *counted = (np.asarray(a) for a in fetched)
        if counted:
            self.metrics.observe_step_counters(
                self._contract.step_counters, counted[0])
        if tokens.ndim == 1:
            # single-token dispatch: normalize to the stacked [k, b] layout
            # the multi-token walk below expects (k == 1)
            tokens, fins, healthy = tokens[None], fins[None], healthy[None]
        k = tokens.shape[0]
        # per-token ITL under a k-token dispatch: one fetch lands up to k
        # tokens per slot at once, so the host-observed gap is split evenly
        # across the tokens this entry will actually APPEND for the slot —
        # stopping at the first unhealthy iteration (quarantine, nothing
        # appended) or the first finish — so inter-token p50/p99 stay honest
        # at tokens_per_sync > 1. At k == 1 the split is gap / 1: exactly the
        # single-step sample.
        gaps: dict[int, float] = {}
        for slot, gen in zip(entry.slots, entry.gens):
            if self._slot_gen[slot] != gen or self._slot_out[slot] is None:
                continue
            n = 0
            for t in range(k):
                token = int(tokens[t, slot])
                if not healthy[t, slot] or (
                        self._vocab and not 0 <= token < self._vocab):
                    break
                n += 1
                if fins[t, slot]:
                    break
            gaps[slot] = (now - self._slot_last_token_t[slot]) / max(1, n)
        poisoned_any = False
        appended = 0
        # iteration OUTER, slot inner: token t of every slot retires before
        # token t+1 of any slot — the same order k separate single-token
        # dispatches would produce, which is what the parity matrix pins
        for t in range(k):
            for slot, gen in zip(entry.slots, entry.gens):
                if self._slot_gen[slot] != gen or self._slot_out[slot] is None:
                    continue  # retired/cancelled/requeued — incl. mid-scan
                token = int(tokens[t, slot])
                if not healthy[t, slot] or (
                        self._vocab and not 0 <= token < self._vocab):
                    poisoned_any = True
                    self._quarantine(slot, now, finished)
                    continue
                out = self._slot_out[slot]
                out.tokens.append(token)
                out.token_times.append(now)
                self._hold_kv_tokens(slot, 1)
                appended += 1
                self.metrics.tokens_generated.inc()
                gap = gaps.get(slot, now - self._slot_last_token_t[slot])
                self.metrics.inter_token_s.observe(gap)
                if self._slot_itl[slot] is not None:
                    self._slot_itl[slot].append(gap)
                self._slot_last_token_t[slot] = now
                if (self.journal is not None
                        and len(out.tokens) - self._slot_logged[slot]
                        >= self.journal.progress_every):
                    self.journal.log_progress(
                        out.request_id, out.tokens[self._slot_logged[slot]:],
                        len(out.tokens),
                    )
                    self._slot_logged[slot] = len(out.tokens)
                if fins[t, slot]:
                    reason = (FINISH_EOS if self.eos_token_id is not None
                              and token == self.eos_token_id else FINISH_LENGTH)
                    self._retire(slot, reason, now, finished, device_finished=True)
        if appended:
            self.metrics.tokens_per_dispatch.observe(appended)
        if poisoned_any:
            self.metrics.steps_poisoned.inc()

    def _propose_drafts(self) -> np.ndarray:
        """One [b, k] int32 draft plane for the next verify dispatch, from
        the drafter and the HOST view of each slot's stream (prompt + fetched
        tokens — up to ``pipeline_depth - 1`` tokens behind the device, which
        costs acceptance rate only: verification is an exact-match test, so a
        stale or wrong draft can never change output). Sampled
        (temperature > 0) slots draft nothing — they advance one token per
        dispatch regardless — and unfilled positions stay 0, which is just a
        draft of token 0 the verifier accepts iff it matches greedy."""
        k = self.draft_tokens
        drafts = np.zeros((self.max_concurrency, k), np.int32)
        for slot in np.flatnonzero(self._active):
            request, out = self._slot_req[slot], self._slot_out[slot]
            if request is None or out is None:
                continue
            if request.params.temperature > 0:
                continue
            m = 0
            for t in self._drafter.propose(request.prompt, out.tokens):
                if m >= k:
                    break
                t = int(t)
                if self._vocab and not 0 <= t < self._vocab:
                    break  # out-of-vocab proposal: unverifiable, stop here
                drafts[slot, m] = t
                m += 1
            if m:
                self.metrics.spec_proposed.inc(m)
        return drafts

    def _process_spec(self, entry: _Inflight, fetched: tuple, now: float,
                      finished: list[RequestOutput]) -> None:
        """Fetch path for a speculative verify dispatch. The device reports
        per slot how many tokens it accepted AND emitted (``ns`` — 0 for
        frozen or poisoned rows, else 1..k+1) plus the stacked [s, b] token/
        finish planes; the walk appends exactly ``ns[slot]`` tokens per
        healthy slot in the same iteration-outer order `_process_step` uses,
        so retirement order matches what ``ns[slot]`` single-token dispatches
        would have produced. A ``!ok`` slot quarantines exactly once (its
        generation bumps on the first offence; the device already rolled its
        KV frontier back to the pre-step cursor)."""
        toks, fins, oks, ns = (np.asarray(a) for a in fetched)
        s = toks.shape[0]
        gaps: dict[int, float] = {}
        for slot, gen in zip(entry.slots, entry.gens):
            if self._slot_gen[slot] != gen or self._slot_out[slot] is None:
                continue
            n = int(ns[slot])
            gaps[slot] = (now - self._slot_last_token_t[slot]) / max(1, n)
            request = self._slot_req[slot]
            if oks[slot] and n and request.params.temperature <= 0:
                # greedy verify telemetry: n - 1 of the k drafts survived
                self.metrics.spec_accepted.inc(n - 1)
                self.metrics.spec_accept_len.observe(n - 1)
        poisoned_any = False
        appended = 0
        for t in range(s):
            for slot, gen in zip(entry.slots, entry.gens):
                if self._slot_gen[slot] != gen or self._slot_out[slot] is None:
                    continue  # retired/cancelled/quarantined mid-walk
                if not oks[slot]:
                    poisoned_any = True
                    self._quarantine(slot, now, finished)
                    continue
                if t >= int(ns[slot]):
                    continue
                token = int(toks[t, slot])
                if self._vocab and not 0 <= token < self._vocab:
                    poisoned_any = True
                    self._quarantine(slot, now, finished)
                    continue
                out = self._slot_out[slot]
                out.tokens.append(token)
                out.token_times.append(now)
                self._hold_kv_tokens(slot, 1)
                appended += 1
                self.metrics.tokens_generated.inc()
                gap = gaps.get(slot, now - self._slot_last_token_t[slot])
                self.metrics.inter_token_s.observe(gap)
                if self._slot_itl[slot] is not None:
                    self._slot_itl[slot].append(gap)
                self._slot_last_token_t[slot] = now
                if (self.journal is not None
                        and len(out.tokens) - self._slot_logged[slot]
                        >= self.journal.progress_every):
                    self.journal.log_progress(
                        out.request_id, out.tokens[self._slot_logged[slot]:],
                        len(out.tokens),
                    )
                    self._slot_logged[slot] = len(out.tokens)
                if fins[t, slot]:
                    reason = (FINISH_EOS if self.eos_token_id is not None
                              and token == self.eos_token_id else FINISH_LENGTH)
                    self._retire(slot, reason, now, finished, device_finished=True)
        if appended:
            self.metrics.tokens_per_dispatch.observe(appended)
            self.metrics.spec_tokens.inc(appended)
        if poisoned_any:
            self.metrics.steps_poisoned.inc()

    def _quarantine(self, slot: int, now: float,
                    finished: list[RequestOutput]) -> None:
        """Watchdog action for a poisoned slot (non-finite logits or an
        out-of-range sampled token): the slot's stream is garbage from this
        step on, but every other slot is untouched — so quarantine ONLY this
        one. The device already froze the slot (health is a finish source in
        the compiled step), so no lagged dispatch mutates it further. First
        offence: free the slot and re-prefill the request from its prompt
        (front of queue; its rng chain restarts from the seed, so the replay
        is token-identical to an unpoisoned run). Second offence: retire with
        `FINISH_ERROR`, keeping the engine serving healthy slots."""
        request = self._slot_req[slot]
        if self.tracer.enabled:
            self.tracer.emit(EV_QUARANTINE, request.request_id, slot=slot,
                             gen=int(self._slot_gen[slot]),
                             retry=request.retries,
                             depth=len(self._inflight))
        if request.retries == 0:
            request.retries += 1
            self.metrics.requests_retried.inc()
            self._release_slot(slot)
            self.scheduler.requeue(request)
        else:
            self._retire(slot, FINISH_ERROR, now, finished)

    def _admit_pending(self, finished: list[RequestOutput]) -> spans.span:
        """Expire overdue requests, tick the KV tier and seat queued runs in
        free slots: one ``serve.admit`` span, returned, whose ``admitted``
        counts the requests seated (their dispatches, fetches and deliveries
        nest inside it)."""
        with spans.span("serve.admit", admitted=0) as sp:
            self._admit_queued(finished, sp.attrs)
        return sp

    def _admit_queued(self, finished: list[RequestOutput], attrs: dict) -> None:
        now = time.perf_counter()
        for request in self.scheduler.pop_expired(now):
            # expired while queued: reject rather than serve a reply the
            # client has already abandoned (REJECT_DEADLINE, never admitted)
            self.metrics.requests_expired.inc()
            self._slo_never_served(request)
            if self.tracer.enabled:
                self.tracer.emit(EV_REJECT, request.request_id,
                                 reason=REJECT_DEADLINE, expired=True,
                                 **self._slo_trace_attrs(request.slo))
            if self.journal is not None:
                self.journal.log_finish(
                    request.request_id, f"rejected:{REJECT_DEADLINE}", []
                )
            finished.append(RequestOutput(
                request_id=request.request_id, prompt_len=len(request.prompt),
                tokens=[], finish_reason=f"rejected:{REJECT_DEADLINE}",
                arrival_time=request.arrival_time, finish_time=now,
            ))
        if self.kv_tier is not None:
            # the per-step tier tick: thrash-guard hysteresis, low-water
            # background spill, idle hibernation, and at most one wake —
            # BEFORE the admission loop, so a prefill-mode wake lands at
            # the queue front this very step
            self.kv_tier.poll()
        while self._free:
            run_len = self.scheduler.peek_run(
                min(len(self._free), self._admit_sizes[-1])
            )
            if run_len == 0:
                return
            nb = max(s for s in self._admit_sizes if s <= run_len)
            group = self.scheduler.pop_run(nb)
            if self.prefix_cache is not None:
                # pin NOW: nothing mutates the trie between the peek_run probe
                # and this acquire, so the match agrees with the suffix bucket
                # the group was sized by
                matches = [
                    self.prefix_cache.acquire(r.prompt)
                    if r.cache_prefix and not r.resume_tokens
                    else NO_MATCH
                    for r in group
                ]
                if any(m.tokens for m in matches):
                    if not self._admit_group_cached(group, matches, finished):
                        return  # block-pool backpressure: group requeued
                    attrs["admitted"] += len(group)
                    continue
                for r in group:
                    if r.cache_prefix and not r.resume_tokens:
                        self.metrics.prefix_misses.inc()
            # all-miss (or cache off): the plain admission program — with the
            # prefix cache disabled this path is bit-for-bit the pre-cache one
            if not self._admit_group(group, finished):
                return  # block-pool backpressure: group requeued
            attrs["admitted"] += len(group)

    def _key_data_row(self, seed: Any, splits: int) -> np.ndarray:
        """One admitted request's rng key data: made on the host
        (`_host_key_data`) for a fresh request, else by the device path
        (another PRNG implementation; a resumed request's chain fast-forwarded
        past its ``splits`` replayed tokens, so its next sample draws the key
        the uninterrupted run would) and fetched, bit-equal either way."""
        row = None if splits else _host_key_data(seed)
        if row is not None:
            self.metrics.admit_host_keys.inc()
            return row
        self.metrics.admit_device_keys.inc()
        key = jax.random.key(seed)
        for _ in range(splits):
            key = jax.random.split(key)[0]
        return np.asarray(jax.random.key_data(key))

    def _admit_group(self, group: list[Request],
                     finished: list[RequestOutput]) -> bool:
        # reserve BEFORE touching slots: on exhaustion the group goes
        # back to the queue front untouched (backpressure, not a crash)
        reservation = self._reserve_blocks(group, None)
        if reservation is None:
            return False
        with TraceAnnotation("serve.admit.args"):
            nb = len(group)
            slots = [self._free.popleft() for _ in group]
            bucket = self.scheduler.bucket_for(max(r.prefill_len for r in group))
            padded = np.zeros((nb, bucket), np.int32)
            lens = np.zeros(nb, np.int32)
            temps = np.zeros(nb, np.float32)
            topks = np.zeros(nb, np.int32)
            budgets = np.zeros(nb, np.int32)
            rng = np.zeros((nb,) + self._rng_data.shape[1:], self._rng_data.dtype)
            for i, request in enumerate(group):
                plen = len(request.prompt)
                k = len(request.resume_tokens)
                # a resumed request (crash recovery) prefills prompt + its
                # already-emitted tokens in ONE continuation pass: same numerics
                # as the original prefill-then-decode, so the stream stays
                # bit-identical (tests/test_serving_recovery.py)
                ptoks = request.prefill_source()
                padded[i, : plen + k] = ptoks
                lens[i] = plen + k
                sp = request.params
                temps[i] = sp.temperature
                topks[i] = sp.top_k or 0
                # the context is fixed-size: cap generation so cache writes stay
                # inside [0, n_positions). The cap is against the ORIGINAL prompt
                # (a resumed request keeps the budget it started with, minus the
                # k tokens it already emitted)
                budgets[i] = min(int(sp.max_new_tokens), self.max_len - plen) - k
                rng[i] = self._key_data_row(sp.seed, k)
                if k:
                    self.metrics.replayed_tokens.inc(plen + k)
            tables_np, dest_np = self._commit_reservation(
                reservation, group, None, slots)
            args = jax.device_put((padded, np.asarray(slots, np.int32), lens,
                                   temps, topks, rng, budgets, dest_np,
                                   tables_np))
        (self._cache, first, fin0, self._d_tables, self._d_tokens,
         self._d_pos, self._d_temps, self._d_topks, self._d_finished,
         self._d_remaining, self._rng_data) = self._dispatch(
            self._compile_key("admit", bucket, nb), self._admit_fn,
            self._cache, self.params, *args,
            self._d_tables, self._d_tokens, self._d_pos, self._d_temps,
            self._d_topks, self._d_finished, self._d_remaining,
            self._rng_data, self._d_eos,
        )
        self.metrics.prefill_tokens.inc(int(lens.sum()))
        self.metrics.admit_batch_size.observe(nb)
        self._finish_admit(group, None, slots, (first, fin0), finished, bucket)
        return True

    def _admit_group_cached(self, group: list[Request],
                            matches: list[PrefixMatch],
                            finished: list[RequestOutput]) -> bool:
        pc = self.prefix_cache
        nb = len(group)
        # context guard: `dynamic_update_slice` CLAMPS out-of-range starts, so
        # a row whose cached prefix plus padded suffix bucket overran
        # n_positions would silently shift its suffix write backwards over the
        # prefix — trim the match instead. Trimming grows that suffix, which
        # can grow the shared bucket and push OTHER rows over; iterate to a
        # fixed point (the bucket only grows and matches only shrink, so this
        # terminates — in the worst case at tokens=0 == plain admission).
        while True:
            bucket = self.scheduler.bucket_for(
                max(len(r.prompt) - m.tokens for r, m in zip(group, matches))
            )
            over = [i for i, m in enumerate(matches)
                    if m.tokens and m.tokens + bucket > self.max_len]
            if not over:
                break
            keep = max(0, (self.max_len - bucket) // pc.block_tokens)
            for i in over:
                matches[i] = pc.trim(matches[i], keep)
        # reservation AFTER the trim fixed point: aliased counts must
        # reflect the matches admission will actually use. On failure the
        # pins are released and the group requeued inside _reserve_blocks.
        reservation = self._reserve_blocks(group, matches)
        if reservation is None:
            return False
        with TraceAnnotation("serve.admit.args"):
            slots = [self._free.popleft() for _ in group]
            padded = np.zeros((nb, bucket), np.int32)
            suffix_lens = np.zeros(nb, np.int32)
            cached_lens = np.zeros(nb, np.int32)
            temps = np.zeros(nb, np.float32)
            topks = np.zeros(nb, np.int32)
            budgets = np.zeros(nb, np.int32)
            rng = np.zeros((nb,) + self._rng_data.shape[1:], self._rng_data.dtype)
            for i, (request, m) in enumerate(zip(group, matches)):
                plen = len(request.prompt)
                suffix = request.prompt[m.tokens:]
                padded[i, :len(suffix)] = suffix
                suffix_lens[i] = len(suffix)
                cached_lens[i] = m.tokens
                sp = request.params
                temps[i] = sp.temperature
                topks[i] = sp.top_k or 0
                # budget depends on the FULL prompt length — token identity with
                # the cold path requires the same generation cap either way
                budgets[i] = min(int(sp.max_new_tokens), self.max_len - plen)
                rng[i] = self._key_data_row(sp.seed, 0)
                if m.tokens:
                    self.metrics.prefix_hits.inc()
                    self.metrics.prefix_tokens_reused.inc(m.tokens)
                elif request.cache_prefix:
                    self.metrics.prefix_misses.inc()
            # the reservation's tables carry the aliased trie blocks up front
            # and the slot's fresh private blocks after — they serve as BOTH
            # the gather view (aliased prefix, zero-copy) and the decode
            # table; dest drops the aliased region so the scatter writes only
            # the suffix's blocks
            tables_np, dest_np = self._commit_reservation(
                reservation, group, matches, slots)
            args = jax.device_put((tables_np, cached_lens, padded, suffix_lens,
                                   np.asarray(slots, np.int32), temps, topks,
                                   rng, budgets, dest_np, tables_np))
        (self._cache, first, fin0, self._d_tables, self._d_tokens,
         self._d_pos, self._d_temps, self._d_topks, self._d_finished,
         self._d_remaining, self._rng_data) = self._dispatch(
            self._compile_key("cached_admit", bucket, nb),
            self._cached_admit_fn,
            self._cache, self.params, *args,
            self._d_tables, self._d_tokens, self._d_pos, self._d_temps,
            self._d_topks, self._d_finished, self._d_remaining,
            self._rng_data, self._d_eos,
        )
        # only the uncached suffixes hit the model — that delta is the point
        self.metrics.prefill_tokens.inc(int(suffix_lens.sum()))
        self.metrics.admit_batch_size.observe(nb)
        self._finish_admit(group, matches, slots, (first, fin0), finished,
                           bucket)
        return True

    # ------------------------------------------------------- paged block pool
    def _reserve_blocks(
        self, group: list[Request], matches: list[PrefixMatch] | None
    ) -> list[tuple[int, list[int]]] | None:
        """All-or-nothing block reservation for one admission group. Each
        request needs blocks covering ``min(prompt + max_new_tokens,
        max_len)`` tokens minus its trie-aliased prefix — reserved UP FRONT
        so mid-decode writes can never find the pool empty. On shortfall,
        evictable trie blocks are reclaimed; if still short, pins are dropped
        and the group goes back to the queue FRONT in its original order:
        backpressure, never a crash, and FIFO order is preserved. Returns
        ``[(aliased_blocks, private_block_ids)]`` per request, or None."""
        alloc, bt = self._allocator, self._block_tokens
        needs: list[tuple[int, int]] = []
        for i, request in enumerate(group):
            m = matches[i] if matches is not None else None
            aliased = (m.tokens // bt) if m is not None else 0
            extent = FIFOScheduler.decode_extent(request, self.max_len)
            n_res = -(-extent // bt)  # ceil: the frontier block counts whole
            needs.append((aliased, max(0, n_res - aliased)))
        total = sum(n for _, n in needs)
        if alloc.free_count < total and self.kv_tier is not None:
            # spill-then-admit: page cold trie blocks (then, under pressure,
            # whole cold slots) to host BEFORE falling back to discard
            # eviction. A thrash-frozen tier makes this a no-op and the
            # pre-tier reclaim/requeue behavior below takes over.
            self.kv_tier.release_for(total)
        if alloc.free_count < total and self.prefix_cache is not None:
            self.prefix_cache.reclaim(total - alloc.free_count)
        if alloc.free_count < total:
            if matches is not None:
                for m in matches:
                    if m.nodes:
                        self.prefix_cache.release(m)
            for request in reversed(group):
                self.scheduler.requeue(request)
            return None
        return [(aliased, alloc.alloc(n) or []) for aliased, n in needs]

    def _commit_reservation(
        self, reservation: list[tuple[int, list[int]]], group: list[Request],
        matches: list[PrefixMatch] | None, slots: list[int],
    ) -> tuple[np.ndarray, np.ndarray]:
        """Materialize a reservation into the admission call's table/dest
        arrays and the slot mirrors. Table rows: trie-aliased blocks first,
        then the slot's private blocks; everything past the reservation
        points at ``num_blocks`` so a stray read clamps harmlessly and a
        stray write drops. ``dest`` marks ONLY the blocks the admission
        scatter must fill — ``[aliased, ceil(prefill_len / bt))`` — the
        aliased prefix stays untouched (zero-copy) and reserved decode
        blocks are filled in place by the decode step before any read."""
        bt = self._block_tokens
        nb = len(group)
        sentinel = self._allocator.num_blocks
        tables = np.full((nb, self._blocks_per_slot), sentinel, np.int32)
        dest = np.full((nb, self._blocks_per_slot), sentinel, np.int32)
        for i, (request, slot) in enumerate(zip(group, slots)):
            aliased, priv = reservation[i]
            if aliased:
                tables[i, :aliased] = matches[i].block_ids[:aliased]
            if priv:
                tables[i, aliased:aliased + len(priv)] = priv
            n_written = -(-request.prefill_len // bt)
            dest[i, aliased:n_written] = tables[i, aliased:n_written]
            self._slot_table_host[slot] = tables[i].copy()
            self._slot_priv[slot] = list(priv)
            self._slot_aliased[slot] = aliased
        return tables, dest

    def _blocks_needed(self, request: Request) -> int:
        """Pool blocks admitting ``request`` right now would reserve (the
        capacity probe's per-request price — unpinned, so a later acquire may
        see a slightly different trie; the reservation re-checks)."""
        bt = self._block_tokens
        extent = FIFOScheduler.decode_extent(request, self.max_len)
        n_res = -(-extent // bt)
        if (self.prefix_cache is not None and request.cache_prefix
                and not request.resume_tokens):
            n_res -= self.prefix_cache.match_len(request.prompt) // bt
        return max(0, n_res)

    def _paged_capacity(self, requests: list[Request]) -> int:
        """Scheduler hook (`FIFOScheduler.capacity_fn`): how many of the
        front-run requests the block pool can seat — free blocks plus what
        trie eviction could reclaim. Optimistic by one race (an evictable
        block the group's own acquire then pins): the reservation re-checks
        and requeues, so the cost is a retry, never a crash."""
        avail = self._allocator.free_count
        if self.prefix_cache is not None:
            avail += int(self.prefix_cache.memory_stats()["blocks_evictable"])
        if self.kv_tier is not None:
            # blocks the spill-then-admit path could free (hibernatable cold
            # slots above the residency floor); 0 while thrash-frozen
            avail += self.kv_tier.pressure_headroom()
        n = 0
        for request in requests:
            need = self._blocks_needed(request)
            if need > avail:
                break
            avail -= need
            n += 1
        return n

    def _finish_admit(self, group: list[Request],
                      matches: list[PrefixMatch] | None, slots: list[int],
                      arrays: tuple, finished: list[RequestOutput],
                      bucket: int | None = None) -> None:
        gens = []
        for i, (slot, request) in enumerate(zip(slots, group)):
            self._slot_gen[slot] += 1
            gens.append(int(self._slot_gen[slot]))
            self._slot_req[slot] = request
            self._count_sample_tail(request.params, 1)
            self._hold_kv_tokens(
                slot, len(request.prompt) + len(request.resume_tokens))
            self._slot_out[slot] = RequestOutput(
                request_id=request.request_id, prompt_len=len(request.prompt),
                # a resumed stream's recovered prefix is part of the output;
                # decode appends from token k+1
                tokens=list(request.resume_tokens), finish_reason="",
                arrival_time=request.arrival_time,
                token_times=[math.nan] * len(request.resume_tokens),
            )
            # the recovered prefix came FROM the journal/snapshot — only
            # tokens past it need (re-)journaling
            self._slot_logged[slot] = len(request.resume_tokens)
            self._active[slot] = True
            slo = request.slo
            self._slot_itl[slot] = (
                [] if slo is not None and slo.itl_p99_s is not None else None
            )
            if matches is not None:
                m = matches[i]
                # pins travel with the slot; released at retirement. The plain
                # path leaves the _release_slot defaults (no match, miss).
                self._slot_match[slot] = m if m.nodes else None
                self._slot_hit[slot] = bool(m.tokens)
        entry = _Inflight("admit", arrays, tuple(slots), tuple(gens))
        self._inflight.append(entry)
        self._trace_dispatch(
            entry, "cached_admit" if matches is not None else "admit"
        )
        # each request's wait, from the scheduler's enqueue stamp to the
        # start of the dispatch that took it
        taken = self._last_dispatch.start
        for request in group:
            if request.queued_time is not None:
                spans.record("serve.queued", request.queued_time, taken,
                             rid=request.request_id, bucket=bucket, seq=entry.seq)
                request.queued_time = None
        if self.tracer.enabled:
            for i, (slot, request) in enumerate(zip(slots, group)):
                m = matches[i] if matches is not None else None
                self.tracer.emit(
                    EV_ADMIT, request.request_id, slot=slot, gen=gens[i],
                    bucket=bucket, seq=entry.seq,
                    cache_hit=bool(m.tokens) if m is not None else False,
                    cached_tokens=m.tokens if m is not None else 0,
                    resumed=len(request.resume_tokens),
                    depth=len(self._inflight),
                )
        # at depth 1 this fetches the first tokens NOW — an EOS or 1-token
        # budget frees its slot before the next group is sized, exactly
        # the pre-pipelining admission behavior
        self._drain_to(self.pipeline_depth - 1, finished)

    def _slo_never_served(self, request: Request) -> None:
        """SLO bookkeeping for an accepted request that terminates without
        ever being admitted (queue-deadline expiry, queued cancel/abort): a
        miss for its class — its TTFT bound, if any, was certainly blown."""
        if request.slo is not None:
            self.metrics.observe_slo(
                request.slo, clean=False,
                ttft_ok=request.slo.ttft_s is None, itl_ok=True, tokens=0,
            )

    @staticmethod
    def _slo_trace_attrs(slo: Any, attained: bool = False) -> dict[str, Any]:
        """SLO class + attainment verdict for a terminal trace event, so
        `tools/trace_report.py --slo` re-tells `metrics.goodput()`'s story
        from the trace alone. Empty for unclassed requests — their terminals
        stay exactly the pre-SLO schema."""
        if slo is None:
            return {}
        return {"slo": slo.name, "attained": bool(attained)}

    def _retire(self, slot: int, reason: str, now: float,
                finished: list[RequestOutput],
                device_finished: bool = False) -> None:
        out = self._slot_out[slot]
        request = self._slot_req[slot]
        out.finish_reason = reason
        out.finish_time = now
        if out.arrival_time is not None:
            self.metrics.request_latency_s.observe(max(0.0, now - out.arrival_time))
        self.metrics.requests_finished.inc()
        # SLO attainment (docs/observability.md): clean finishes only; the
        # TTFT bound is judged on the host-observed first-token latency and
        # the ITL bound on THIS request's own p99 decode gap (nearest-rank,
        # same convention as the metrics histograms)
        slo = request.slo
        ttft_ok = itl_ok = True
        if slo is not None:
            if slo.ttft_s is not None:
                ttft_ok = (
                    out.first_token_time is not None
                    and out.arrival_time is not None
                    and out.first_token_time - out.arrival_time <= slo.ttft_s
                )
            gaps = self._slot_itl[slot]
            if slo.itl_p99_s is not None and gaps:
                itl_ok = nearest_rank(sorted(gaps), 0.99) <= slo.itl_p99_s
        attained = self.metrics.observe_slo(
            slo, clean=reason in (FINISH_EOS, FINISH_LENGTH),
            ttft_ok=ttft_ok, itl_ok=itl_ok,
            tokens=len(out.tokens) - len(request.resume_tokens),
        )
        if self.tracer.enabled:
            self.tracer.emit(EV_FINISH, out.request_id, slot=slot,
                             gen=int(self._slot_gen[slot]), reason=reason,
                             tokens=len(out.tokens),
                             depth=len(self._inflight),
                             **self._slo_trace_attrs(slo, attained))
        if self.journal is not None:
            # the terminal record carries the whole stream: completed work is
            # parity-checkable and dedupable from the journal alone
            self.journal.log_finish(out.request_id, reason, out.tokens)
        if (self.prefix_cache is not None and reason != FINISH_ERROR
                and self._slot_req[slot].cache_prefix
                and not self._slot_req[slot].resume_tokens):
            # donate the retired slot's prompt-region KV to the prefix trie.
            # Safe under pipelining: decode writes land at >= prompt_len and a
            # finished slot is frozen by its on-device mask, so [0, prompt_len)
            # is exactly the admission-time prefill whenever we get here. A
            # FINISH_ERROR slot is poisoned — never donate it. A resumed
            # stream is excluded too: its prompt rows came from a
            # continuation prefill padded to a bigger bucket than a cold
            # prefill of the prompt alone would use, and donated rows must
            # only ever be ones a cold path would have produced.
            # Zero-copy: ownership of the prompt's FULL blocks moves to the
            # trie (duplicates are freed inside adopt, the already-aliased
            # prefix just stays the trie's). Blocks at or past the frontier
            # — anything decode wrote or may still write from a lagged
            # dispatch — are NEVER adopted; they are freed by _release_slot
            # once the table row is neutralized.
            prompt = self._slot_req[slot].prompt
            n_full = len(prompt) // self._block_tokens
            aliased = int(self._slot_aliased[slot])
            if n_full:
                self.prefix_cache.adopt(
                    prompt,
                    [int(x) for x in self._slot_table_host[slot][:n_full]],
                    owned_from=aliased,
                )
                donated = max(0, n_full - aliased)
                self._slot_priv[slot] = self._slot_priv[slot][donated:]
        self._release_slot(slot, device_finished)
        finished.append(out)

    def _count_sample_tail(self, params: SamplingParams, sign: int) -> None:
        """A slot took (+1) or gave up (-1) a tenant with these params."""
        if params.temperature > 0:
            self._draw_slots += sign
            if (params.top_k or 0) > 0:
                self._top_k_slots += sign

    def _hold_kv_tokens(self, slot: int, n: int) -> None:
        """``slot`` holds ``n`` more keys and values (its prompt at admit, one
        a delivered token)."""
        self._slot_kv_tokens[slot] += n
        self._held_kv_tokens += n

    def _release_slot(self, slot: int, device_finished: bool = False) -> None:
        """Return a slot to the free pool. Device state needs no touch-up:
        the slot is frozen by its on-device finished mask (or, for a cancel,
        burns out harmlessly against its token budget), lagged in-flight
        results are invalidated by the generation bump, and the next
        admission's scatter rewrites every per-slot array."""
        if self.prefix_cache is not None and self._slot_match[slot] is not None:
            self.prefix_cache.release(self._slot_match[slot])
        if self._slot_priv[slot]:
            self._allocator.free(self._slot_priv[slot])
        self._slot_priv[slot] = []
        self._slot_table_host[slot] = None
        self._slot_aliased[slot] = 0
        # a slot the device finished (`device_finished`: EOS or budget, read
        # from a fetched finish mask) writes nothing through its table row
        # until the next admission rewrites it, so its release runs no device
        # program between the retirement and that admission's dispatch.
        # A CANCELLED slot is not device-finished: dispatches already in
        # flight — and any issued before the next admission reuses this
        # slot — would keep writing through the stale table row into
        # blocks just freed (and possibly handed to a new tenant). Point
        # the row at num_blocks: paged_decode_update's mode="drop"
        # scatter then discards the write. In-flight work dispatched
        # BEFORE this update is still safe by device dispatch order —
        # its stale writes execute before any re-allocating admission's
        # scatter can land.
        if not device_finished:
            self._d_tables = self._d_tables.at[slot].set(
                jnp.int32(self._allocator.num_blocks))
        self._count_sample_tail(self._slot_req[slot].params, -1)
        self._hold_kv_tokens(slot, -self._slot_kv_tokens[slot])
        self._slot_match[slot] = None
        self._slot_hit[slot] = False
        self._slot_itl[slot] = None
        self._slot_req[slot] = None
        self._slot_out[slot] = None
        self._active[slot] = False
        self._slot_gen[slot] += 1
        self._free.append(slot)
