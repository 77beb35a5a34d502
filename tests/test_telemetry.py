"""Continuous telemetry (`serving/telemetry.py`, docs/observability.md
"Continuous telemetry"): memory accounting exact to `nbytes`, occupancy
gauges consistent across admit/retire/evict at every pipeline-depth ×
admit-batch cell, capacity headroom monotone as slots fill, and the three
export surfaces (Prometheus round-trip, JSONL time-series, /metrics
endpoint) never leaking a non-finite value.
"""

import json
import math
import urllib.request
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = [pytest.mark.serving, pytest.mark.telemetry]

from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.models.kv_cache import tree_bytes_by_dtype, tree_nbytes
from accelerate_tpu.serving import (
    NULL_TELEMETRY,
    KVTierConfig,
    PagedKVConfig,
    Request,
    SamplingParams,
    ServingEngine,
    ServingMetrics,
    TelemetryConfig,
    TelemetryExporter,
)
from accelerate_tpu.serving.telemetry import (
    parse_prometheus_text,
    prometheus_name,
    sanitize_scalars,
    to_prometheus_text,
)


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    return module, params


def _prompts(rng_seed, lengths, vocab=256):
    r = np.random.default_rng(rng_seed)
    return [r.integers(0, vocab, (n,)).astype(np.int32).tolist() for n in lengths]


def _stub_engine(snapshot=None):
    """The duck-typed minimum the exporter samples: metrics with a snapshot
    and a steps counter (no memory_stats/capacity_headroom)."""
    snapshot = snapshot if snapshot is not None else {"serving/x": 1.0}
    return SimpleNamespace(
        metrics=SimpleNamespace(steps=SimpleNamespace(value=7),
                                snapshot=lambda: dict(snapshot)),
    )


# ----------------------------------------------------------- byte accounting
@pytest.mark.parametrize("kind", ["fp32", "bf16", "int8"])
def test_pool_bytes_match_nbytes_across_dtypes(kind):
    """The contract the gauges are named for: the ``slot_pool_bytes`` and
    ``block_pool/pool_bytes`` gauges (one pool, the engine's) equal the sum
    of the underlying arrays' nbytes, exactly, for fp32/bf16/int8 KV
    storage."""
    kw = {"fp32": dict(dtype=jnp.float32),
          "bf16": dict(dtype=jnp.bfloat16),
          "int8": dict(dtype=jnp.float32, kv_cache_dtype=jnp.int8)}[kind]
    cfg = GPT2Config.tiny(**kw)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(8, 32), prefix_cache=True,
                           paged_kv=PagedKVConfig(block_tokens=8))
    mem = engine.memory_stats()
    assert mem["slot_pool_bytes"] == tree_nbytes(engine._cache) == sum(
        int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(engine._cache))
    by_dtype = tree_bytes_by_dtype(engine._cache)
    assert sum(by_dtype.values()) == mem["slot_pool_bytes"]
    for dtype, n in by_dtype.items():
        assert mem[f"slot_pool_bytes/{dtype}"] == n
    if kind == "int8":
        # quantized KV plus its fp32 absmax scale planes, both accounted
        assert "int8" in by_dtype and "float32" in by_dtype
    if kind == "bf16":
        assert "bfloat16" in by_dtype
    assert mem["block_pool/pool_bytes"] == mem["slot_pool_bytes"]
    assert mem["block_pool/block_tokens"] == 8
    assert mem["block_pool/blocks_total"] == 2 * cfg.n_positions // 8


# -------------------------------------------------- occupancy gauge parity
@pytest.mark.parametrize("tier", [
    "plain", "tier",
    # the quantized cells re-drive the whole spill matrix over int8 blocks —
    # multi-second each, slow-gated like the other heavy matrices
    pytest.param("tier-quant", marks=pytest.mark.slow),
])
@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("admit", [1, 4])
def test_occupancy_gauges_consistent_across_matrix(model, depth, admit, tier):
    """At every pipeline-depth × admit-batch cell (the same matrix the
    parity tests run), the occupancy gauges stay self-consistent through
    admit, retire, and LRU eviction, and settle to a clean steady state.
    The ``tier`` cells run the paged pool with the host KV tier attached
    and additionally hold the cross-tier byte invariant (``host_tier/bytes
    == blocks × block_bytes``, and the trie's spilled sub-ledger agrees
    with the tier's) through spill-driven churn. The ``tier-quant`` cells
    rerun that with an int8 pool: every invariant must hold unchanged at
    the HALVED block bytes (int8 payload + fp32 scale planes spill and
    page together, so the cross-tier ledger never sees an fp32 block)."""
    quant = tier == "tier-quant"
    if quant:
        cfg = GPT2Config.tiny(dtype=jnp.float32, kv_cache_dtype=jnp.int8)
        module = GPT2LMHead(cfg)
        params = module.init_params(jax.random.key(0))
    else:
        module, params = model
    # 16 blocks is one full row — the minimum pool, so pressure is real
    kw = dict(max_concurrency=3, prompt_buckets=(8, 32), max_queue=8,
              pipeline_depth=depth, admit_batch=admit, prefix_cache=True,
              paged_kv=PagedKVConfig(block_tokens=8, num_blocks=16))
    if tier != "plain":
        kw.update(kv_tier=KVTierConfig(min_resident_slots=1,
                                       low_water_blocks=2,
                                       thrash_enter_events=10_000))
    engine = ServingEngine(module, params, **kw)
    if quant:
        # the halved-block-bytes anchor: an int8 block (payload + fp32
        # scale planes) must cost well under half its fp32 equivalent
        c = module.config
        h, d = c.n_head, c.n_embd // c.n_head
        assert engine.kv_tier.block_bytes == c.n_layer * 2 * (8 * h * d
                                                              + 8 * h * 4)
        assert engine.kv_tier.block_bytes < c.n_layer * 2 * 8 * h * d * 4 / 2
    prompts = _prompts(17, [20, 24, 22, 20, 26, 24, 22, 26])
    prompts[3] = list(prompts[0])  # duplicate → prefix hit after donation
    for p in prompts:
        assert engine.submit(Request(
            prompt=p, params=SamplingParams(max_new_tokens=4, temperature=0.0),
        )).accepted

    def check():
        mem = engine.memory_stats()
        head = engine.capacity_headroom()
        assert mem["slots_active"] + mem["slots_free"] == mem["slots_total"]
        assert mem["slots_active"] == engine.active_slots
        assert mem["queue_depth"] == engine.scheduler.queue_depth
        assert (mem["block_pool/blocks_free"]
                + mem["block_pool/blocks_resident"]
                + mem["block_pool/blocks_private"]
                == mem["block_pool/blocks_total"])
        assert (mem["block_pool/blocks_pinned"]
                + mem["block_pool/blocks_evictable"]
                + mem["block_pool/blocks_stranded"]
                == mem["block_pool/blocks_resident"])
        pcs = engine.prefix_cache.memory_stats()
        spilled = pcs.get("host_tier", {"blocks": 0})["blocks"]
        assert (mem["block_pool/blocks_resident"] + spilled
                == engine.prefix_cache.node_count())
        assert 0.0 <= mem["block_pool/fragmentation"] <= 1.0
        if tier != "plain":
            # cross-tier byte invariant, and the two host ledgers agree
            assert (mem["host_tier/bytes"]
                    == mem["host_tier/blocks"] * mem["host_tier/block_bytes"])
            assert spilled == engine.kv_tier.trie_host_blocks
            assert (pcs["host_tier"]["bytes"]
                    == spilled * engine.kv_tier.block_bytes)
            assert mem["host_tier/blocks"] >= spilled  # + hibernated content
        assert head["slots_free"] == mem["slots_free"]
        assert head["admissible_requests"] <= head["slots_free"]
        assert head["token_capacity_remaining"] >= 0
        return mem

    while engine.has_work:
        engine.step()
        check()
    mem = engine.memory_stats()
    assert mem["slots_active"] == 0 and mem["block_pool/blocks_pinned"] == 0
    if tier != "plain":
        assert mem["host_tier/hibernated"] == 0
        # force a spill of the drained trie's donations: the invariant must
        # hold with a genuinely non-zero host ledger, not just at 0 == 0
        assert engine.kv_tier.page_out_trie(4) > 0
        assert check()["host_tier/blocks"] > 0
        # the tiny pool saw churn on at least one side of the tier boundary
        assert (engine.metrics.prefix_evictions.value
                + engine.metrics.host_page_outs.value) > 0
    else:
        # the tiny pool saw real churn, or the scenario proves nothing
        assert engine.metrics.prefix_evictions.value > 0


def test_capacity_headroom_monotone_as_slots_fill(model):
    """Headroom is monotone non-increasing as slots fill (the block-gated
    capacity must never report more room after an admission than before
    it)."""
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=4,
                           prompt_buckets=(8,), max_queue=8)
    idle = engine.capacity_headroom()
    assert idle["admissible_requests"] == 4
    assert idle["seconds_to_exhaustion"] is None  # no rate yet, never inf
    assert idle["est_slot_free_s"] == 0.0
    assert idle["token_capacity_remaining"] == 4 * (engine.max_len - 1)
    assert idle["blocks_free"] == engine._allocator.num_blocks
    seen = [idle]
    for i in range(4):
        assert engine.submit(Request(
            prompt=[1 + i, 2, 3, 4],
            params=SamplingParams(max_new_tokens=40, temperature=0.0),
        )).accepted
        engine.step()  # admission happens inside step
        seen.append(engine.capacity_headroom())
    assert [h["slots_free"] for h in seen] == [4, 3, 2, 1, 0]
    for prev, cur in zip(seen, seen[1:]):
        assert cur["admissible_requests"] <= prev["admissible_requests"]
        assert (cur["token_capacity_remaining"]
                <= prev["token_capacity_remaining"])
        assert cur["blocks_free"] <= prev["blocks_free"]
    full = seen[-1]
    assert full["seconds_to_exhaustion"] is not None  # decoding → rate > 0
    assert full["est_slot_free_s"] is not None and full["est_slot_free_s"] > 0


# ------------------------------------------------------------ export surfaces
def test_prometheus_round_trip_from_engine_run(model, tmp_path):
    module, params = model
    prom = tmp_path / "metrics.prom"
    telemetry = TelemetryExporter(TelemetryConfig(
        interval_s=0.0, prometheus_path=str(prom)))
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(8,), prefix_cache=True,
                           paged_kv=PagedKVConfig(block_tokens=8),
                           telemetry=telemetry)
    for p in _prompts(3, [6, 7, 6]):
        engine.submit(Request(prompt=p, params=SamplingParams(
            max_new_tokens=3, temperature=0.0)))
    while engine.has_work:
        engine.step()
    telemetry.sample(engine)
    text = prom.read_text()
    assert text == telemetry.prometheus_text()
    parsed = parse_prometheus_text(text)
    assert parsed  # not empty
    for name, value in parsed.items():
        assert name.startswith("accelerate_tpu_")
        base, _, label = name.partition("{")
        assert all(c.isalnum() or c == "_" for c in base)
        if label:  # histogram series carry a {le="..."} label block
            assert base.endswith("_bucket") and 'le="' in label
        assert math.isfinite(value)
    assert (parsed[prometheus_name("serving/mem/slot_pool_bytes")]
            == tree_nbytes(engine._cache))
    assert (parsed[prometheus_name("serving/mem/block_pool/pool_bytes")]
            == tree_nbytes(engine._cache))
    telemetry.close()


def test_jsonl_time_series_byte_gauges_exact(model, tmp_path):
    module, params = model
    path = tmp_path / "telemetry.jsonl"
    telemetry = TelemetryExporter(TelemetryConfig(
        interval_s=0.0, jsonl_path=str(path)))
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(8,), prefix_cache=True,
                           paged_kv=PagedKVConfig(block_tokens=8),
                           telemetry=telemetry)
    for p in _prompts(5, [6, 7]):
        engine.submit(Request(prompt=p, params=SamplingParams(
            max_new_tokens=3, temperature=0.0)))
    while engine.has_work:
        engine.step()
    telemetry.close()
    raw = path.read_text()
    assert "NaN" not in raw and "Infinity" not in raw
    lines = [json.loads(line) for line in raw.splitlines()]
    assert len(lines) == len(telemetry.points())
    for point in lines:
        assert "_ts" in point and "_step" in point  # JSONLTracker conventions
        assert (point["serving/mem/slot_pool_bytes"]
                == tree_nbytes(engine._cache))
        assert (point["serving/mem/block_pool/pool_bytes"]
                == tree_nbytes(engine._cache))


def test_http_metrics_endpoint(tmp_path):
    telemetry = TelemetryExporter(TelemetryConfig(interval_s=0.0))
    telemetry.sample(_stub_engine({"serving/x": 2.5, "serving/y": 3}))
    port = telemetry.serve_http(0)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    assert parse_prometheus_text(body) == parse_prometheus_text(
        telemetry.prometheus_text())
    assert parse_prometheus_text(body)[prometheus_name("serving/x")] == 2.5
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/other", timeout=10)
    telemetry.close()


# ------------------------------------------------------------ non-finite guard
def test_non_finite_gauges_never_escape():
    nan, inf = float("nan"), float("inf")
    assert sanitize_scalars({"a": nan, "b": inf, "c": 1.5, "d": "s"}) == {
        "a": None, "b": None, "c": 1.5, "d": "s"}
    text = to_prometheus_text({"serving/bad": nan, "serving/worse": -inf,
                               "serving/good": 2.0})
    parsed = parse_prometheus_text(text)
    assert list(parsed) == [prometheus_name("serving/good")]
    # end to end: a poisoned snapshot serializes as null, never raw NaN
    telemetry = TelemetryExporter(TelemetryConfig(interval_s=0.0))
    point = telemetry.sample(_stub_engine({"serving/bad": inf}))
    assert point["serving/bad"] is None
    assert "Infinity" not in json.dumps(point)


def test_jsonl_tracker_guards_non_finite(tmp_path, monkeypatch):
    from accelerate_tpu.tracking import JSONLTracker

    # trackers consult PartialState(); shield from launcher-contract env vars
    # other tests may leak, which would route into jax.distributed init
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "ACCELERATE_TPU_NUM_PROCESSES", "JAX_PROCESS_ID"):
        monkeypatch.delenv(k, raising=False)
    tracker = JSONLTracker("run", logging_dir=str(tmp_path))
    tracker.log({"ok": 1.0, "bad": float("nan"), "worse": float("-inf")},
                step=3)
    tracker.finish()
    raw = (tmp_path / "run.metrics.jsonl").read_text()
    assert "NaN" not in raw and "Infinity" not in raw
    entry = json.loads(raw.splitlines()[-1])
    assert entry["ok"] == 1.0 and entry["_step"] == 3
    assert entry["bad"] is None and entry["worse"] is None


# ------------------------------------------------------------ exporter basics
def test_ring_bounded_and_cadence_gated():
    t = [0.0]
    telemetry = TelemetryExporter(
        TelemetryConfig(interval_s=1.0, capacity=4), clock=lambda: t[0])
    stub = _stub_engine()
    assert telemetry.poll(stub) is not None  # first poll always samples
    assert telemetry.poll(stub) is None      # cadence-gated
    t[0] = 0.5
    assert telemetry.poll(stub) is None
    t[0] = 1.0
    assert telemetry.poll(stub) is not None
    for _ in range(10):
        telemetry.sample(stub)               # sample ignores the cadence
    assert len(telemetry.points()) == 4      # ring capped
    assert telemetry.dropped == 8            # 12 samples, 4 kept
    assert telemetry.latest()["_step"] == 7  # stamped from metrics.steps


def test_null_telemetry_default_is_inert(model):
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(8,))
    assert engine.telemetry is NULL_TELEMETRY
    assert not engine.telemetry.enabled
    assert NULL_TELEMETRY.poll(engine) is None
    assert NULL_TELEMETRY.sample(engine) is None
    NULL_TELEMETRY.close()  # no-op, never raises


def test_exporter_samples_real_metrics_without_engine_extras():
    """Duck-typing floor: a bare ServingMetrics-carrying object (no
    memory_stats / capacity_headroom) still samples cleanly."""
    telemetry = TelemetryExporter(TelemetryConfig(interval_s=0.0))
    point = telemetry.sample(SimpleNamespace(metrics=ServingMetrics()))
    assert point["serving/requests_submitted"] == 0
    assert not any(k.startswith("serving/mem/") for k in point)
