"""Quantized serving (`docs/serving.md` "Quantized serving"): int8 paged KV
pools with sibling fp32 absmax scale planes, and engine ``weight_quant=``
packed int8/nf4 weights consumed directly by the jitted programs.

The contract is per-mode: fp32/bf16 paths stay bit-for-bit untouched (the
existing parity matrices are the regression net — nothing here re-proves
them), while every quantized mode must be bit-identical to the SAME mode's
solo ``generate`` across depth x admit x {gather, fused} x spec, crash-exact
through journal resume and hibernate/wake, and within a per-mode tolerance
of the dense model (the solo-generate tolerance oracle). Byte accounting is
exact: pool + scale leaves sum to ``nbytes``, and packed weight bytes are
what `utils.quantization.quantized_nbytes` says they are.

The multi-second parity drives (full matrix, crash resume, hibernate/wake,
weight-mode serving) are ``slow``-marked like the repo's other heavy
matrices; the tier-1 lane keeps the byte accounting, mode validation,
telemetry namespace, and tolerance oracles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = [pytest.mark.serving, pytest.mark.quant]

from accelerate_tpu.models import kv_cache
from accelerate_tpu.models.generation import generate
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.parallel.sharding import infer_cache_shardings, kv_cache_sharding
from accelerate_tpu.serving import (
    PagedKVConfig,
    Request,
    SamplingParams,
    ServingEngine,
)
from accelerate_tpu.serving.engine import WeightQuantConfig
from accelerate_tpu.serving.kv_tier import KVTierConfig
from accelerate_tpu.serving.telemetry import QUANT_GAUGES, TelemetryExporter
from accelerate_tpu.utils.quantization import (
    QuantizedModule,
    dequantize_params,
    quantize_params,
    quantized_nbytes,
)

BT = 16  # GPT2Config.tiny has n_positions=128 -> 8 blocks per slot at 16


@pytest.fixture(scope="module")
def model8():
    """fp32 compute over an int8 KV cache — the KV-quant mode under test."""
    cfg = GPT2Config.tiny(dtype=jnp.float32, kv_cache_dtype=jnp.int8)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    return module, params


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
    """Alternate greedy and seeded-sampling params across the prompt list."""
    return [
        Request(list(p), SamplingParams(
            max_new_tokens=n_tokens,
            temperature=0.9 if i % 2 else 0.0,
            top_k=5 if i % 2 else None,
            seed=100 + i,
        ))
        for i, p in enumerate(prompts)
    ]


def _refs(module, params, reqs):
    return {
        i: _solo(module, params, r.prompt, r.params.max_new_tokens,
                 temperature=r.params.temperature, top_k=r.params.top_k,
                 seed=r.params.seed)
        for i, r in enumerate(reqs)
    }


def _drive(engine, outputs):
    while engine.has_work:
        for out in engine.step():
            outputs[out.request_id] = out
    return outputs


def _quantize(module, params, mode):
    """The engine's exact load-time quantization, reproduced for the solo
    oracle: same `WeightQuantConfig` -> same `QuantizationConfig` -> the
    same packed tree, bit for bit."""
    wq = WeightQuantConfig(mode=mode)
    qp = quantize_params(params, wq.quantization_config(
        module.config.param_dtype))
    return wq, qp


# ------------------------------------------------- int8 paged KV: parity
@pytest.mark.slow
@pytest.mark.paged
@pytest.mark.parametrize("attn", ["gather", "fused"])
@pytest.mark.parametrize("spec", [None, 2])
def test_paged_int8_parity_matrix(model8, attn, spec):
    """Paged int8 KV serving is bit-identical to the solo int8-cache
    generate — same blockwise absmax at the same positions, through the
    per-block scale planes, on both decode attention paths, under
    speculation — across the depth x admit matrix (jits shared across
    cells, so the matrix costs compiles once)."""
    module, params = model8
    prompts = _prompts(11, (5, 9, 17, 26, 7, 13))
    reqs = _mixed_requests(prompts, 12)
    refs = _refs(module, params, reqs)
    for depth in (1, 2):
        for admit in (1, 4):
            engine = ServingEngine(
                module, params, max_concurrency=4,
                prompt_buckets=(16, 32), pipeline_depth=depth,
                admit_batch=admit, paged_kv=PagedKVConfig(block_tokens=BT),
                paged_attention=attn, speculation=spec,
            )
            outs = engine.run([Request(list(r.prompt), r.params)
                               for r in reqs])
            got = {o.request_id: o.tokens for o in outs}
            assert got == refs, (depth, admit)
            mem = engine.memory_stats()
            assert (mem["block_pool/blocks_free"]
                    + mem["block_pool/blocks_resident"]
                    + mem["block_pool/blocks_private"]
                    == mem["block_pool/blocks_total"])


@pytest.mark.paged
def test_paged_int8_byte_accounting(model8, model):
    """Exact nbytes math: the int8 pool's payload + fp32 scale planes +
    int32 cursors sum to the cache tree's bytes, the split matches the
    closed-form layout, and KV bytes land well under half the fp32 pool."""
    module, params = model8
    fp_module, fp_params = model
    kw = dict(max_concurrency=4, prompt_buckets=(16,),
              paged_kv=PagedKVConfig(block_tokens=BT))
    eng8 = ServingEngine(module, params, **kw)
    engfp = ServingEngine(fp_module, fp_params, **kw)

    cfg = module.config
    n_blocks = eng8._allocator.num_blocks
    kv_heads, head_dim = cfg.n_head, cfg.n_embd // cfg.n_head
    payload = cfg.n_layer * 2 * n_blocks * BT * kv_heads * head_dim  # int8
    scales = cfg.n_layer * 2 * n_blocks * BT * kv_heads * 4          # fp32

    mem = eng8.memory_stats()
    qs = eng8.quant_stats()
    assert qs["kv_bits"] == 8
    assert qs["kv_payload_bytes"] == payload
    assert qs["kv_scale_bytes"] == scales
    # the per-dtype split partitions the pool exactly — nothing uncounted
    split = {k.rsplit("/", 1)[-1]: v for k, v in mem.items()
             if k.startswith("slot_pool_bytes/")}
    assert sum(split.values()) == mem["slot_pool_bytes"]
    assert split["int8"] == payload and split["float32"] == scales
    # capacity win: int8 payload + scales vs the same pool at fp32
    fp_kv = engfp.quant_stats()
    assert fp_kv == {}  # fp engines export NO quant gauges
    fp_bytes = engfp.memory_stats()["slot_pool_bytes"]
    assert (payload + scales) / fp_bytes <= 0.55


# ------------------------------------------------ weight quant: parity
@pytest.mark.slow
@pytest.mark.parametrize("mode", ["int8", "nf4"])
def test_weight_quant_serving_parity(model, mode):
    """Serving over packed weights is bit-identical to the quantized solo
    generate (`QuantizedModule` + the same packed tree), and the packed
    bytes the engine reports are exactly `quantized_nbytes`."""
    module, params = model
    wq, qp = _quantize(module, params, mode)
    prompts = _prompts(13, (4, 9, 15, 6))
    reqs = _mixed_requests(prompts, 10)
    refs = _refs(QuantizedModule(module), qp, reqs)
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(16,), weight_quant=wq)
    outs = engine.run([Request(list(r.prompt), r.params) for r in reqs])
    assert {o.request_id: o.tokens for o in outs} == refs
    qs = engine.quant_stats()
    assert qs["weight_bits"] == (8 if mode == "int8" else 4)
    assert qs["weight_packed_bytes"] == quantized_nbytes(engine.params)
    assert qs["weight_packed_bytes"] < qs["weight_dense_bytes"]
    assert (qs["weight_saved_bytes"]
            == qs["weight_dense_bytes"] - qs["weight_packed_bytes"])


# tolerances are for the RANDOM tiny net (near-noise weights are nf4's
# worst case — no outlier structure for the normal-quantile codebook to
# exploit); trained checkpoints land far tighter
@pytest.mark.parametrize("mode,tol", [("int8", 0.05), ("nf4", 0.5)])
def test_weight_quant_tolerance_oracle(model, mode, tol):
    """The per-mode tolerance contract against the DENSE model: quantized
    logits track fp32 logits within the mode's error budget on a full
    prompt forward. Token streams are compared against the quantized solo
    oracle elsewhere — this bounds how far quantization itself drifts."""
    module, params = model
    _, qp = _quantize(module, params, mode)
    ids = jnp.asarray(_prompts(17, (24,))[0], jnp.int32)[None, :]
    dense = module.apply({"params": params}, ids)
    quant = QuantizedModule(module).apply({"params": qp}, ids)
    rel = float(jnp.max(jnp.abs(quant - dense)) / jnp.max(jnp.abs(dense)))
    assert rel <= tol, f"{mode} drifted {rel:.4f} > {tol}"


def test_weight_quant_mode_validation(model):
    module, params = model
    with pytest.raises(ValueError, match="int8.*nf4|nf4.*int8"):
        ServingEngine(module, params, weight_quant="fp8",
                      max_concurrency=2, prompt_buckets=(16,))
    # the string shorthand resolves to the default config for the mode
    eng = ServingEngine(module, params, weight_quant="int8",
                        max_concurrency=2, prompt_buckets=(16,))
    assert eng.weight_quant == WeightQuantConfig(mode="int8")


# ------------------------------------ combined modes + telemetry surface
@pytest.mark.slow
def test_combined_int8_kv_and_weights_parity(model8):
    """Both levers at once — int8 paged pool (fused attention) under packed
    int8 weights — still bit-identical to the equally-quantized solo."""
    module, params = model8
    wq, qp = _quantize(module, params, "int8")
    prompts = _prompts(19, (5, 12, 21))
    reqs = _mixed_requests(prompts, 10)
    refs = _refs(QuantizedModule(module), qp, reqs)
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(32,), weight_quant=wq,
                           paged_kv=PagedKVConfig(block_tokens=BT),
                           paged_attention="fused")
    outs = engine.run([Request(list(r.prompt), r.params) for r in reqs])
    assert {o.request_id: o.tokens for o in outs} == refs


def test_quant_gauges_ride_their_own_namespace(model8, model):
    """Telemetry lifts the engine's ``quant/`` group to ``serving/quant/``
    (the documented family, `telemetry.QUANT_GAUGES`); an fp engine's point
    carries none of them."""
    module, params = model8
    eng8 = ServingEngine(module, params, max_concurrency=2,
                         prompt_buckets=(16,), weight_quant="int8",
                         paged_kv=PagedKVConfig(block_tokens=BT))
    point = TelemetryExporter(interval_s=0.0).sample(eng8)
    present = {k for k in point if k.startswith("serving/quant/")}
    assert present == set(QUANT_GAUGES)
    assert not any(k.startswith("serving/mem/quant/") for k in point)

    fp_module, fp_params = model
    engfp = ServingEngine(fp_module, fp_params, max_concurrency=2,
                          prompt_buckets=(16,))
    fp_point = TelemetryExporter(interval_s=0.0).sample(engfp)
    assert not any(k.startswith("serving/quant/") for k in fp_point)


# --------------------------------------------- crash-exact resume / wake
@pytest.mark.slow
@pytest.mark.recovery
@pytest.mark.paged
def test_quant_resume_from_journal_crash_exact(model8, tmp_path):
    """Journal kill-and-resume with int8 paged KV + packed int8 weights:
    the fresh engine re-quantizes at the same positions (prompt + replayed
    tokens are all that survive), so every stream stays bit-identical to
    the quantized solo oracle."""
    module, params = model8
    wq, qp = _quantize(module, params, "int8")
    jpath = tmp_path / "requests.journal"

    def build():
        return ServingEngine(module, params, max_concurrency=2,
                             prompt_buckets=(16, 32), pipeline_depth=2,
                             paged_kv=PagedKVConfig(block_tokens=BT),
                             weight_quant=wq, journal=jpath)

    reqs = _mixed_requests(_prompts(23, (5, 9, 14, 7)), 12)
    refs = _refs(QuantizedModule(module), qp, reqs)
    a = build()
    for r in reqs:
        assert a.submit(Request(list(r.prompt), r.params)).accepted
    pre = {}
    for _ in range(6):
        for out in a.step():
            pre[out.request_id] = out
    del a  # simulated SIGKILL: the fsync'd journal is all that survives

    b = build()
    report = b.resume()
    assert report.resumed, "at least one request must resume MID-stream"
    final = dict(report.completed)
    final.update(pre)
    _drive(b, final)
    assert {rid: o.tokens for rid, o in final.items()} == refs


@pytest.mark.slow
@pytest.mark.tier
@pytest.mark.paged
def test_quant_hibernate_wake_parity(model8):
    """Forced hibernation mid-decode over an int8 pool: the host tier
    spills int8 payload + scale planes (block bytes at the quantized size,
    not fp32), and woken streams finish bit-identical to solo."""
    module, params = model8
    cfg = module.config
    engine = ServingEngine(
        module, params, max_concurrency=2, prompt_buckets=(16,),
        paged_kv=PagedKVConfig(block_tokens=BT),
        kv_tier=KVTierConfig(min_resident_slots=1),
    )
    kv_heads, head_dim = cfg.n_head, cfg.n_embd // cfg.n_head
    expect_block = cfg.n_layer * 2 * (BT * kv_heads * head_dim      # int8
                                      + BT * kv_heads * 4)          # scales
    assert engine.kv_tier.block_bytes == expect_block
    assert expect_block < cfg.n_layer * 2 * BT * kv_heads * head_dim * 4 / 2

    reqs = _mixed_requests(_prompts(29, (6, 11)), 14)
    refs = _refs(module, params, reqs)
    for r in reqs:
        assert engine.submit(Request(list(r.prompt), r.params)).accepted
    outs, forced = {}, False
    while engine.has_work:
        for o in engine.step():
            outs[o.request_id] = o
        if not forced:
            ready = [int(s) for s in np.flatnonzero(engine._active)
                     if engine._slot_out[s] is not None
                     and len(engine._slot_out[s].tokens) >= 2]
            if ready:
                for s in ready:
                    engine.kv_tier.hibernate_slot(s)
                forced = True
    assert forced, "hibernation was never forced — the scenario proves nothing"
    assert {rid: o.tokens for rid, o in outs.items()} == refs


# --- fast primitive/config units (no engine, tier-1 lane) -------------------


def test_q_roundtrip_error_bound_and_shapes():
    x = jax.random.normal(jax.random.key(3), (4, 16, 2, 32), jnp.float32)
    q, scale = kv_cache._q(x)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert q.shape == x.shape and scale.shape == x.shape[:-1]
    # absmax/127 quantization error is at most half a step per element
    err = np.abs(np.asarray(kv_cache._dq(q, scale, jnp.float32)) - np.asarray(x))
    assert (err <= np.asarray(scale)[..., None] / 2 + 1e-7).all()


def test_q_zero_rows_stay_exact():
    x = jnp.zeros((2, 8, 4), jnp.float32)
    q, scale = kv_cache._q(x)
    assert (np.asarray(scale) == 1.0 / 127.0).all()
    assert (np.asarray(kv_cache._dq(q, scale, jnp.float32)) == 0.0).all()


def test_q_extremes_hit_full_range_and_negate_symmetrically():
    x = jnp.array([[1.0, -2.0, 0.5, 2.0]], jnp.float32)
    q, scale = kv_cache._q(x)
    qn, scale_n = kv_cache._q(-x)
    assert np.asarray(q).max() == 127 and np.asarray(qn).min() == -127
    assert (np.asarray(q) == -np.asarray(qn)).all()
    assert (np.asarray(scale) == np.asarray(scale_n)).all()


def test_dq_casts_to_compute_dtype():
    q, scale = kv_cache._q(jax.random.normal(jax.random.key(0), (3, 4)))
    assert kv_cache._dq(q, scale, jnp.bfloat16).dtype == jnp.bfloat16
    assert kv_cache._dq(q, scale, jnp.float32).dtype == jnp.float32


def test_weight_quant_config_maps_to_quantization_config():
    int8 = WeightQuantConfig(mode="int8").quantization_config(jnp.float32)
    assert int8.load_in_8bit and not int8.load_in_4bit
    nf4 = WeightQuantConfig(mode="nf4", block_size=32).quantization_config(
        jnp.bfloat16)
    assert nf4.load_in_4bit and nf4.quant_type == "nf4"
    assert nf4.block_size == 32 and nf4.compute_dtype == jnp.bfloat16


def test_quant_gauges_list_matches_quant_stats_surface():
    # the lint (tools/check_metrics_docs.py) trusts this static tuple to BE
    # the quant_stats key surface — keep them in lockstep
    expected = {f"serving/quant/{k}" for k in (
        "weight_bits", "weight_packed_bytes", "weight_dense_bytes",
        "weight_saved_bytes", "kv_bits", "kv_payload_bytes",
        "kv_scale_bytes")}
    assert set(QUANT_GAUGES) == expected


def test_quantized_nbytes_shrinks_and_dequantizes_back(model):
    module, params = model
    qcfg = WeightQuantConfig(mode="int8").quantization_config(jnp.float32)
    qparams = quantize_params(params, qcfg)
    assert quantized_nbytes(qparams) < quantized_nbytes(params)
    dense = dequantize_params(qparams, jnp.float32)
    chex_shapes = jax.tree.map(lambda a, b: a.shape == b.shape, dense, params)
    assert all(jax.tree.leaves(chex_shapes))


def test_scale_planes_get_pool_shardings():
    from jax.sharding import Mesh, PartitionSpec

    mesh = Mesh(np.array(jax.devices("cpu")[:1]).reshape(1, 1),
                ("data", "tensor"))
    pool = {"cached_key": jnp.zeros((4, 8, 2 * 4), jnp.int8),  # payload, heads folded
            "key_scale": jnp.zeros((4, 8, 2)),                # scale plane
            "cache_index": jnp.zeros((2,), jnp.int32)}
    rules = kv_cache_sharding(mesh, slots=2, paged=True)
    shardings = infer_cache_shardings(pool, rules)
    assert shardings["cached_key"] is rules.kv
    # scale planes ride the same (blocks, tokens, heads) rule, told by name
    assert shardings["key_scale"] is rules.scale
    assert shardings["key_scale"].spec == PartitionSpec(None, None, None)
    assert shardings["cache_index"] is rules.index
