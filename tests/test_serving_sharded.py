"""Mesh-sharded serving: token parity with the single-device engine, sharding
inference over the full GPT-2 tree, and the engine's mesh validation.

The load-bearing contract is BIT-FOR-BIT parity: ``ServingEngine(mesh=(d, m))``
must emit exactly the tokens ``mesh=None`` emits for the same requests — TP
shards the math, never the values (fp32 on CPU makes the comparison exact; the
conftest's force_cpu_platform(8) provides the virtual devices). Every test here
is tier-1: lean traces, the module-scoped tiny model, one baseline run shared
across all mesh shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("flax.linen")

pytestmark = [pytest.mark.serving, pytest.mark.sharded]

from jax.sharding import Mesh, NamedSharding, PartitionSpec

from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead, gpt2_sharding_rules
from accelerate_tpu.parallel.mesh import serving_mesh
from accelerate_tpu.parallel.sharding import (
    infer_cache_shardings,
    infer_param_shardings,
    kv_cache_sharding,
)
from accelerate_tpu.serving import Request, SamplingParams, ServingEngine

P = PartitionSpec

if len(jax.devices()) < 4:  # pragma: no cover - conftest forces 8
    pytest.skip("sharded serving tests need >= 4 devices", allow_module_level=True)


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    return module, params


def _prompts(seed, lengths, vocab=256):
    r = np.random.default_rng(seed)
    return [r.integers(0, vocab, (n,)).astype(np.int32).tolist() for n in lengths]


def _requests(prompts, n_new=8, greedy=True):
    return [
        Request(prompt=list(p),
                params=SamplingParams(
                    max_new_tokens=n_new,
                    temperature=0.0 if greedy else 0.8,
                    top_k=None if greedy else 20,
                    seed=i,
                ))
        for i, p in enumerate(prompts)
    ]


def _serve(module, params, reqs, mesh=None, **kw):
    kw.setdefault("pipeline_depth", 2)
    kw.setdefault("admit_batch", 4)
    engine = ServingEngine(module, params, max_concurrency=4,
                           prompt_buckets=(8, 32), mesh=mesh, **kw)
    outs = engine.run(reqs)
    return {o.request_id: (tuple(o.tokens), o.finish_reason) for o in outs}, engine


@pytest.fixture(scope="module")
def baseline(model):
    """mesh=None reference outputs, computed once for every shape below.
    Greedy decoding: argmax is stable under the ~1e-7 ULP logit shifts the TP
    all-reduce's reduction reordering introduces, so bit-for-bit parity is the
    right bar here; the seeded-SAMPLING parity bar is split by mesh axis below
    (exact for pure DP, per-shape deterministic for TP)."""
    module, params = model
    prompts = _prompts(0, (5, 12, 20, 9, 3, 17))
    out, _ = _serve(module, params, _requests(prompts))
    return prompts, out


# ------------------------------------------------------------------ token parity
@pytest.mark.parametrize("shape", [(1, 1), (2, 1), (1, 2), (2, 2)])
def test_mesh_token_parity(model, baseline, shape):
    """Every (data, model) shape — pure DP, pure TP, and both — reproduces the
    single-device token streams exactly, finish reasons included."""
    module, params = model
    prompts, expect = baseline
    got, engine = _serve(module, params, _requests(prompts), mesh=shape)
    assert got == expect
    assert engine.mesh_shape == shape
    # compile telemetry carries the mesh tag for every jitted program hit
    tag = f"@mesh{shape[0]}x{shape[1]}"
    assert engine.metrics.compile_count.value >= 2  # step + >=1 admit bucket
    assert all(k.endswith(tag) for k in engine.metrics.compiles)


def test_mesh_parity_synchronous_single_admit(model, baseline):
    """depth=1 / admit_batch=1: the non-pipelined, unbatched path is just as
    mesh-oblivious (different jit programs, same tokens)."""
    module, params = model
    prompts, expect = baseline
    got, _ = _serve(module, params, _requests(prompts), mesh=(2, 2),
                    pipeline_depth=1, admit_batch=1)
    assert got == expect


def test_mesh_sampling_parity_and_determinism(model):
    """Seeded sampling, split by what the mesh does to the arithmetic:

    - pure DP (2, 1) only re-tiles the slot dim — every per-row reduction is
      unchanged, so sampled streams match mesh=None BIT-FOR-BIT;
    - TP (2, 2) all-reduces partial matmuls, which reorders fp32 sums (~1e-7
      logit shifts — measured, not hypothetical), so a gumbel near-tie can
      legitimately flip. The guarantee there is DETERMINISM: the same mesh
      shape replays the same seeds to the same tokens, every time."""
    module, params = model
    prompts = _prompts(3, (5, 9, 3))
    reqs = lambda: _requests(prompts, n_new=6, greedy=False)  # noqa: E731
    base, _ = _serve(module, params, reqs())
    dp, _ = _serve(module, params, reqs(), mesh=(2, 1))
    assert dp == base
    # determinism: TWO replays through one sharded engine (request ids differ
    # across runs, so compare the ordered streams, not the id-keyed dicts)
    engine = ServingEngine(module, params, max_concurrency=4,
                           prompt_buckets=(8, 32), pipeline_depth=2,
                           admit_batch=4, mesh=(2, 2))
    tp_a = [(tuple(o.tokens), o.finish_reason) for o in engine.run(reqs())]
    tp_b = [(tuple(o.tokens), o.finish_reason) for o in engine.run(reqs())]
    assert tp_a == tp_b
    # sanity: every request still terminates cleanly under TP sampling
    assert all(reason == "length" for _, reason in tp_a)


def test_mesh_parity_with_prefix_cache(model):
    """Two waves sharing a long prefix through one engine: wave 1 donates at
    retirement, wave 2 admits through the CACHED path (block-pool gather) —
    the sharded cached-admission program must land in the compile telemetry
    AND stay token-identical to the unsharded cached engine."""
    module, params = model
    r = np.random.default_rng(7)
    shared = r.integers(0, 256, (24,)).astype(np.int32).tolist()
    waves = [
        [shared + r.integers(0, 256, (k,)).astype(np.int32).tolist()
         for k in (3, 5, 4)]
        for _ in range(2)
    ]

    def serve_waves(mesh):
        engine = ServingEngine(module, params, max_concurrency=4,
                               prompt_buckets=(8, 32), pipeline_depth=2,
                               admit_batch=4, prefix_cache=True, mesh=mesh)
        out = {}
        for wave in waves:
            for o in engine.run(_requests(wave, n_new=6)):
                out[len(out)] = (tuple(o.tokens), o.finish_reason)
        return out, engine

    base, _ = serve_waves(None)
    got, engine = serve_waves((2, 2))
    assert got == base
    assert engine.metrics.prefix_hits.value >= 3  # wave 2 hit the pool
    assert any(k.startswith("cached_admit[") for k in engine.metrics.compiles)


# ----------------------------------------------------------- sharding inference
def test_infer_param_shardings_full_gpt2_tree(model):
    """Megatron TP rules over the whole tiny GPT-2 tree: qkv/up column-split,
    proj/down row-split, embeddings vocab-split, and every scalar/1-D leaf the
    rules don't fit comes out REPLICATED (never an error, never sharded)."""
    _, params = model
    mesh = serving_mesh(data=2, model=2)
    shardings = infer_param_shardings(params, mesh, rules=gpt2_sharding_rules())

    flat = {
        "/".join(str(getattr(k, "key", k)) for k in path): s
        for path, s in jax.tree_util.tree_flatten_with_path(shardings)[0]
    }
    specs = {name: s.spec for name, s in flat.items()}

    def spec_of(substr, ndim=None):
        hits = [s for n, s in specs.items() if substr in n]
        assert hits, f"no param path contains {substr!r}"
        return hits

    for s in spec_of("qkv/kernel"):
        assert s == P(None, "tensor")
    for s in spec_of("proj/kernel"):
        assert s == P("tensor", None)
    for s in spec_of("up/kernel"):
        assert s == P(None, "tensor")
    for s in spec_of("down/kernel"):
        assert s == P("tensor", None)
    for s in spec_of("qkv/bias"):
        assert s == P("tensor")
    # every unmatched leaf — layernorm scales/biases, proj/down biases,
    # position embeddings — must be explicitly replicated
    leaves = {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    for name, leaf in leaves.items():
        spec = specs[name]
        if leaf.ndim <= 1 and not any(
            t in name for t in ("qkv/bias", "up/bias", "wte")
        ):
            assert spec == P() or all(p is None for p in spec), (name, spec)
    # the plan must be placeable as-is: every leaf device_puts cleanly
    jax.block_until_ready(jax.tree.map(jax.device_put, params, shardings))


def test_infer_param_shardings_degrades_not_raises(model):
    """`_sanitize_spec` repairs instead of erroring: a mesh missing the axes a
    rule names drops them; a rule whose rank exceeds the leaf's replicates; an
    indivisible dim replicates."""
    _, params = model
    # hand-built 2-device mesh with ONLY (data, tensor): the wte rule names
    # ("tensor", "fsdp") — the missing fsdp axis must be dropped, not raise
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("data", "tensor"))
    shardings = infer_param_shardings(params, mesh, rules=gpt2_sharding_rules())
    flat = {
        "/".join(str(getattr(k, "key", k)) for k in path): s.spec
        for path, s in jax.tree_util.tree_flatten_with_path(shardings)[0]
    }
    wte = next(s for n, s in flat.items() if "wte" in n)
    assert wte == P("tensor", None) or wte == P(("tensor",), None)

    # rank overflow: a 2-D rule hitting a scalar leaf -> replicated
    from accelerate_tpu.parallel.sharding import ShardingRules, _sanitize_spec

    assert _sanitize_spec(P(None, "tensor"), (), mesh) == P()
    # indivisible dim: tiny n_embd=64 is divisible, so probe with a prime
    assert _sanitize_spec(P("tensor", None), (7, 64), mesh) == P(None, None)
    # rules whose axes are entirely absent -> fully replicated plan
    odd = ShardingRules(rules=[(r".*kernel", P(None, "nonexistent_axis"))])
    sh = infer_param_shardings(params, mesh, rules=odd, shard_params_on_fsdp=False)
    assert all(
        all(p is None for p in s.spec)
        for s in jax.tree_util.tree_leaves(sh)
    )


def test_kv_cache_sharding_slot_and_head_rules():
    """Slot dim shards on "data" only when the slot count divides the degree;
    heads shard on "tensor"; the fresh-rows variant (slots=None) never shards
    the slot dim (the block pool's rules: the next test)."""
    mesh = serving_mesh(data=2, model=2)
    s4 = kv_cache_sharding(mesh, slots=4)
    assert s4.kv.spec == P(("data",), None, "tensor", None)
    assert s4.index.spec == P(("data",))
    s3 = kv_cache_sharding(mesh, slots=3)  # 3 % 2 != 0 -> replicated slots
    assert s3.kv.spec == P(None, None, "tensor", None)
    fresh = kv_cache_sharding(mesh, slots=None)
    assert fresh.kv.spec == P(None, None, "tensor", None)
    assert fresh.scale.spec == P(None, None, "tensor")

    cache = {
        "cached_key": jax.ShapeDtypeStruct((4, 16, 2, 8), jnp.float32),
        "key_scale": jax.ShapeDtypeStruct((4, 16, 2), jnp.float32),
        "cache_index": jax.ShapeDtypeStruct((4,), jnp.int32),
    }
    tree = infer_cache_shardings(cache, s4)
    assert tree["cached_key"].spec == s4.kv.spec
    assert tree["key_scale"].spec == s4.scale.spec
    assert tree["cache_index"].spec == s4.index.spec

    # TP degree 1: head axis drops out entirely
    s_dp = kv_cache_sharding(serving_mesh(data=4, model=1), slots=4)
    assert s_dp.kv.spec == P(("data",), None, None, None)


def test_paged_pool_sharding_splits_the_folded_head_dim():
    """The paged pool is ``[num_blocks, block_tokens, kv_heads * head_dim]``:
    blocks replicate, the folded last dim shards on "tensor" (whole heads),
    and a K/V leaf is told from a scale plane by its name, both being 3-dim."""
    mesh = serving_mesh(data=2, model=2)
    paged = kv_cache_sharding(mesh, slots=4, paged=True)
    assert paged.kv.spec == P(None, None, "tensor")
    assert paged.scale.spec == P(None, None, "tensor")
    assert paged.index.spec == P(("data",))
    assert paged.gathered.spec == P(("data",), None, "tensor", None)
    cache = {
        "cached_key": jax.ShapeDtypeStruct((12, 16, 2 * 8), jnp.int8),
        "cached_value": jax.ShapeDtypeStruct((12, 16, 2 * 8), jnp.int8),
        "key_scale": jax.ShapeDtypeStruct((12, 16, 2), jnp.float32),
        "value_scale": jax.ShapeDtypeStruct((12, 16, 2), jnp.float32),
        "cache_index": jax.ShapeDtypeStruct((4,), jnp.int32),
    }
    tree = infer_cache_shardings(cache, paged)
    assert tree["cached_key"] is paged.kv and tree["cached_value"] is paged.kv
    assert tree["key_scale"] is paged.scale and tree["value_scale"] is paged.scale
    assert tree["cache_index"] is paged.index
    # by name, not by rank: a slot cache's 4-dim K/V and 3-dim scale still part
    slot = kv_cache_sharding(mesh, slots=4)
    tree = infer_cache_shardings({
        "cached_value": jax.ShapeDtypeStruct((4, 16, 2, 8), jnp.int8),
        "value_scale": jax.ShapeDtypeStruct((4, 16, 2), jnp.float32),
    }, slot)
    assert tree["cached_value"] is slot.kv and tree["value_scale"] is slot.scale


# ------------------------------------------------------------------- validation
def test_engine_rejects_indivisible_heads(model):
    """tiny n_head=2 cannot split over a model axis of 4: loud ValueError at
    construction, never a silent wrong sharding."""
    module, params = model
    with pytest.raises(ValueError, match="n_head"):
        ServingEngine(module, params, max_concurrency=2, prompt_buckets=(8,),
                      mesh=(1, 4))


def test_engine_mesh_forms_equivalent(model):
    """The three ``mesh=`` spellings — (data, model) tuple, Mesh, and
    ParallelismConfig — resolve to the same shape."""
    from accelerate_tpu.parallel.mesh import ParallelismConfig

    module, params = model
    kw = dict(max_concurrency=2, prompt_buckets=(8,))
    e_tuple = ServingEngine(module, params, mesh=(1, 2), **kw)
    e_mesh = ServingEngine(module, params, mesh=serving_mesh(data=1, model=2), **kw)
    e_cfg = ServingEngine(
        module, params,
        mesh=ParallelismConfig(data_parallel_size=1, tensor_size=2), **kw)
    assert e_tuple.mesh_shape == e_mesh.mesh_shape == e_cfg.mesh_shape == (1, 2)
    with pytest.raises(ValueError, match="serving"):
        ServingEngine(module, params, mesh=ParallelismConfig(
            data_parallel_size=1, tensor_size=1, fsdp_size=2), **kw)
