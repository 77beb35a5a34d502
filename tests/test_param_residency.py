"""Parameter residency (`docs/serving.md` "Parameter residency"): the engine
holds in the compute dtype every leaf the model's cache contract declares it
consumes only through a cast to that dtype (`CacheContract.
compute_dtype_params`), cast once at construction.

Held to: the same programs answer with the narrowed tree exactly as with the
float32 one (tokens, cache, logits); a jaxpr oracle that every use of a
declared leaf, in the decode step and an admit program, is a cast to the
compute dtype, and every use of a LayerNorm leaf computes at float32; and the
paths that must not change (quantized weights, an fp8 recipe, a compute dtype
no narrower than the params; a contract that declares nothing is
`tests/test_qwen3_next.py`'s) hand the engine's programs the tree as before.
The byte gauges read what the narrowing saved. CPU, tiny widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.models.kv_cache import tree_nbytes
from accelerate_tpu.ops.fp8 import DelayedScalingRecipe
from accelerate_tpu.serving import Request, SamplingParams, ServingEngine
from accelerate_tpu.serving.engine import WeightQuantConfig
from accelerate_tpu.utils.quantization import quantize_params

pytestmark = pytest.mark.serving

LAYER_NORMS = ("ln_1", "ln_2", "ln_f")
PROMPTS = ([3, 17, 42, 9, 11], list(range(1, 20)), [7] * 12)


def _names(path) -> tuple[str, ...]:
    return tuple(str(getattr(k, "key", k)) for k in path)


def _is_norm(path) -> bool:
    return any(n in LAYER_NORMS for n in _names(path))


@pytest.fixture(scope="module")
def module():
    return GPT2LMHead(GPT2Config.tiny())


@pytest.fixture(scope="module")
def params(module):
    """Initialised, then every LayerNorm leaf moved off the values bfloat16
    holds exactly (ones and zeros), so that narrowing one would show."""
    tree = module.init_params(jax.random.key(0))
    noise = iter(jax.random.split(jax.random.key(1), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 0.1 * jax.random.normal(next(noise), leaf.shape)
        if _is_norm(path) else leaf, tree)


def _engine(module, params, **kw):
    args = dict(max_concurrency=2, prompt_buckets=(8, 32), paged_kv=True,
                paged_attention="fused", admit_batch=2, eos_token_id=None)
    args.update(kw)
    return ServingEngine(module, params, **args)


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _recorded(engine, prompts, new_tokens=6):
    """Serve ``prompts`` greedy and keep every dispatch: the program, its
    arguments and what it returned, copied (later programs donate them)."""
    calls, dispatch = [], engine._dispatch

    def spy(key, fn, *args):
        kept = (_copy(args[0]), args[1]) + _copy(args[2:])
        out = dispatch(key, fn, *args)
        calls.append((key, fn, kept, _copy(out)))
        return out

    engine._dispatch = spy
    ids = [engine.submit(Request(prompt=p, params=SamplingParams(
        temperature=0.0, max_new_tokens=new_tokens))).request_id for p in prompts]
    outs = {}
    while engine.has_work:
        for out in engine.step():
            outs[out.request_id] = out
    return calls, [outs[i].tokens for i in ids]


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))


def test_declared_leaves_are_held_narrow_and_norms_float32(module, params):
    engine = _engine(module, params)
    for (path, held), given in zip(jax.tree_util.tree_leaves_with_path(engine.params),
                                   jax.tree.leaves(params), strict=True):
        assert given.dtype == jnp.float32
        if _is_norm(path):
            assert held is given, _names(path)
        else:
            assert held.dtype == jnp.bfloat16, _names(path)
            np.testing.assert_array_equal(np.asarray(held), np.asarray(given.astype(jnp.bfloat16)))


def test_narrowed_programs_answer_as_float32_ones(module, params):
    """Every admit and decode dispatch of a greedy serve, replayed with the
    float32 tree: the same first tokens, decode tokens, cache and per-slot
    state. Then the decode module's and the admit module's logits on the
    recorded inputs. Asserted bit for bit: each product reads the same
    bfloat16 operand values, whichever tree it was handed."""
    engine = _engine(module, params)
    calls, _ = _recorded(engine, PROMPTS)
    kinds = {key.partition("[")[0].partition("@")[0] for key, *_ in calls}
    assert {"admit", "step"} <= kinds, kinds
    for key, fn, args, out in calls:
        assert args[1] is engine.params
        _assert_same(out, fn(_copy(args[0]), params, *args[2:]))

    key, fn, args, _ = next(c for c in calls if c[0].startswith("step"))
    cache, _, tokens, pos, *_, finished, _, _, _, tables = args

    def decode_logits(p):
        return engine.module.apply(
            {"params": p, "cache": cache}, tokens[:, None], decode=True,
            position_offset=pos, mutable=["cache"], cache_write_mask=~finished,
            block_tables=tables)[0]

    def admit_logits(p):
        fresh = jax.tree.map(lambda s: jnp.zeros((2,) + s.shape[1:], s.dtype),
                             engine._fresh_shapes)
        rows = jnp.asarray([PROMPTS[0] + [0] * 3, PROMPTS[2][:8]], jnp.int32)
        return engine._admit_module.apply(
            {"params": p, "cache": fresh}, rows, decode=True, position_offset=0,
            mutable=["cache"])[0]

    for logits in (decode_logits, admit_logits):
        narrow, wide = jax.jit(logits)(engine.params), jax.jit(logits)(params)
        assert narrow.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))


# ------------------------------------------------------------ the jaxpr oracle
def _inner(eqn, pos):
    """The variables the sub-jaxprs of ``eqn`` that take its operands one for
    one (a nested jit, a custom derivative, a scan) bind its ``pos``-th operand
    to, or None where ``eqn`` reads it itself (so does a loop or branch that
    binds them otherwise, and the checks below refuse it)."""
    subs = []
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else (value,):
            sub = getattr(sub, "jaxpr", sub)
            if isinstance(sub, jcore.Jaxpr) and len(sub.invars) == len(eqn.invars):
                subs.append((sub, sub.invars[pos]))
    return subs or None


def _uses(jaxpr, var) -> list:
    """Every equation that reads ``var``, followed into the sub-jaxprs it is
    handed to; a variable a sub-jaxpr returns as it came counts as a use
    by its own equation (None), which no check accepts."""
    found = [None] if any(v is var for v in jaxpr.outvars) else []
    for eqn in jaxpr.eqns:
        for pos, v in enumerate(eqn.invars):
            if v is not var:
                continue
            inner = _inner(eqn, pos)
            if inner is None:
                found.append(eqn)
            else:
                for sub, sub_var in inner:
                    found += _uses(sub, sub_var)
    return found


@pytest.mark.parametrize("kind", ["step", "admit"])
def test_declared_leaves_reach_products_only_through_a_cast(module, params, kind):
    """Traced with the float32 tree, the program the engine dispatches reads
    each declared leaf only through `convert_element_type` to the compute
    dtype, and each LayerNorm leaf at float32. A later edit that consumes a
    declared leaf at full precision fails here."""
    engine = _engine(module, params)
    calls, _ = _recorded(engine, PROMPTS[:2], new_tokens=3)
    key, fn, args, _ = next(c for c in calls if c[0].startswith(kind))
    closed = jax.make_jaxpr(lambda p: fn(_copy(args[0]), p, *args[2:]))(params)
    paths = [path for path, _ in jax.tree_util.tree_leaves_with_path(params)]
    assert len(paths) == len(closed.jaxpr.invars)
    declared = module.config.cache_contract().compute_dtype_params
    for path, var in zip(paths, closed.jaxpr.invars):
        uses = _uses(closed.jaxpr, var)
        assert uses, f"{kind}: {_names(path)} is never read"
        if declared(_names(path)):
            assert not _is_norm(path)
            for eqn in uses:
                assert (eqn is not None and eqn.primitive.name == "convert_element_type"
                        and eqn.params["new_dtype"] == module.config.dtype), (
                    f"{kind}: declared leaf {_names(path)} read by {eqn}")
        else:
            assert _is_norm(path)
            for eqn in uses:
                assert eqn is not None and all(
                    o.aval.dtype == jnp.float32 for o in eqn.outvars), (
                    f"{kind}: LayerNorm leaf {_names(path)} read by {eqn}")


# ------------------------------------------------- the paths that must not move
def test_fp8_recipe_declares_nothing(params):
    cfg = GPT2Config.tiny(fp8_recipe=DelayedScalingRecipe())
    assert cfg.cache_contract().compute_dtype_params is None
    engine = ServingEngine(GPT2LMHead(cfg), params, max_concurrency=2, prompt_buckets=(8,))
    assert all(a is b for a, b in zip(jax.tree.leaves(engine.params), jax.tree.leaves(params)))
    assert engine.memory_stats()["param_narrowed_saved_bytes"] == 0


def test_compute_dtype_no_narrower_than_params_keeps_them(params):
    """A float32 compute dtype: no declared leaf is wider than it."""
    engine = ServingEngine(GPT2LMHead(GPT2Config.tiny(dtype=jnp.float32)), params,
                           max_concurrency=2, prompt_buckets=(8,))
    assert all(a is b for a, b in zip(jax.tree.leaves(engine.params), jax.tree.leaves(params)))
    stats = engine.memory_stats()
    assert stats["param_narrowed_saved_bytes"] == 0
    assert stats["param_bytes"] == tree_nbytes(params)


@pytest.mark.parametrize("mode", ["int8", "nf4"])
def test_weight_quant_quantizes_the_float32_tree(module, params, mode):
    """Quantized serving packs the tree it is given, float32, and narrows
    nothing: leaves it leaves dense stay float32."""
    engine = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(8,),
                           weight_quant=mode)
    want = quantize_params(params, WeightQuantConfig(mode=mode).quantization_config(jnp.float32))
    _assert_same(engine.params, want)
    assert {leaf.dtype for leaf in jax.tree.leaves(engine.params)
            if jnp.issubdtype(leaf.dtype, jnp.floating)} == {jnp.dtype(jnp.float32)}
    stats = engine.memory_stats()
    assert stats["param_narrowed_saved_bytes"] == 0
    assert stats["param_bytes"] == stats["quant/weight_packed_bytes"]


def test_param_gauges_read_the_saved_bytes(module, params):
    engine = _engine(module, params)
    stats = engine.memory_stats()
    declared = sum(leaf.nbytes for path, leaf in jax.tree_util.tree_leaves_with_path(params)
                   if not _is_norm(path))
    assert stats["param_narrowed_saved_bytes"] == declared // 2 > 0
    assert stats["param_bytes"] == tree_nbytes(engine.params) == (
        tree_nbytes(params) - stats["param_narrowed_saved_bytes"])
