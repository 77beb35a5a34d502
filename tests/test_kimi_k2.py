"""Kimi K2 behind the serving engine, against the plain reference
(`benchmarks/chip/reference/kimi_k2.py`: float32, the un-absorbed form for
every position, no cache; it imports nothing of the program): the layers, the
absorbed form against the plain one, the fused kernel over a latent pool
against a gather oracle, the sigmoid router and YaRN against numbers worked by
hand, prefill in a padded bucket then decode through the paged latent pool
against the reference's full forward pass (logits, not tokens), the
expert-parallel share, the parameter count of the cut configuration, and what
the engine does with a latent leaf. CPU, tiny widths, seeded weights."""

import dataclasses
import math
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import flops_kimi_k2 as F  # noqa: E402
import harness  # noqa: E402
import weights_kimi_k2 as W  # noqa: E402
from reference import kimi_k2 as ref  # noqa: E402

from accelerate_tpu.models.kimi_k2 import (  # noqa: E402
    DenseMLP,
    KimiK2Config,
    KimiK2ForCausalLM,
    LatentAttention,
    SigmoidMoE,
    by_token_chunks,
    yarn_inv_freq,
    yarn_rope,
)
from accelerate_tpu.models.kv_cache import LATENT_LEAF, leaf_name, tree_nbytes  # noqa: E402
from accelerate_tpu.ops import moe as moe_ops  # noqa: E402
from accelerate_tpu.ops.flash_attention import (  # noqa: E402
    paged_decode_attention,
    paged_decode_vmem_bytes,
)
from accelerate_tpu.ops.moe import (  # noqa: E402
    held_experts_mlp,
    route_sigmoid_top_k,
    shared_expert_mlp,
)
from accelerate_tpu.serving import PagedKVConfig, Request, SamplingParams, ServingEngine  # noqa: E402

pytestmark = pytest.mark.serving
SEED = 7
# float32 both sides, "highest" matmuls: sums in another order, and the
# absorbed form multiplies by W_uk before the latent where the plain form
# multiplies after; bfloat16 anywhere reads a thousand times this
TOL = 3e-5
CONFIG = "kimi-k2.7-code.json"


@pytest.fixture(scope="module")
def cfg():
    """The benchmark configuration's rehearsal sizes: every width tiny, a dense
    layer and two expert layers, the router 16 wide over 8 held experts, float32."""
    return harness.overlay(harness.load_json("configs", CONFIG), True)


@pytest.fixture(scope="module")
def model_cfg(cfg):
    return W.model_config(cfg)


@pytest.fixture(scope="module")
def params(cfg):
    return W.make_program(SEED, cfg, jnp.float32)


@pytest.fixture(scope="module")
def ref_params(cfg):
    return W.make_reference(SEED, cfg, jnp.float32)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def hidden(cfg, shape, key=0):
    return jax.random.normal(jax.random.key(key), shape + (cfg["hidden_size"],), jnp.float32)


def positions_of(b, t):
    return jnp.broadcast_to(jnp.arange(t)[None], (b, t))


# ------------------------------------------------------------------ the layers
def test_latent_attention_plain_form_matches_reference(cfg, model_cfg, params, ref_params):
    x = hidden(cfg, (2, 24))
    got = LatentAttention(model_cfg).apply({"params": params["layer_1"]["attn"]}, x, positions_of(2, 24))
    want = ref.latent_attention(ref_params["layers"][1], x, cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert float(jnp.abs(want).max()) > 1e-2


def test_dense_layer_mlp_matches_reference(cfg, model_cfg, params, ref_params):
    x, p = hidden(cfg, (2, 9), key=3), ref_params["layers"][0]
    got = DenseMLP(model_cfg).apply({"params": params["layer_0"]["mlp"]}, x)
    np.testing.assert_allclose(got, ref.swiglu(x, p["wg"], p["wu"], p["wd"]), atol=TOL, rtol=TOL)


def test_expert_layer_matches_reference_on_its_share(cfg, model_cfg, params, ref_params):
    x = hidden(cfg, (2, 12))
    got = SigmoidMoE(model_cfg).apply({"params": params["layer_1"]["moe"]}, x)
    want = ref.moe(ref_params["layers"][1], x, cfg, held=W.held_experts(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    whole = ref.moe(ref_params["layers"][1], x, cfg, held=W.held_experts(cfg), shared=False)
    assert float(jnp.abs(whole).max()) > 1e-3  # the held picks are not nothing


def test_whole_model_matches_reference(cfg, model_cfg, params, ref_params):
    ids = jax.random.randint(jax.random.key(1), (2, 21), 0, cfg["vocab_size"])
    got = KimiK2ForCausalLM(model_cfg).apply({"params": params}, ids)
    want = ref.forward(ref_params, ids, cfg, held=W.held_experts(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("tokens", [2048, 3072])
def test_ffn_by_token_chunks_equals_the_whole(monkeypatch, cfg, model_cfg, params, tokens):
    """An admit program's long segment runs its shared expert a chunk of
    tokens at a time and its routed experts over the whole segment, in
    windows of held picks where they outnumber one (forced here: 128 picks a
    window); the result and the picks held are those of 512-token pieces."""
    x = hidden(cfg, (1, tokens), key=tokens)
    moe = SigmoidMoE(model_cfg)
    pieces = [moe.apply({"params": params["layer_2"]["moe"]}, x[:, at: at + 512], mutable=["counters"])
              for at in range(0, tokens, 512)]
    e, f, item = model_cfg.hidden_size, model_cfg.moe_intermediate_size, jnp.dtype(model_cfg.dtype).itemsize
    monkeypatch.setattr(moe_ops, "EXPERT_PASS_BYTES", 128 * (e * item + 2 * f * 4 + f * item + e * 4))
    assert moe_ops.expert_pass_rows(e, f, model_cfg.dtype) == 128
    before = moe_ops.GROUPED_PRODUCT_TRACES.copy()
    got, counted = moe.apply({"params": params["layer_2"]["moe"]}, x, mutable=["counters"])
    assert (moe_ops.GROUPED_PRODUCT_TRACES - before)["window", 128] == 1
    np.testing.assert_allclose(got, jnp.concatenate([p[0] for p in pieces], 1), atol=TOL, rtol=TOL)
    assert int(counted["counters"]["moe_picks_held"]) == sum(
        int(p[1]["counters"]["moe_picks_held"]) for p in pieces)
    out, n = by_token_chunks(lambda xt: (xt * 2, jnp.int32(1)), x[0])
    assert int(n) == tokens // (1536 if tokens % 1536 == 0 else 1024)
    np.testing.assert_array_equal(out, x[0] * 2)
    out, = by_token_chunks(lambda xt, yt: (xt + yt,), x[0], x[0] * 2)
    np.testing.assert_array_equal(out, x[0] * 3)


# ----------------------------------------------------- absorbed equals plain
@pytest.mark.parametrize("segment", [1, 5])
def test_absorbed_form_on_cached_rows_equals_the_plain_form(cfg, model_cfg, params, segment):
    """A prefill from an empty cache (plain form) writes the latent rows; a
    segment on top of them (a decode step, a verify segment, a suffix prefill)
    runs the absorbed form through the cache and gives the logits the plain
    form gives for the whole sequence at once."""
    module = KimiK2ForCausalLM(dataclasses.replace(model_cfg, kv_cache_per_slot=True))
    ids = jax.random.randint(jax.random.key(2), (2, 19 + segment), 0, cfg["vocab_size"])
    want = module.apply({"params": params}, ids)
    cache = module.init(jax.random.key(0), ids[:, :1], decode=True)["cache"]
    first, mut = module.apply({"params": params, "cache": cache}, ids[:, :19], decode=True,
                              position_offset=0, mutable=["cache"])
    np.testing.assert_allclose(first, want[:, :19], atol=TOL, rtol=TOL)
    got, mut = module.apply({"params": params, "cache": mut["cache"]}, ids[:, 19:], decode=True,
                            position_offset=jnp.asarray([19, 19]), mutable=["cache"])
    np.testing.assert_allclose(got, want[:, 19:], atol=TOL, rtol=TOL)
    leaves = {leaf_name(path) for path, _ in jax.tree_util.tree_flatten_with_path(mut["cache"])[0]}
    assert leaves == {LATENT_LEAF, "cache_index"}


# -------------------------------------------------- the kernel, latent pool
def latent_case(dtype, block_tokens, rows=5, heads=4, lanes=128, value_dim=96, blocks_per_row=6):
    rng = np.random.default_rng(3)
    blocks = rows * blocks_per_row
    span = blocks_per_row * block_tokens
    pool = jnp.asarray(rng.standard_normal((blocks, block_tokens, lanes)), dtype)
    q = jnp.asarray(rng.standard_normal((rows, heads, lanes)), dtype)
    # one position, just past a block edge, mid-block, a whole span, an empty row
    lengths = np.array([1, block_tokens + 1, 2 * block_tokens + 3, span, 0], np.int32)[:rows]
    tables = rng.permutation(blocks).astype(np.int32).reshape(rows, blocks_per_row)
    for row, n in enumerate(lengths):
        tables[row, -(-int(n) // block_tokens):] = blocks  # the released-slot sentinel
    return q, pool, jnp.asarray(tables), jnp.asarray(lengths), value_dim


def latent_oracle(q, pool, tables, lengths, value_dim, scale):
    blocks, bt, lanes = pool.shape
    rows = pool.astype(jnp.float32)[jnp.minimum(tables, blocks - 1)].reshape(q.shape[0], -1, lanes)
    scores = jnp.einsum("bhl,btl->bht", q.astype(jnp.float32), rows) * scale
    live = jnp.arange(rows.shape[1])[None, None, :] < lengths[:, None, None]
    weights = jax.nn.softmax(jnp.where(live, scores, -1e30), -1) * live
    return jnp.einsum("bht,btv->bhv", weights, rows[..., :value_dim])


@pytest.mark.parametrize("block_tokens", [8, 16])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_fused_kernel_on_a_latent_pool_equals_the_gather_oracle(dtype, tol, block_tokens):
    """All heads against one shared row a token, the value its leading lanes,
    no value pool; table entries at the released-slot sentinel past a row's
    frontier; a stale NaN past the frontier does not reach the output."""
    q, pool, tables, lengths, value_dim = latent_case(dtype, block_tokens)
    stale = int(tables[1, 1])  # row 1 holds block_tokens + 1 positions: poison the rest of its block
    pool = pool.at[stale, 2:].set(jnp.nan)
    got = paged_decode_attention(q, pool, None, tables, lengths, value_dim=value_dim, scale=0.2)
    clean = jnp.where(jnp.isnan(pool.astype(jnp.float32)), 0.0, pool.astype(jnp.float32))
    want = latent_oracle(q, clean, tables, lengths, value_dim, 0.2)
    assert got.shape == (q.shape[0], q.shape[1], value_dim) and got.dtype == q.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    live = np.asarray(lengths) > 0  # an empty row reads 0 / 1, not 0 / 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[live], np.asarray(want)[live], atol=tol, rtol=tol)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[~live], 0.0)


@pytest.mark.parametrize("fault", ["value_dim_with_value_pool", "no_value_dim", "value_dim_too_wide",
                                   "scale_planes", "row_narrower_than_query"])
def test_latent_pool_arguments_are_checked(fault):
    q, pool, tables, lengths, value_dim = latent_case(jnp.float32, 8)
    kw = dict(value_dim=value_dim)
    v_pool = None
    if fault == "value_dim_with_value_pool":
        v_pool = pool
    elif fault == "no_value_dim":
        kw = {}
    elif fault == "value_dim_too_wide":
        kw = dict(value_dim=pool.shape[-1] + 1)
    elif fault == "scale_planes":
        planes = jnp.ones(pool.shape[:2] + (1,), jnp.float32)
        kw.update(k_scale_pool=planes, v_scale_pool=planes)
    else:
        pool = pool[..., :64]
    with pytest.raises(ValueError, match="latent|value_dim"):
        paged_decode_attention(q, pool, v_pool, tables, lengths, **kw)


def test_vmem_counts_one_chunk_buffer_for_a_latent_pool():
    """One pool's two chunk buffers and float32 working copy, at the latent
    pool's 512 tokens a chunk; a key/value pool of the same row has two pools
    of 256-token chunks: the same bytes."""
    kw = dict(q_heads=64, itemsize=2, block_tokens=64)
    latent = paged_decode_vmem_bytes(1, 640, value_dim=512, **kw)
    fixed = paged_decode_vmem_bytes(1, 640, **kw) - 2 * 256 * 640 * (2 * 2 + 4)
    assert latent - fixed == 512 * 640 * (2 * 2 + 4)
    assert latent < 16 << 20


# ---------------------------------------------------------- router, by hand
def test_selection_bias_changes_which_experts_are_chosen_and_not_their_weights():
    """One token, four experts, two chosen. Scores sigmoid(logits) = 0.6, 0.5,
    0.4, 0.3. Without a bias experts 0 and 1 are chosen with weights 2 * 0.6 /
    1.1 and 2 * 0.5 / 1.1. A bias of +0.15 on expert 2 lifts it over expert 1:
    experts 0 and 2 are chosen, and their weights are their own scores'
    (0.6 and 0.4, not 0.55), renormalised: 2 * 0.6 / 1.0 and 2 * 0.4 / 1.0."""
    scores = np.array([0.6, 0.5, 0.4, 0.3])
    logits = np.log(scores / (1 - scores))
    x, router = jnp.ones((1, 1)), jnp.asarray(logits[None, :], jnp.float32)
    w, idx = route_sigmoid_top_k(x, router, jnp.zeros(4), 2, scaling=2.0)
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(w, [[1.2 / 1.1, 1.0 / 1.1]], rtol=1e-6)
    w, idx = route_sigmoid_top_k(x, router, jnp.asarray([0.0, 0.0, 0.15, 0.0]), 2, scaling=2.0)
    assert idx.tolist() == [[0, 2]]
    np.testing.assert_allclose(w, [[1.2, 0.8]], rtol=1e-6)


def test_router_matches_reference(cfg, ref_params):
    p, x = ref_params["layers"][1], hidden(cfg, (40,), key=5)
    w, idx = route_sigmoid_top_k(x, p["router"], p["bias"], int(cfg["num_experts_per_tok"]),
                                 float(cfg["routed_scaling_factor"]))
    want_w, want_idx = ref.route(p, x, cfg)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    unbiased = route_sigmoid_top_k(x, p["router"], jnp.zeros_like(p["bias"]),
                                   int(cfg["num_experts_per_tok"]))[1]
    changed = float((jnp.sort(unbiased, -1) != jnp.sort(idx, -1)).any(-1).mean())
    assert 0.0 < changed < 1.0  # the seeded bias flips some tokens' choice and not all


def test_shared_expert_without_a_gate_is_the_plain_mlp(cfg, ref_params):
    p, x = ref_params["layers"][1], hidden(cfg, (7,), key=6)
    got = shared_expert_mlp(x, None, jnp.concatenate([p["s_wg"], p["s_wu"]], -1), p["s_wd"])
    np.testing.assert_allclose(got, ref.swiglu(x, p["s_wg"], p["s_wu"], p["s_wd"]), atol=TOL, rtol=TOL)


# ------------------------------------------------------------ YaRN, by hand
def test_yarn_frequencies_and_scale_at_the_published_numbers():
    """d = 64, theta = 50,000, L0 = 4,096, factor 64, beta 32 and 1:
    corr(32) = 64 ln(4096 / (64 pi)) / (2 ln 50000) = 8.91 -> low 8;
    corr(1) = 64 ln(4096 / (2 pi)) / (2 ln 50000) = 19.16 -> high 20;
    so pairs 0-8 keep f_i, pairs 20-31 turn 64 times slower, pair 14 is half
    way: f_14 (0.5 / 64 + 0.5). m(64, 1) = 0.1 ln 64 + 1 = 1.41589;
    scale = 192^(-1/2) * 1.41589^2 = 0.144680."""
    cfg = KimiK2Config()
    assert math.floor(64 * math.log(4096 / (64 * math.pi)) / (2 * math.log(50000))) == 8
    assert math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000))) == 20
    f = 50000.0 ** (-np.arange(32) / 32.0)
    want = np.where(np.arange(32) <= 8, f, np.where(np.arange(32) >= 20, f / 64,
                                                    f * ((np.arange(32) - 8) / 12 / 64 + 1 - (np.arange(32) - 8) / 12)))
    got = np.asarray(yarn_inv_freq(cfg))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[14], f[14] * (0.5 / 64 + 0.5), rtol=1e-6)
    assert abs(cfg.softmax_scale - 0.144680) < 1e-6
    assert abs(cfg.softmax_scale - 192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2) < 1e-12
    assert cfg.latent_width == 576 and cfg.latent_row_lanes == 640


def test_rotary_matches_reference_at_a_rows_own_offset(cfg, model_cfg):
    x = jax.random.normal(jax.random.key(0), (1, 9, 2, cfg["qk_rope_head_dim"]), jnp.float32)
    got = yarn_rope(x, jnp.arange(9)[None], model_cfg)
    np.testing.assert_allclose(got, ref.rotary(x, cfg), atol=1e-6)
    np.testing.assert_allclose(yarn_rope(x[:, 4:5], jnp.asarray([[4]]), model_cfg), got[:, 4:5], atol=1e-6)
    np.testing.assert_allclose(yarn_inv_freq(model_cfg), ref.yarn_inv_freq(cfg), rtol=1e-7)
    assert abs(model_cfg.softmax_scale - ref.softmax_scale(cfg)) < 1e-12


# ------------------------------------------------------------- the share test
def test_four_expert_shares_and_the_shared_expert_once_make_the_uncut_layer(cfg, ref_params):
    """16 routed experts as 4 expert-parallel shares of 4: every chip computes
    its routed part from the one router, the shared expert is counted once,
    and the sum is the uncut reference layer."""
    width, shares = F.router_width(cfg), 4
    held = width // shares
    whole_cfg = dict(cfg, n_routed_experts=width, published={"n_routed_experts": width})
    whole = W.upcast(W.make_layer(SEED, whole_cfg, 1, jnp.float32))  # all 16 experts, one router
    x = hidden(cfg, (3, 10), key=7).reshape(30, -1)
    k = int(cfg["num_experts_per_tok"])
    weights, idx = route_sigmoid_top_k(x, whole["router"], whole["bias"], k,
                                       float(cfg["routed_scaling_factor"]))
    total, picks = 0.0, 0
    for first in range(0, width, held):
        part = slice(first, first + held)
        gate_up = jnp.concatenate([whole["wg"][part], whole["wu"][part]], -1)
        out, n, touched = held_experts_mlp(x, weights, idx, gate_up, whole["wd"][part], first)
        assert 0 < int(touched) <= held
        total, picks = total + out, picks + int(n)
        alone = ref.moe({**whole, "wg": whole["wg"][part], "wu": whole["wu"][part], "wd": whole["wd"][part]},
                        x, cfg, held=(first, held), shared=False)
        np.testing.assert_allclose(out, alone, atol=TOL, rtol=TOL)  # one share is the reference given that share
    assert picks == 30 * k  # no token dropped, every pick held exactly once
    total = total + shared_expert_mlp(x, None, jnp.concatenate([whole["s_wg"], whole["s_wu"]], -1), whole["s_wd"])
    want = ref.moe(whole, x, cfg)  # held=None: the uncut layer
    np.testing.assert_allclose(total, want, atol=TOL, rtol=TOL)


# ------------------------------------------------------- the cut configuration
def test_cut_configuration_holds_3497_million_parameters():
    """The issue's table: attention 101.1 M a layer, the dense MLP 396.4 M, an
    expert 44.04 M, the router 2.75 M, a dense layer 497.5 M, an expert layer
    with 12 held experts 676.4 M, embedding and head 293.6 M: 3,497 M in all,
    6.99 GB; and the program's own tree holds exactly that many."""
    cfg = harness.load_json("configs", CONFIG)
    m = 1e6
    assert round(F.attention_matmul_params(cfg) / m, 1) == 101.1
    assert round(F.dense_mlp_params(cfg) / m, 1) == 396.4
    assert round(F.expert_params(cfg) / m, 2) == 44.04
    assert round(cfg["hidden_size"] * F.router_width(cfg) / m, 2) == 2.75
    assert round(F.layer_params(cfg, True) / m, 1) == 497.5
    assert round(F.layer_params(cfg, False) / m, 1) == 676.4
    assert round(2 * cfg["vocab_size"] * cfg["hidden_size"] / m, 1) == 293.6
    total = F.total_params(cfg)
    print(f"kimi-k2.7-code, chip 0 of stage 0: {total:,} parameters, {F.param_bytes(cfg) / 1e9:.3f} GB")
    assert round(total / m) == 3497 and round(F.param_bytes(cfg) / 1e9, 2) == 7.02
    module = KimiK2ForCausalLM(W.model_config(cfg))
    shapes = jax.eval_shape(lambda: module.init(jax.random.key(0), jnp.zeros((1, 2), jnp.int32)))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) == total
    assert sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree.leaves(shapes)) \
        == F.param_bytes(cfg)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 61, "n_routed_experts": 384, "vocab_size": 163840}
    assert cfg["deployment"]["chips_sharing_a_layer"] * int(cfg["n_routed_experts"]) == 384


# ------------------------------------------------------------------ the engine
class Probe(nn.Module):
    """The model with its logits handed to the test as they are computed."""

    config: KimiK2Config
    seen = []

    @nn.compact
    def __call__(self, input_ids, **kw):
        logits = KimiK2ForCausalLM(self.config, name="lm")(input_ids, **kw)
        jax.debug.callback(lambda x: Probe.seen.append(np.asarray(x)), logits, ordered=True)
        return logits


def engine_for(module, tree, **kw):
    args = dict(max_concurrency=2, prompt_buckets=(32, 64), paged_kv=True, paged_attention="fused",
                admit_batch=2, eos_token_id=None)
    args.update(kw)
    return ServingEngine(module, tree, **args)


def serve(engine, prompts, new_tokens):
    budgets = new_tokens if isinstance(new_tokens, (list, tuple)) else [new_tokens] * len(prompts)
    ids = [engine.submit(Request(prompt=p, params=SamplingParams(temperature=0.0, max_new_tokens=n))).request_id
           for p, n in zip(prompts, budgets)]
    outs = {}
    while engine.has_work:
        for out in engine.step():
            outs[out.request_id] = out
    return [outs[i] for i in ids]


def prompts_of(cfg, lengths, key=0):
    rng = np.random.default_rng(key)
    return [rng.integers(0, cfg["vocab_size"], n).tolist() for n in lengths]


@pytest.mark.parametrize("paged_attention", ["fused", "gather"])
def test_prefill_in_a_bucket_then_decode_gives_the_reference_logits(cfg, model_cfg, params, ref_params,
                                                                   paged_attention):
    """Two requests of unequal length admitted together in one padded bucket
    (plain form), then 16 decode turns through the paged latent pool (absorbed
    form); the shorter row crosses a block edge (11 -> 27 positions, blocks of
    16) and the longer one two (29 -> 45)."""
    Probe.seen.clear()
    engine = engine_for(Probe(model_cfg), {"lm": params}, paged_attention=paged_attention, pipeline_depth=1)
    prompts = prompts_of(cfg, (11, 29))
    outs = serve(engine, prompts, 17)
    jax.effects_barrier()
    admit, steps = Probe.seen[0], Probe.seen[1:]
    assert admit.shape[:2] == (2, 32) and len(steps) >= 16 and all(s.shape[:2] == (2, 1) for s in steps)
    for row, (prompt, out) in enumerate(zip(prompts, outs)):
        assert len(out.tokens) == 17
        full = jnp.asarray([prompt + out.tokens])
        want = np.asarray(ref.forward(ref_params, full, cfg, held=W.held_experts(cfg))[0])
        p = len(prompt)
        np.testing.assert_allclose(admit[row, p - 1], want[p - 1], atol=TOL, rtol=TOL)
        for turn in range(16):  # turn t is fed token t and sits at position p + t
            np.testing.assert_allclose(steps[turn][row, 0], want[p + turn], atol=TOL, rtol=TOL)
        assert out.tokens == [int(t) for t in want[p - 1: p + 16].argmax(-1)]


def test_cache_tree_holds_one_latent_leaf_a_layer_and_no_value_pool(cfg, model_cfg, params):
    engine = engine_for(KimiK2ForCausalLM(model_cfg), params, paged_kv=PagedKVConfig(num_blocks=40))
    flat = jax.tree_util.tree_flatten_with_path(engine._cache)[0]
    names = sorted(leaf_name(path) for path, _ in flat)
    layers = model_cfg.num_hidden_layers
    assert names == sorted([LATENT_LEAF, "cache_index"] * layers)
    lanes = model_cfg.latent_row_lanes
    assert lanes % 128 == 0 and lanes >= model_cfg.latent_width
    for path, leaf in flat:
        if leaf_name(path) == LATENT_LEAF:
            assert leaf.shape == (40, 16, lanes)
    serve(engine, prompts_of(cfg, (10, 12)), 5)
    stats = engine.memory_stats()
    assert stats["block_pool/pool_bytes"] == tree_nbytes(engine._cache) == layers * (40 * 16 * lanes * 4 + 2 * 4)
    assert "slot_state_bytes" not in stats or stats["slot_state_bytes"] == 0
    # what the fused kernel read: the rows' live positions out of their tables' span
    snapshot = engine.metrics.snapshot()
    assert 0 < snapshot["serving/paged_decode/live_tokens"] < snapshot["serving/paged_decode/span_tokens"]
    pad = np.asarray(engine._cache["layer_0"]["attn"][LATENT_LEAF])[..., model_cfg.latent_width:]
    np.testing.assert_array_equal(pad, 0.0)  # the stored pad lanes hold zeros


def test_step_counters_count_the_held_picks(cfg, model_cfg, params):
    engine = engine_for(KimiK2ForCausalLM(model_cfg), params)
    serve(engine, prompts_of(cfg, (10, 12)), 5)
    counters, steps = engine.metrics.step_counters, engine.metrics.counted_steps.value
    assert set(counters) == {"moe_picks_held", "moe_experts_touched"} and steps >= 4
    expert_layers = model_cfg.num_hidden_layers - model_cfg.first_k_dense_replace
    k = model_cfg.num_experts_per_tok
    assert 0 < counters["moe_experts_touched"] <= steps * expert_layers * model_cfg.experts_held
    assert counters["moe_experts_touched"] <= counters["moe_picks_held"] <= steps * expert_layers * 2 * k


@pytest.mark.parametrize("argument", [{"prefix_cache": True}, {"speculation": 2}, {"kv_tier": True},
                                      {"tokens_per_sync": 3}])
def test_what_addresses_the_cache_by_position_works_on_a_latent_leaf(cfg, model_cfg, params, argument):
    """A latent row is a function of its token's position alone: a suffix
    prefill on aliased prefix blocks, a verify segment with its rollback, the
    tier's spill and a scan of steps answer as the plain engine does."""
    module = KimiK2ForCausalLM(model_cfg)
    shared = prompts_of(cfg, (24,), key=4)[0]
    prompts = [shared + tail for tail in prompts_of(cfg, (5, 9, 3), key=5)]
    plain = [serve(engine_for(module, params), [p], 9)[0].tokens for p in prompts]
    engine = engine_for(module, params, paged_kv=PagedKVConfig(block_tokens=8), **argument)
    got = [serve(engine, [p], 9)[0].tokens for p in prompts]  # one after another: the later ones hit the trie
    assert got == plain
    if "prefix_cache" in argument:
        assert engine.metrics.snapshot()["serving/prefix_hits"] >= 2


def test_mesh_is_refused_for_a_latent_leaf(model_cfg, params):
    with pytest.raises(ValueError, match="latent cache leaf.*mesh is not supported"):
        engine_for(KimiK2ForCausalLM(model_cfg), params, mesh=(1, 1))


def test_int8_pool_is_refused_for_a_latent_leaf(model_cfg, params):
    module = KimiK2ForCausalLM(dataclasses.replace(model_cfg, kv_cache_dtype=jnp.int8))
    with pytest.raises(ValueError, match="int8 is not supported for a latent cache leaf"):
        engine_for(module, params)


def test_kimi_k2_contract(model_cfg):
    contract = model_cfg.cache_contract()
    assert contract.kv_heads == 1 and contract.head_dim == model_cfg.latent_row_lanes
    assert contract.value_dim == model_cfg.kv_lora_rank and contract.state_leaves == ()
    assert contract.step_counters == ("moe_picks_held", "moe_experts_touched")
    tiny = KimiK2Config.tiny(experts_held=4)
    assert tiny.is_dense(0) and not tiny.is_dense(1) and tiny.num_hidden_layers == 3
    assert tiny.n_routed_experts > tiny.experts_held
