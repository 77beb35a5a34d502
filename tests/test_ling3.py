"""Ling 3.0 flash behind the serving engine, against the plain reference
(`benchmarks/chip/reference/ling3.py`: float32, the delta rule token by token
under one scan, latent attention in the un-absorbed form at every position, no
cache; it imports nothing of the program): the layers, the per-channel delta
rule's chunked form against its step and against the scalar-decay rule, the
group-limited router, prefill of ragged prompts in one padded bucket then
decode through per-slot KDA state and the paged latent pool against the
reference's full forward pass (logits, not tokens), the expert-parallel share,
the parameter count of the cut configuration, and what the engine does with a
contract that has state leaves and a latent leaf. CPU, tiny widths, seeded
weights."""

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

import flops_ling3 as F  # noqa: E402
import harness  # noqa: E402
import weights_ling3 as W  # noqa: E402
from reference import ling3 as ref  # noqa: E402

from accelerate_tpu.models.kv_cache import LATENT_LEAF, leaf_name, state_nbytes, tree_nbytes  # noqa: E402
from accelerate_tpu.models.ling3 import (  # noqa: E402
    STATE_LEAVES,
    STEP_COUNTERS,
    GatedLatentAttention,
    GroupLimitedMoE,
    KimiDeltaAttention,
    Ling3Config,
    Ling3ForCausalLM,
)
from accelerate_tpu.ops.gated_delta import (  # noqa: E402
    gated_delta_prefill,
    gated_delta_step,
    kda_prefill,
)
from accelerate_tpu.ops.moe import (  # noqa: E402
    held_experts_mlp,
    route_sigmoid_top_k,
    shared_expert_mlp,
)
from accelerate_tpu.serving import Request, SamplingParams, ServingEngine  # noqa: E402

pytestmark = pytest.mark.serving
SEED = 7
# float32 both sides, "highest" matmuls: sums in another order (the chunked WY
# form against the recurrence, the absorbed form against the plain one);
# bfloat16 anywhere reads a thousand times this
TOL = 3e-5
CONFIG = "ling-3.0-flash-vl.json"


@pytest.fixture(scope="module")
def cfg():
    """The benchmark configuration's rehearsal sizes: every width tiny, one
    period of six layers (KDA x5, MLA; two dense), the router 32 wide in 4
    groups of which 2 are kept, 8 experts (group 0) held, float32."""
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
def test_kda_mixer_matches_reference(cfg, model_cfg, params, ref_params):
    x = hidden(cfg, (2, 45))  # 45 tokens: two chunks of 32, the second padded
    got = KimiDeltaAttention(model_cfg).apply({"params": params["layer_0"]["kda"]}, x)
    want = ref.kimi_delta_attention(ref_params["layers"][0], x, cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert float(jnp.abs(want).max()) > 1e-2
    mean = ref.kimi_delta_attention(ref_params["layers"][0], x, cfg, low="decay_mean")
    assert float(jnp.abs(mean - want).max()) > 100 * TOL  # the channels' decays differ, visibly


def test_gated_latent_attention_matches_reference(cfg, model_cfg, params, ref_params):
    x = hidden(cfg, (2, 24), key=1)
    got = GatedLatentAttention(model_cfg).apply({"params": params["layer_5"]["attn"]}, x, positions_of(2, 24))
    want = ref.latent_attention(ref_params["layers"][5], x, cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert float(jnp.abs(want).max()) > 1e-2


def test_expert_layer_matches_reference_on_its_share(cfg, model_cfg, params, ref_params):
    x = hidden(cfg, (2, 12), key=2)
    got, counted = GroupLimitedMoE(model_cfg).apply({"params": params["layer_2"]["moe"]}, x,
                                                    mutable=["counters"])
    want = ref.moe(ref_params["layers"][2], x, cfg, held=W.held_experts(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    routed = ref.moe(ref_params["layers"][2], x, cfg, held=W.held_experts(cfg), shared=False)
    assert float(jnp.abs(routed).max()) > 1e-3  # the held picks are not nothing
    c = {k: int(v) for k, v in counted["counters"].items()}
    assert set(c) == set(STEP_COUNTERS)
    # a token's picks on this chip come several at once or not at all
    assert 0 < c["moe_rows_routed_here"] < 24 and c["moe_picks_held"] > c["moe_rows_routed_here"]


def test_whole_model_matches_reference(cfg, model_cfg, params, ref_params):
    ids = jax.random.randint(jax.random.key(1), (2, 41), 0, cfg["vocab_size"])
    got = Ling3ForCausalLM(model_cfg).apply({"params": params}, ids)
    want = ref.forward(ref_params, ids, cfg, held=W.held_experts(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_layer_pattern_and_contract(model_cfg):
    assert [model_cfg.is_latent(i) for i in range(6)] == [False] * 5 + [True]
    assert [model_cfg.is_dense(i) for i in range(6)] == [True, True] + [False] * 4
    full = Ling3Config()
    assert sum(full.is_latent(i) for i in range(42)) == 7 and full.latent_row_lanes == 640
    assert abs(full.softmax_scale - 192 ** -0.5) < 1e-12
    contract = model_cfg.cache_contract()
    assert contract.kv_heads == 1 and contract.head_dim == model_cfg.latent_row_lanes
    assert contract.value_dim == model_cfg.kv_lora_rank and contract.state_leaves == STATE_LEAVES
    assert contract.step_counters == ("moe_picks_held", "moe_experts_touched", "moe_rows_routed_here")


@pytest.mark.parametrize("name", ["expert_swiglu_limit_list", "share_expert_swiglu_limit_list"])
def test_a_swiglu_limit_in_a_held_layer_is_refused(name):
    published = (0,) * 35 + (4,) * 7
    Ling3Config.tiny(**{name: published})  # layers 0-5 read 0: fine
    with pytest.raises(NotImplementedError, match=name):
        Ling3Config(**{name: published})  # all 42 layers: layer 35 has a limit


# ----------------------------------------------- the per-channel delta rule
def delta_inputs(t, g, b=2, h=3, dk=16, dv=8, key=0):
    ks = jax.random.split(jax.random.key(key), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q, k = unit(jax.random.normal(ks[0], (b, t, h, dk))), unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h)))
    if g == "random":  # the whole of the bounded gate's range, channel by channel
        g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (b, t, h, dk)))
    elif g == "near_zero":
        g = -1e-4 * jax.random.uniform(ks[4], (b, t, h, dk))
    elif g == "head_constant":
        g = jnp.broadcast_to(-jax.random.uniform(ks[4], (b, t, h, 1)), (b, t, h, dk))
    else:
        g = jnp.full((b, t, h, dk), float(g))
    return q, k, v, g, beta


def by_steps(q, k, v, g, beta):
    def step(S, xs):
        S, o = gated_delta_step(S, *xs)
        return S, o

    S0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), jnp.float32)
    S, o = jax.lax.scan(step, S0, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


@pytest.mark.parametrize("t, g, tol", [
    (1, "random", 1e-6), (15, "random", 1e-5), (100, "random", 3e-5), (100, "near_zero", 3e-5),
    (1536, "random", 1e-4), (1536, "near_zero", 1e-4),
    # every channel at the gate's lower bound for a whole bucket: exp(80) on one
    # side of the pair products, exp(-80) on the other, their rounding with them
    (1536, -5.0, 1e-3),
])
def test_kda_prefill_equals_t_steps(t, g, tol):
    q, k, v, g, beta = delta_inputs(t, g, key=t)
    o, S = jax.jit(kda_prefill)(q, k, v, g, beta)
    want_o, want_S = by_steps(q, k, v, g, beta)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    np.testing.assert_allclose(o, want_o, atol=tol, rtol=tol)
    np.testing.assert_allclose(S, want_S, atol=tol, rtol=tol)
    assert float(jnp.abs(want_o).max()) > 0.1


@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
def test_kda_prefill_at_every_chunk_size_and_from_a_state(chunk):
    q, k, v, g, beta = delta_inputs(70, "random", key=3)
    whole_o, whole_S = by_steps(q, k, v, g, beta)
    first = kda_prefill(q[:, :30], k[:, :30], v[:, :30], g[:, :30], beta[:, :30], chunk=chunk)
    o, S = kda_prefill(q[:, 30:], k[:, 30:], v[:, 30:], g[:, 30:], beta[:, 30:], first[1], chunk=chunk)
    np.testing.assert_allclose(jnp.concatenate([first[0], o], 1), whole_o, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(S, whole_S, atol=TOL, rtol=TOL)
    with pytest.raises(ValueError, match="multiple of the sub-chunk"):
        kda_prefill(q, k, v, g, beta, chunk=24)


def test_a_decay_constant_over_a_heads_channels_is_the_scalar_rule():
    """`g [.., h, dk]` with equal channels gives what `g [.., h]` gives, step
    and segment; `gated_delta_prefill` sends a per-channel decay to `kda_prefill`."""
    q, k, v, g, beta = delta_inputs(100, "head_constant", key=5)
    scalar_o, scalar_S = gated_delta_prefill(q, k, v, g[..., 0], beta)
    o, S = kda_prefill(q, k, v, g, beta)
    np.testing.assert_allclose(o, scalar_o, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(S, scalar_S, atol=TOL, rtol=TOL)
    routed_o, routed_S = gated_delta_prefill(q, k, v, g, beta)
    np.testing.assert_array_equal(routed_o, o)
    np.testing.assert_array_equal(routed_S, S)
    S0 = jax.random.normal(jax.random.key(9), scalar_S.shape)
    a = gated_delta_step(S0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    b = gated_delta_step(S0, q[:, 0], k[:, 0], v[:, 0], g[:, 0, :, 0], beta[:, 0])
    np.testing.assert_allclose(a[0], b[0], atol=1e-6)
    np.testing.assert_allclose(a[1], b[1], atol=1e-6)


# ---------------------------------------------------------- router, by hand
def test_group_limit_by_hand():
    """One token, 8 experts in 4 groups of 2, 2 groups kept, 3 experts chosen.
    Scores 0.9 0.1 | 0.6 0.5 | 0.55 0.52 | 0.3 0.2: group sums 1.0, 1.1, 1.07,
    0.5, so groups 1 and 2 are kept and expert 0, the best of all, is out of
    reach: the choice is 2, 4, 5 (0.6, 0.55, 0.52), where the plain top-3 is 0,
    2, 4. Weights are the chosen scores over their sum, times the scaling."""
    scores = np.array([0.9, 0.1, 0.6, 0.5, 0.55, 0.52, 0.3, 0.2])
    router = jnp.asarray(np.log(scores / (1 - scores))[None, :], jnp.float32)
    x, zero = jnp.ones((1, 1)), jnp.zeros(8)
    w, idx = route_sigmoid_top_k(x, router, zero, 3, scaling=2.5, n_group=4, topk_group=2)
    assert idx.tolist() == [[2, 4, 5]]
    np.testing.assert_allclose(w, [2.5 * np.array([0.6, 0.55, 0.52]) / 1.67], rtol=1e-6)
    assert route_sigmoid_top_k(x, router, zero, 3, scaling=2.5)[1].tolist() == [[0, 2, 4]]
    # the bias enters the groups' scores and the choice, never the weights
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0.4, 0.3], jnp.float32)  # group 3: 0.7 + 0.5 = 1.2
    w, idx = route_sigmoid_top_k(x, router, bias, 3, scaling=1.0, n_group=4, topk_group=2)
    assert idx.tolist() == [[6, 2, 3]]  # s + b: 0.7, 0.6, 0.5 (expert 7 ties expert 3 at 0.5 and comes later)
    np.testing.assert_allclose(w, [np.array([0.3, 0.6, 0.5]) / 1.4], rtol=1e-6)


def test_router_keeps_its_groups_and_matches_reference(cfg, ref_params):
    p, x = ref_params["layers"][2], hidden(cfg, (200,), key=5)
    k, groups, kept = int(cfg["num_experts_per_tok"]), int(cfg["n_group"]), int(cfg["topk_group"])
    args = (x, p["router"], p["bias"], k, float(cfg["routed_scaling_factor"]))
    w, idx = route_sigmoid_top_k(*args, n_group=groups, topk_group=kept)
    want_w, want_idx = ref.route(p, x, cfg)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    size = F.router_width(cfg) // groups
    used = [len(set((row // size).tolist())) for row in np.asarray(idx)]
    assert max(used) <= kept and all(len(set(row.tolist())) == k for row in np.asarray(idx))
    np.testing.assert_allclose(w.sum(-1), float(cfg["routed_scaling_factor"]), rtol=1e-5)
    # the limit binds: the plain top-k differs for some tokens and not for all
    free = route_sigmoid_top_k(*args)[1]
    changed = float((jnp.sort(free, -1) != jnp.sort(idx, -1)).any(-1).mean())
    assert 0.0 < changed < 1.0
    np.testing.assert_array_equal(free, ref.route(p, x, cfg, low="no_group_limit")[1])


def test_one_group_is_the_ungrouped_router(cfg, ref_params):
    """`n_group` 1 is today's `route_sigmoid_top_k`, bit for bit (Kimi K2's
    configuration), whatever `topk_group` says; so is keeping every group."""
    p, x = ref_params["layers"][2], hidden(cfg, (40,), key=6)
    args = (x, p["router"], p["bias"], 4, 2.5)
    plain = route_sigmoid_top_k(*args)
    for kw in (dict(n_group=1, topk_group=1), dict(n_group=1, topk_group=4), dict(n_group=4, topk_group=4)):
        got = route_sigmoid_top_k(*args, **kw)
        np.testing.assert_array_equal(got[1], plain[1])
        np.testing.assert_array_equal(got[0], plain[0])
    text = lambda **kw: jax.jit(lambda *a: route_sigmoid_top_k(*a, 4, 2.5, **kw)).lower(*args[:3]).as_text()  # noqa: E731
    assert text() == text(n_group=1, topk_group=1)


# ------------------------------------------------------------- the share test
def test_four_expert_shares_and_the_shared_expert_once_make_the_uncut_layer(cfg, ref_params):
    """32 routed experts in 4 groups as 4 expert-parallel shares of one whole
    group each: every chip computes its routed part from the one group-limited
    router, the shared expert is counted once, and the sum is the uncut
    reference layer. A token has picks on exactly `topk_group` chips at most."""
    width, shares = F.router_width(cfg), 4
    held = width // shares
    whole_cfg = dict(cfg, num_experts=width, published={"num_experts": width})
    whole = W.upcast(W.make_layer(SEED, whole_cfg, 2, jnp.float32))  # all 32 experts, one router
    x = hidden(cfg, (3, 10), key=7).reshape(30, -1)
    k = int(cfg["num_experts_per_tok"])
    weights, idx = route_sigmoid_top_k(x, whole["router"], whole["bias"], k, float(cfg["routed_scaling_factor"]),
                                       int(cfg["n_group"]), int(cfg["topk_group"]))
    total, picks, chips_of_a_token = 0.0, 0, np.zeros(30, int)
    for first in range(0, width, held):
        part = slice(first, first + held)
        gate_up = jnp.concatenate([whole["wg"][part], whole["wu"][part]], -1)
        out, n, touched = held_experts_mlp(x, weights, idx, gate_up, whole["wd"][part], first)
        assert 0 < int(touched) <= held
        total, picks = total + out, picks + int(n)
        chips_of_a_token += np.asarray(((idx >= first) & (idx < first + held)).any(-1))
        alone = ref.moe({**whole, "wg": whole["wg"][part], "wu": whole["wu"][part], "wd": whole["wd"][part]},
                        x, cfg, held=(first, held), shared=False)
        np.testing.assert_allclose(out, alone, atol=TOL, rtol=TOL)  # one share is the reference given that share
    assert picks == 30 * k  # no token dropped, every pick held exactly once
    assert chips_of_a_token.max() <= int(cfg["topk_group"]) and chips_of_a_token.min() >= 1
    total = total + shared_expert_mlp(x, None, jnp.concatenate([whole["s_wg"], whole["s_wu"]], -1), whole["s_wd"])
    np.testing.assert_allclose(total, ref.moe(whole, x, cfg), atol=TOL, rtol=TOL)  # held=None: the uncut layer


# ------------------------------------------------------- the cut configuration
def test_cut_configuration_holds_3687_million_parameters():
    """The issue's arithmetic: a KDA mixer 63.0 M, the MLA mixer 32.0 M, a
    dense MLP 47.2 M, an expert 5.898 M, an expert layer's FFN with 128 held
    experts, the shared one and the router 762.2 M, embedding and head 201.2 M:
    about 3.69 B, 7.4 GB; and the program's own tree holds exactly that many."""
    cfg = harness.load_json("configs", CONFIG)
    m = 1e6
    assert round(F.kda_params(cfg) / m, 1) == 63.0 and round(F.latent_params(cfg) / m, 1) == 32.0
    assert round(F.dense_mlp_params(cfg) / m, 1) == 47.2 and round(F.expert_params(cfg) / m, 3) == 5.898
    ffn = 128 * F.expert_params(cfg) + F.shared_params(cfg) + F.router_params(cfg)
    assert round(ffn / m, 1) == 762.2
    assert round(2 * cfg["vocab_size"] * cfg["hidden_size"] / m, 1) == 201.2
    total = F.total_params(cfg)
    print(f"ling-3.0-flash-vl, chip 0 of stage 0: {total:,} parameters, {F.param_bytes(cfg) / 1e9:.3f} GB")
    assert round(total / m) == 3692 and round(F.param_bytes(cfg) / 1e9, 2) == 7.39
    module = Ling3ForCausalLM(W.model_config(cfg))
    shapes = jax.eval_shape(lambda: module.init(jax.random.key(0), jnp.zeros((1, 2), jnp.int32)))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) == total
    assert sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree.leaves(shapes)) \
        == F.param_bytes(cfg)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184}
    assert cfg["deployment"]["chips_sharing_a_layer"] * int(cfg["num_experts"]) == 512
    assert 4 * cfg["vocab_size"] == 157184 and 7 * cfg["num_hidden_layers"] == 42
    # the chip holds whole routing groups: 2 of the 8
    assert int(cfg["num_experts"]) % (512 // cfg["n_group"]) == 0
    # even group choice: C(6,4) of the C(8,4) kept sets miss both held groups
    assert round(100 * (1 - math.comb(6, 4) / math.comb(8, 4)), 1) == 78.6


# ------------------------------------------------------------------ the engine
class Probe(nn.Module):
    """The model with its logits handed to the test as they are computed."""

    config: Ling3Config
    seen = []

    @nn.compact
    def __call__(self, input_ids, **kw):
        logits = Ling3ForCausalLM(self.config, name="lm")(input_ids, **kw)
        jax.debug.callback(lambda x: Probe.seen.append(np.asarray(x)), logits, ordered=True)
        return logits


def engine_for(module, tree, **kw):
    args = dict(max_concurrency=4, prompt_buckets=(32, 64), paged_kv=True, paged_attention="fused",
                admit_batch=4, eos_token_id=None)
    args.update(kw)
    return ServingEngine(module, tree, **args)


def serve(engine, prompts, new_tokens):
    ids = [engine.submit(Request(prompt=p, params=SamplingParams(temperature=0.0, max_new_tokens=new_tokens))
                         ).request_id for p in prompts]
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
    """Four requests of unequal length admitted together in one padded bucket
    (the chunked delta rule with pad tokens masked, the plain latent form),
    then 16 decode turns through per-slot KDA state and the paged latent pool
    (the absorbed form); the shortest prompt is shorter than the convolution's
    window, the longest ends one token short of the bucket."""
    Probe.seen.clear()
    engine = engine_for(Probe(model_cfg), {"lm": params}, paged_attention=paged_attention, pipeline_depth=1)
    prompts = prompts_of(cfg, (2, 13, 31, 20))
    outs = serve(engine, prompts, 17)
    jax.effects_barrier()
    admit, steps = Probe.seen[0], Probe.seen[1:]
    assert admit.shape[:2] == (4, 32) and len(steps) >= 16 and all(s.shape[:2] == (4, 1) for s in steps)
    for row, (prompt, out) in enumerate(zip(prompts, outs)):
        assert len(out.tokens) == 17
        full = jnp.asarray([prompt + out.tokens])
        want = np.asarray(ref.forward(ref_params, full, cfg, held=W.held_experts(cfg))[0])
        p = len(prompt)
        np.testing.assert_allclose(admit[row, p - 1], want[p - 1], atol=TOL, rtol=TOL)
        for turn in range(16):  # turn t is fed token t and sits at position p + t
            np.testing.assert_allclose(steps[turn][row, 0], want[p + turn], atol=TOL, rtol=TOL)
        assert out.tokens == [int(t) for t in want[p - 1: p + 16].argmax(-1)]


def test_cache_tree_holds_state_leaves_and_one_latent_leaf(cfg, model_cfg, params):
    engine = engine_for(Ling3ForCausalLM(model_cfg), params)
    flat = jax.tree_util.tree_flatten_with_path(engine._cache)[0]
    names = sorted(leaf_name(path) for path, _ in flat)
    assert names == sorted(["conv_state", "kda_state"] * 5 + [LATENT_LEAF, "cache_index"])
    heads, d = model_cfg.num_attention_heads, model_cfg.head_dim
    for path, leaf in flat:
        if leaf_name(path) == "kda_state":
            assert leaf.shape == (4, heads, d, d) and leaf.dtype == jnp.float32
        if leaf_name(path) == "conv_state":
            assert leaf.shape == (4, model_cfg.short_conv_kernel_size - 1, 3 * heads * d)
        if leaf_name(path) == LATENT_LEAF:
            assert leaf.shape[1:] == (16, model_cfg.latent_row_lanes)
    serve(engine, prompts_of(cfg, (10, 12)), 5)
    stats = engine.memory_stats()
    state = 5 * 4 * (heads * d * d + 3 * 3 * heads * d) * 4
    assert stats["slot_state_bytes"] == state == state_nbytes(engine._cache, STATE_LEAVES)
    assert stats["block_pool/pool_bytes"] + state == tree_nbytes(engine._cache)  # the pool gauge is the latent leaf's
    snapshot = engine.metrics.snapshot()
    assert 0 < snapshot["serving/paged_decode/live_tokens"] < snapshot["serving/paged_decode/span_tokens"]
    counters, steps = engine.metrics.step_counters, engine.metrics.counted_steps.value
    assert set(counters) == set(STEP_COUNTERS) and steps >= 4
    assert counters["moe_rows_routed_here"] <= steps * 4 * 4  # four expert layers, four slot rows
    assert counters["moe_experts_touched"] <= counters["moe_picks_held"]
    assert counters["moe_rows_routed_here"] <= counters["moe_picks_held"]


@pytest.mark.parametrize("argument, named", [
    ({"prefix_cache": True}, "prefix_cache"), ({"kv_tier": True}, "kv_tier"),
    ({"speculation": 2}, "speculation"), ({"mesh": (1, 1)}, "mesh")])
def test_state_beside_a_latent_leaf_is_refused_what_needs_a_token_range(model_cfg, params, argument, named):
    with pytest.raises(ValueError, match=rf"per-slot recurrent state.*{named} is not supported"):
        engine_for(Ling3ForCausalLM(model_cfg), params, **argument)
