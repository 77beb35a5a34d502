"""K-EXAONE behind the serving engine, against the plain reference
(`benchmarks/chip/reference/k_exaone.py`: float32, every query against the
keys it may see under explicit causal and window masks, no cache; it imports
nothing of the program): the two kinds of attention layer (RoPE and a window
of 8 on the sliding ones, no position on the full one, each shown to matter),
the post-sublayer norms, the expert-parallel share, prefill of prompts
shorter than, as long as and longer than the window in one padded bucket then
decode far past it through the per-slot window rings and the paged pool
against the reference's full forward pass (logits, not tokens), a finished
slot's ring frozen, the parameter count of the cut configuration, and what the
engine does with a contract whose state leaves are rings. CPU, tiny widths,
seeded weights."""

import dataclasses
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

import flops_k_exaone as F  # noqa: E402
import harness  # noqa: E402
import weights_k_exaone as W  # noqa: E402
from reference import k_exaone as ref  # noqa: E402

from accelerate_tpu.models import k_exaone  # noqa: E402
from accelerate_tpu.models.k_exaone import (  # noqa: E402
    STEP_COUNTERS,
    WINDOW_LEAVES,
    KExaoneAttention,
    KExaoneConfig,
    KExaoneForCausalLM,
)
from accelerate_tpu.models.kimi_k2 import SigmoidMoE  # noqa: E402
from accelerate_tpu.models.kv_cache import leaf_name, state_nbytes, tree_nbytes  # noqa: E402
from accelerate_tpu.ops.flash_attention import paged_decode_attention  # noqa: E402
from accelerate_tpu.ops.moe import held_experts_mlp, route_sigmoid_top_k, shared_expert_mlp  # noqa: E402
from accelerate_tpu.serving import Request, SamplingParams, ServingEngine  # noqa: E402

pytestmark = pytest.mark.serving
SEED = 7
# float32 both sides, "highest" matmuls: sums in another order (the ring's
# rows out of position order, the softmax over padded keys); bfloat16
# anywhere reads a thousand times this
TOL = 3e-5
CONFIG = "k-exaone-236b-a23b.json"


@pytest.fixture(scope="module")
def cfg():
    """The benchmark configuration's rehearsal sizes: every width tiny, the
    cell's five layers (sliding at 0-2 and 4, full at 3; layer 0 dense), a
    window of 8, the router 16 wide with experts 0-3 held, float32."""
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


def far(a, b):
    return float(jnp.abs(a - b).max()) > 100 * TOL


# ------------------------------------------------------------------ the layers
def test_sliding_layer_rotates_and_sees_its_window(cfg, model_cfg, params, ref_params, monkeypatch):
    x = hidden(cfg, (2, 30))  # 30 positions, a window of 8
    got = KExaoneAttention(model_cfg, True).apply({"params": params["layer_0"]["attn"]}, x, positions_of(2, 30))
    want = ref.attention(ref_params["layers"][0], x, cfg, sliding=True)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert float(jnp.abs(want).max()) > 1e-2
    assert far(want, ref.attention(ref_params["layers"][0], x, cfg, sliding=True, low="no_window"))
    # without the rotation the sliding layer is another function
    monkeypatch.setattr(k_exaone, "partial_rope", lambda x, positions, theta, rot: x)
    unrotated = KExaoneAttention(model_cfg, True).apply({"params": params["layer_0"]["attn"]}, x,
                                                        positions_of(2, 30))
    assert far(unrotated, want)


def test_full_layer_carries_no_position(cfg, model_cfg, params, ref_params):
    x = hidden(cfg, (2, 30), key=1)
    got = KExaoneAttention(model_cfg, False).apply({"params": params["layer_3"]["attn"]}, x, positions_of(2, 30))
    want = ref.attention(ref_params["layers"][3], x, cfg, sliding=False)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    # RoPE where the model has none reads visibly apart
    assert far(want, ref.attention(ref_params["layers"][3], x, cfg, sliding=False, low="rope_global"))


def test_expert_layer_is_kimi_k2s_on_its_share(cfg, model_cfg, params, ref_params):
    x = hidden(cfg, (2, 12), key=2)
    got, counted = SigmoidMoE(model_cfg).apply({"params": params["layer_1"]["moe"]}, x, mutable=["counters"])
    want = ref.moe(ref_params["layers"][1], x, cfg, held=W.held_experts(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    routed = ref.moe(ref_params["layers"][1], x, cfg, held=W.held_experts(cfg), shared=False)
    assert float(jnp.abs(routed).max()) > 1e-3  # the held picks are not nothing
    assert set(counted["counters"]) == {"moe_picks_held", "moe_experts_touched"}


def test_whole_model_matches_reference(cfg, model_cfg, params, ref_params):
    ids = jax.random.randint(jax.random.key(1), (2, 41), 0, cfg["vocab_size"])
    got = KExaoneForCausalLM(model_cfg).apply({"params": params}, ids)
    want = ref.forward(ref_params, ids, cfg, held=W.held_experts(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_norms_sit_on_the_sublayers_outputs(cfg, ref_params):
    """The residual stream enters each sublayer raw: scaling a layer's input
    scales what it adds by nothing (QK-norm and the post-norms absorb it) only
    where no pre-norm stands in front; the reference's layer is `x + N(Attn(x))`."""
    p, x = ref_params["layers"][0], hidden(cfg, (1, 16), key=3)
    added = ref.mix(p, x, cfg, True) - x
    rms = jnp.sqrt(jnp.mean(added * added, -1) / jnp.mean(p["norm_attn"] ** 2))
    np.testing.assert_allclose(rms, 1.0, rtol=0.2)  # a normed output, whatever the input's size


def test_layer_pattern_and_contract(model_cfg):
    assert [model_cfg.is_sliding(i) for i in range(5)] == [True, True, True, False, True]
    assert [model_cfg.is_dense(i) for i in range(5)] == [True] + [False] * 4
    full = KExaoneConfig()
    assert sum(full.is_sliding(i) for i in range(48)) == 36 and full.sliding_window == 128
    assert full.n_routed_experts == 128 and full.n_shared_experts == 1
    contract = model_cfg.cache_contract()
    assert (contract.kv_heads, contract.head_dim, contract.value_dim) == (2, 16, None)
    assert contract.state_leaves == WINDOW_LEAVES == ("window_key", "window_value")
    assert contract.step_counters == STEP_COUNTERS == ("moe_picks_held", "moe_experts_touched", "window_rows")
    with pytest.raises(ValueError, match="layer_types"):
        KExaoneConfig(num_hidden_layers=6, layer_types=("sliding_attention", "chunked_attention") * 3)


def test_ring_attention_is_the_window_in_any_row_order():
    """The ring holds a slot's last W keys in rows p % W, and a decode step
    reads it through the fused paged kernel as a pool of one block a slot
    (table [slot], the live rows' count as the length): attention over its
    live rows is attention over the window, whatever their order."""
    ks = jax.random.split(jax.random.key(4), 3)
    b, w, hq, hkv, d = 3, 8, 4, 2, 16
    q = jax.random.normal(ks[0], (b, hq, d))
    keys, values = (jax.random.normal(k, (b, w, hkv * d)) for k in ks[1:])
    slots = jnp.arange(b, dtype=jnp.int32)[:, None]

    def ring(keys, values, length):
        return paged_decode_attention(q, keys, values, slots, jnp.asarray(length, jnp.int32))

    got = ring(keys, values, [3, 8, 8])
    perm = jnp.asarray([5, 2, 7, 0, 4, 1, 6, 3])
    shuffled = ring(keys[:, perm], values[:, perm], [8, 8, 8])
    np.testing.assert_allclose(got[1:], shuffled[1:], atol=1e-5)
    grouped = q.reshape(b, hkv, hq // hkv, d)
    for row, n in enumerate([3, 8, 8]):
        kk, vv = keys[row, :n].reshape(n, hkv, d), values[row, :n].reshape(n, hkv, d)
        p = jax.nn.softmax(jnp.einsum("hgd,thd->hgt", grouped[row], kk) * d ** -0.5, -1)
        np.testing.assert_allclose(got[row], jnp.einsum("hgt,thd->hgd", p, vv).reshape(hq, d), atol=1e-5)


# ------------------------------------------------------------- the share test
def test_eight_expert_shares_and_the_shared_expert_once_make_the_uncut_layer(cfg, ref_params):
    """The router's 16 experts as 8 expert-parallel shares of 2, as the
    deployment's 8 chips hold 16 of 128: every chip computes its routed part
    from the one router, the shared expert is counted once, and the sum is the
    uncut reference layer."""
    width, shares = F.router_width(cfg), 8
    held = width // shares
    whole_cfg = dict(cfg, num_experts=width, published={"num_experts": width})
    whole = W.upcast(W.make_layer(SEED, whole_cfg, 1, jnp.float32))  # all 16 experts, one router
    x = hidden(cfg, (3, 10), key=7).reshape(30, -1)
    k = int(cfg["num_experts_per_tok"])
    weights, idx = route_sigmoid_top_k(x, whole["router"], whole["bias"], k, float(cfg["routed_scaling_factor"]))
    total, picks = 0.0, 0
    for first in range(0, width, held):
        part = slice(first, first + held)
        gate_up = jnp.concatenate([whole["wg"][part], whole["wu"][part]], -1)
        out, n, _ = held_experts_mlp(x, weights, idx, gate_up, whole["wd"][part], first)
        total, picks = total + out, picks + int(n)
        alone = ref.moe({**whole, "wg": whole["wg"][part], "wu": whole["wu"][part], "wd": whole["wd"][part]},
                        x, cfg, held=(first, held), shared=False)
        np.testing.assert_allclose(out, alone, atol=TOL, rtol=TOL)  # one share is the reference given that share
    assert picks == 30 * k  # no token dropped, every pick held exactly once
    total = total + shared_expert_mlp(x, None, jnp.concatenate([whole["s_wg"], whole["s_wu"]], -1), whole["s_wd"])
    np.testing.assert_allclose(total, ref.moe(whole, x, cfg), atol=TOL, rtol=TOL)  # held=None: the uncut layer


@pytest.mark.parametrize("width,chips", [(128, 8), (16, 8), (16, 1)])
def test_selection_bias_gives_every_chip_one_value_of_each_stratum(width, chips):
    """The bias is the router's mid-quantiles of normal(0, 0.01); each chip's
    block holds one of each of `block` equal strata, in an order of the key's."""
    block = width // chips
    quantiles = np.sort(np.asarray(0.01 * jax.scipy.special.ndtri((np.arange(width) + 0.5) / width)))
    draws = [np.asarray(W.selection_bias(jax.random.PRNGKey(k), width, block, 0.01)) for k in (0, 1)]
    for bias in draws:
        np.testing.assert_allclose(np.sort(bias), quantiles, rtol=1e-6)
        for chip in range(chips):
            held = np.sort(bias[chip * block: (chip + 1) * block])
            strata = quantiles.reshape(block, chips)
            assert np.all((strata[:, 0] <= held) & (held <= strata[:, -1]))
    assert not np.array_equal(*draws)


def test_cut_and_uncut_layers_draw_one_selection_bias(cfg):
    """The bias is stratified by the deployment's chips, not by what a cut
    holds: the cut (experts 0-3 of 16) and the uncut layer draw the same."""
    assert W.chip_block(cfg) == 2
    whole_cfg = dict(cfg, num_experts=F.router_width(cfg))
    cut, whole = (W.make_layer(SEED, c, 1, jnp.float32)["bias"] for c in (cfg, whole_cfg))
    np.testing.assert_array_equal(cut, whole)
    with pytest.raises(ValueError, match="chips"):
        W.chip_block(dict(cfg, deployment={"chips_sharing_a_layer": 3}))


# ------------------------------------------------------- the cut configuration
def test_cut_configuration_holds_3712_million_parameters():
    """The cut's arithmetic: attention 113.2 M a layer, layer 0 with its
    dense MLP 453.0 M, an expert layer with 16 held experts, the shared one
    and the router 755.8 M, embedding and head 235.9 M: about 3.712 B, 7.42 GB;
    and the program's own tree holds exactly that many."""
    cfg = harness.load_json("configs", CONFIG)
    m = 1e6
    assert round(F.attention_params(cfg) / m, 1) == 113.2
    assert round(F.layer_params(cfg, 0) / m, 1) == 453.0 and round(F.layer_params(cfg, 1) / m, 1) == 755.8
    assert round(2 * cfg["vocab_size"] * cfg["hidden_size"] / m, 1) == 235.9
    total = F.total_params(cfg)
    print(f"k-exaone-236b-a23b, chip 0 of stage 0: {total:,} parameters, {F.param_bytes(cfg) / 1e9:.3f} GB")
    # 7.424 GB in bfloat16, and the four routers' 3.1 M parameters kept in float32 besides
    assert round(total / 1e9, 3) == 3.712 and round(F.param_bytes(cfg) / 1e9, 3) == 7.430
    module = KExaoneForCausalLM(W.model_config(cfg))
    shapes = jax.eval_shape(lambda: module.init(jax.random.key(0), jnp.zeros((1, 2), jnp.int32)))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) == total
    assert sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree.leaves(shapes)) \
        == F.param_bytes(cfg)
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "num_nextn_predict_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600,
                                "num_nextn_predict_layers": 1}
    assert cfg["deployment"]["chips_sharing_a_layer"] * int(cfg["num_experts"]) == 128
    assert 8 * cfg["vocab_size"] == 153600
    assert [ref.is_sliding(i, cfg) for i in range(5)] == [True, True, True, False, True]


# ------------------------------------------------------------------ the engine
class Probe(nn.Module):
    """The model with its logits handed to the test as they are computed."""

    config: KExaoneConfig
    seen = []

    @nn.compact
    def __call__(self, input_ids, **kw):
        logits = KExaoneForCausalLM(self.config, name="lm")(input_ids, **kw)
        jax.debug.callback(lambda x: Probe.seen.append(np.asarray(x)), logits, ordered=True)
        return logits


def engine_for(module, tree, **kw):
    args = dict(max_concurrency=4, prompt_buckets=(32, 64), paged_kv=True, paged_attention="fused",
                admit_batch=4, eos_token_id=None)
    args.update(kw)
    return ServingEngine(module, tree, **args)


def serve(engine, prompts, new_tokens):
    ids = [engine.submit(Request(prompt=p, params=SamplingParams(temperature=0.0, max_new_tokens=n))
                         ).request_id for p, n in zip(prompts, new_tokens)]
    outs = {}
    while engine.has_work:
        for out in engine.step():
            outs[out.request_id] = out
    return [outs[i] for i in ids]


def prompts_of(cfg, lengths, key=0):
    rng = np.random.default_rng(key)
    return [rng.integers(0, cfg["vocab_size"], n).tolist() for n in lengths]


@pytest.mark.parametrize("paged_attention", ["fused", "gather"])
def test_prefill_in_a_bucket_then_decode_past_the_window_gives_the_reference_logits(
        cfg, model_cfg, params, ref_params, paged_attention):
    """Four requests admitted together in one padded bucket, prompts of 3, 8
    (the window), 21 and 31 tokens (the band kernel's window on the sliding
    layers, causal attention on the full one, each prompt's last 8 keys and
    values into its rings), then 20 decode turns: every ring wraps at least
    twice while the full layer reads the paged pool."""
    Probe.seen.clear()
    engine = engine_for(Probe(model_cfg), {"lm": params}, paged_attention=paged_attention, pipeline_depth=1)
    prompts = prompts_of(cfg, (3, 8, 21, 31))
    outs = serve(engine, prompts, [21] * 4)
    jax.effects_barrier()
    admit, steps = Probe.seen[0], Probe.seen[1:]
    assert admit.shape[:2] == (4, 32) and len(steps) >= 20 and all(s.shape[:2] == (4, 1) for s in steps)
    for row, (prompt, out) in enumerate(zip(prompts, outs)):
        assert len(out.tokens) == 21
        full = jnp.asarray([prompt + out.tokens])
        want = np.asarray(ref.forward(ref_params, full, cfg, held=W.held_experts(cfg))[0])
        p = len(prompt)
        np.testing.assert_allclose(admit[row, p - 1], want[p - 1], atol=TOL, rtol=TOL)
        for turn in range(20):  # turn t is fed token t and sits at position p + t
            np.testing.assert_allclose(steps[turn][row, 0], want[p + turn], atol=TOL, rtol=TOL)
        assert out.tokens == [int(t) for t in want[p - 1: p + 20].argmax(-1)]


def test_a_finished_slots_ring_is_frozen(cfg, model_cfg, params):
    """A decode step with a slot's write mask off leaves its rings bit for bit
    (and counts none of its rows), while the live slot's ring takes its key at
    row pos % W."""
    module = KExaoneForCausalLM(dataclasses.replace(model_cfg, kv_cache_per_slot=True))
    ids = jnp.asarray(prompts_of(cfg, (13, 13), key=3))
    _, mutated = module.apply({"params": params}, ids, decode=True, mutable=["cache"])
    cache = mutated["cache"]
    step = dict(decode=True, position_offset=jnp.asarray([13, 13]), mutable=["cache", "counters"],
                cache_write_mask=jnp.asarray([False, True]))
    _, after = module.apply({"params": params, "cache": cache}, ids[:, :1], **step)
    for name in WINDOW_LEAVES:
        before, now = cache["layer_0"]["attn"][name], after["cache"]["layer_0"]["attn"][name]
        np.testing.assert_array_equal(now[0], before[0])
        changed = np.flatnonzero(np.asarray(jnp.abs(now[1] - before[1]).max(-1)) > 0)
        assert changed.tolist() == [13 % model_cfg.sliding_window]
    # four sliding layers, the live slot's 8 ring rows each
    assert int(after["counters"]["layer_0"]["attn"]["window_rows"]) == 8
    total = sum(int(v) for path, v in jax.tree_util.tree_flatten_with_path(after["counters"])[0]
                if leaf_name(path) == "window_rows")
    assert total == 4 * 8


def test_cache_tree_holds_rings_beside_the_pool(cfg, model_cfg, params):
    engine = engine_for(KExaoneForCausalLM(model_cfg), params)
    flat = jax.tree_util.tree_flatten_with_path(engine._cache)[0]
    names = sorted(leaf_name(path) for path, _ in flat)
    assert names == sorted(list(WINDOW_LEAVES) * 4 + ["cached_key", "cached_value", "cache_index"])
    w, width = model_cfg.sliding_window, model_cfg.num_key_value_heads * model_cfg.head_dim
    for path, leaf in flat:
        if leaf_name(path) in WINDOW_LEAVES:
            assert leaf.shape == (4, w, width)
        if leaf_name(path) == "cached_key":
            assert leaf.shape[1:] == (16, width)
    serve(engine, prompts_of(cfg, (10, 12)), [12, 5])
    stats = engine.memory_stats()
    rings = 4 * 2 * 4 * w * width * 4
    assert stats["slot_state_bytes"] == rings == state_nbytes(engine._cache, WINDOW_LEAVES)
    assert stats["block_pool/pool_bytes"] + rings == tree_nbytes(engine._cache)  # the pool gauge is the full layer's
    snapshot = engine.metrics.snapshot()
    assert 0 < snapshot["serving/paged_decode/live_tokens"] < snapshot["serving/paged_decode/span_tokens"]
    counters, steps = engine.metrics.step_counters, engine.metrics.counted_steps.value
    assert set(counters) == set(STEP_COUNTERS) and steps >= 4
    # each counted step reads at most a window a live slot a sliding layer
    assert 0 < counters["window_rows"] <= steps * 2 * 4 * w
    assert counters["moe_experts_touched"] <= counters["moe_picks_held"]


@pytest.mark.parametrize("argument, named", [
    ({"prefix_cache": True}, "prefix_cache"), ({"kv_tier": True}, "kv_tier"),
    ({"speculation": 2}, "speculation"), ({"mesh": (1, 1)}, "mesh")])
def test_rings_are_refused_what_needs_a_token_range(model_cfg, params, argument, named):
    with pytest.raises(ValueError, match=rf"per-slot recurrent state.*{named} is not supported"):
        engine_for(KExaoneForCausalLM(model_cfg), params, **argument)
