"""Qwen3-Next behind the serving engine, against the plain reference
(`benchmarks/chip/reference/qwen3_next.py`, which imports nothing of the
program): each mixer and the expert layer, the chunked delta rule against its
one-token form, prefill in a padded bucket then decode through the paged cache
against the reference's full forward pass (logits, not tokens), a finished
slot's frozen state, the expert-parallel share, and what the engine refuses
for a model that keeps recurrent state. CPU, tiny widths, seeded weights."""

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

import harness  # noqa: E402
import weights_qwen3_next as W  # noqa: E402
from reference import qwen3_next as ref  # noqa: E402

from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead  # noqa: E402
from accelerate_tpu.models.qwen3_next import (  # noqa: E402
    GatedAttention,
    GatedDeltaNet,
    Qwen3NextConfig,
    Qwen3NextForCausalLM,
    SparseMoE,
    partial_rope,
)
from accelerate_tpu.ops import gated_delta  # noqa: E402
from accelerate_tpu.ops.moe import held_experts_mlp, route_top_k, shared_expert_mlp  # noqa: E402
from accelerate_tpu.serving import Request, SamplingParams, ServingEngine  # noqa: E402

pytestmark = pytest.mark.serving
SEED = 5
TOL = 2e-5  # float32 both sides, "highest" matmuls: sums in another order


@pytest.fixture(scope="module")
def cfg():
    """The benchmark configuration's rehearsal sizes: every width tiny, the
    router 16 wide over 8 held experts, float32."""
    return harness.overlay(harness.load_json("configs", "qwen3-next-80b-a3b.json"), True)


@pytest.fixture(scope="module")
def model_cfg(cfg):
    return dataclasses.replace(W.model_config(cfg), delta_chunk=8)


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


# ------------------------------------------------------------------ the layers
def test_gated_attention_matches_reference(cfg, model_cfg, params, ref_params):
    x = hidden(cfg, (2, 24))
    positions = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    got = GatedAttention(model_cfg).apply({"params": params["layer_3"]["attn"]}, x, positions)
    want = ref.gated_attention(ref_params["layers"][3], x, cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("tokens", [5, 8, 29])
def test_gated_deltanet_matches_reference(cfg, model_cfg, params, ref_params, tokens):
    x = hidden(cfg, (2, tokens), key=tokens)
    got = GatedDeltaNet(model_cfg).apply({"params": params["layer_0"]["delta"]}, x)
    want = ref.gated_deltanet(ref_params["layers"][0], x, cfg)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_moe_layer_matches_reference_on_its_share(cfg, model_cfg, params, ref_params):
    x = hidden(cfg, (2, 12))
    got = SparseMoE(model_cfg).apply({"params": params["layer_1"]["moe"]}, x)
    want = ref.moe(ref_params["layers"][1], x, cfg, held=W.held_experts(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert float(jnp.abs(want).max()) > 1e-3


def test_whole_model_matches_reference(cfg, model_cfg, params, ref_params):
    ids = jax.random.randint(jax.random.key(1), (2, 21), 0, cfg["vocab_size"])
    got = Qwen3NextForCausalLM(model_cfg).apply({"params": params}, ids)
    want = ref.forward(ref_params, ids, cfg, held=W.held_experts(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_partial_rope_matches_reference_and_leaves_the_rest():
    x = jax.random.normal(jax.random.key(0), (1, 9, 2, 32), jnp.float32)
    positions = jnp.arange(9)[None]
    got = partial_rope(x, positions, 1e7, 8)
    np.testing.assert_allclose(got, ref.rotary(x, 1e7, 8), atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    # a row at its own offset reads the same angles as that position in a prefix
    np.testing.assert_allclose(partial_rope(x[:, 4:5], jnp.asarray([[4]]), 1e7, 8), got[:, 4:5], atol=1e-6)


# ------------------------------------------------------------- the share test
def test_two_expert_shares_and_the_shared_expert_once_make_the_uncut_layer(cfg, ref_params):
    """Both chips of an expert-parallel pair compute their routed part; the
    shared expert is counted once; the sum is the uncut reference layer."""
    width, held = W.router_width(cfg), int(cfg["num_experts"])
    assert width == 2 * held
    whole_cfg = dict(cfg, num_experts=width, published={"num_experts": width})
    whole = W.upcast(W.make_layer(SEED, whole_cfg, 1, jnp.float32))  # all 16 experts, one router
    x = hidden(cfg, (3, 10), key=7).reshape(30, -1)
    weights, idx = route_top_k(x, whole["router"], int(cfg["num_experts_per_tok"]))
    total, picks = 0.0, 0
    for first in (0, held):
        part = slice(first, first + held)
        gate_up = jnp.concatenate([whole["wg"][part], whole["wu"][part]], -1)
        out, n, touched = held_experts_mlp(x, weights, idx, gate_up, whole["wd"][part], first)
        assert 0 < int(touched) <= held
        total, picks = total + out, picks + int(n)
    assert picks == 30 * int(cfg["num_experts_per_tok"])  # no token dropped, every pick held once
    total = total + shared_expert_mlp(x, whole["s_gate"], jnp.concatenate([whole["s_wg"], whole["s_wu"]], -1),
                                      whole["s_wd"])
    want = ref.moe(whole, x, cfg)  # held=None: the uncut layer
    np.testing.assert_allclose(total, want, atol=TOL, rtol=TOL)
    # and one share alone is the reference given that share
    half = ref.moe({**whole, "wg": whole["wg"][:held], "wu": whole["wu"][:held], "wd": whole["wd"][:held]},
                   x, cfg, held=(0, held), shared=False)
    out, _, _ = held_experts_mlp(x, weights, idx, jnp.concatenate([whole["wg"][:held], whole["wu"][:held]], -1),
                                 whole["wd"][:held], 0)
    np.testing.assert_allclose(out, half, atol=TOL, rtol=TOL)
    assert float(jnp.abs(want - half).max()) > 1e-3  # the absent half is not nothing


# ------------------------------------------------------------- the delta rule
def delta_inputs(b=2, t=21, h=3, dk=8, dv=4, key=0):
    ks = jax.random.split(jax.random.key(key), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def by_steps(q, k, v, g, beta, lengths=None):
    b, t, h, dk = q.shape
    g, beta = gated_delta.mask_pad(g, beta, lengths)
    state, outs = jnp.zeros((b, h, dk, v.shape[-1])), []
    for i in range(t):
        state, o = gated_delta.gated_delta_step(state, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i])
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("chunk", [4, 8, 64])
def test_chunked_delta_rule_equals_token_by_token(chunk):
    q, k, v, g, beta = delta_inputs()
    want_o, want_s = by_steps(q, k, v, g, beta)
    got_o, got_s = gated_delta.gated_delta_prefill(q, k, v, g, beta, chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("chunk", [12, 64])
def test_chunked_delta_rule_on_a_run_of_equal_tokens(chunk):
    """Equal tokens give equal keys: the chunk's triangular system is then
    dense with entries near beta, and an inverse built as the power series of
    its strict part loses every digit at 64 (terms of 1e10 for entries under
    1). A served prompt may hold such a run, and the warm-up's prompts do."""
    q, k, v, g, beta = delta_inputs(b=1, t=128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = 0.01 * g, 0.9 + 0.1 * beta
    want_o, want_s = by_steps(q, k, v, g, beta)
    got_o, got_s = gated_delta.gated_delta_prefill(q, k, v, g, beta, chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, atol=20 * TOL, rtol=20 * TOL)
    np.testing.assert_allclose(got_s, want_s, atol=20 * TOL, rtol=20 * TOL)


def test_pad_tokens_leave_the_state_untouched():
    q, k, v, g, beta = delta_inputs(t=16)
    lengths = jnp.asarray([16, 5])
    g_m, beta_m = gated_delta.mask_pad(g, beta, lengths)
    _, state = gated_delta.gated_delta_prefill(q, k, v, g_m, beta_m, chunk=4)
    _, short = gated_delta.gated_delta_prefill(q[1:, :5], k[1:, :5], v[1:, :5], g[1:, :5], beta[1:, :5], chunk=4)
    np.testing.assert_allclose(state[1], short[0], atol=TOL, rtol=TOL)
    _, stepped = by_steps(q, k, v, g, beta, lengths)
    np.testing.assert_allclose(state, stepped, atol=TOL, rtol=TOL)


def test_conv_window_is_the_last_true_inputs():
    x = jax.random.normal(jax.random.key(0), (3, 10, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    y, window = gated_delta.causal_conv_prefill(x, w, jnp.asarray([10, 4, 2]))
    np.testing.assert_array_equal(window[0], x[0, 7:10])
    np.testing.assert_array_equal(window[1], x[1, 1:4])
    np.testing.assert_array_equal(window[2], jnp.concatenate([jnp.zeros((1, 6)), x[2, :2]]))
    # one more token through the step equals the convolution of the longer row
    y_next, window_next = gated_delta.causal_conv_step(window[1:2], x[1:2, 4], w)
    np.testing.assert_allclose(y_next[0], y[1, 4], atol=1e-6)
    np.testing.assert_array_equal(window_next[0], x[1, 2:5])


def test_conv_window_first_is_the_same_convolution():
    """Taking the window first, behind a barrier beside the input (what Ling
    3.0 flash's KDA layers ask for), changes the program's order, not what it
    computes."""
    x = jax.random.normal(jax.random.key(0), (3, 10, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    lengths = jnp.asarray([10, 4, 2])
    want = gated_delta.causal_conv_prefill(x, w, lengths)
    got = gated_delta.causal_conv_prefill(x, w, lengths, window_first=True)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    for first in (False, True):
        jaxpr = str(jax.make_jaxpr(lambda a: gated_delta.causal_conv_prefill(a, w, lengths, first))(x))
        assert ("optimization_barrier" in jaxpr) == first


# ------------------------------------------------------------------ the engine
class Probe(nn.Module):
    """The model with its logits handed to the test as they are computed."""

    config: Qwen3NextConfig
    seen = []

    @nn.compact
    def __call__(self, input_ids, **kw):
        logits = Qwen3NextForCausalLM(self.config, name="lm")(input_ids, **kw)
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
    """Two requests of unequal length admitted together in one padded bucket,
    then 16 decode turns through the paged cache and the per-slot state."""
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


def test_a_finished_slots_state_does_not_move(cfg, model_cfg, params):
    engine = engine_for(Qwen3NextForCausalLM(model_cfg), params)
    prompts = prompts_of(cfg, (9, 14), key=1)
    ids = [engine.submit(Request(prompt=p, params=SamplingParams(temperature=0.0, max_new_tokens=n))).request_id
           for p, n in zip(prompts, (3, 12))]

    def state_of(slot):
        flat = jax.tree_util.tree_flatten_with_path(engine._cache)[0]
        return [np.asarray(leaf[slot]) for path, leaf in flat
                if getattr(path[-1], "key", None) in ("conv_state", "delta_state")]

    done, frozen = set(), None
    while engine.has_work:
        done |= {out.request_id for out in engine.step()}
        if ids[0] in done and frozen is None:
            jax.block_until_ready(engine._cache)
            frozen = state_of(0)
            assert len(frozen) == 6 and any(np.abs(leaf).max() > 0 for leaf in frozen)
    assert ids[1] in done
    for before, after in zip(frozen, state_of(0)):
        np.testing.assert_array_equal(before, after)


def test_a_reused_slot_starts_from_its_admission(cfg, model_cfg, params):
    """Nothing resets a slot at retirement: the next admission's scatter
    overwrites its whole state, so a second tenant answers as a first would."""
    module = Qwen3NextForCausalLM(model_cfg)
    prompts = prompts_of(cfg, (13, 21, 17), key=2)
    engine = engine_for(module, params, max_concurrency=1, admit_batch=1)
    reused = serve(engine, prompts, 6)
    for prompt, out in zip(prompts, reused):
        alone = serve(engine_for(module, params, max_concurrency=1, admit_batch=1), [prompt], 6)[0]
        assert out.tokens == alone.tokens


def test_engine_holds_the_params_it_is_given(model_cfg, params):
    """The contract declares no leaf consumed only in the compute dtype, so
    the engine hands its programs the very tree it was given, even where the
    compute dtype is narrower than the params (`docs/serving.md` "Parameter
    residency")."""
    module = Qwen3NextForCausalLM(dataclasses.replace(model_cfg, dtype=jnp.bfloat16))
    assert module.config.cache_contract().compute_dtype_params is None
    engine = engine_for(module, params)
    assert all(a is b for a, b in zip(jax.tree.leaves(engine.params), jax.tree.leaves(params), strict=True))
    stats = engine.memory_stats()
    assert stats["param_narrowed_saved_bytes"] == 0
    assert stats["param_bytes"] == sum(leaf.nbytes for leaf in jax.tree.leaves(params))


def test_step_counters_and_state_gauges(cfg, model_cfg, params):
    engine = engine_for(Qwen3NextForCausalLM(model_cfg), params)
    serve(engine, prompts_of(cfg, (10, 12)), 5)
    counters, steps = engine.metrics.step_counters, engine.metrics.counted_steps.value
    assert set(counters) == {"moe_picks_held", "moe_experts_touched"} and steps >= 4
    layers, held, k = model_cfg.num_hidden_layers, model_cfg.experts_held, model_cfg.num_experts_per_tok
    assert 0 < counters["moe_experts_touched"] <= steps * layers * held
    assert counters["moe_experts_touched"] <= counters["moe_picks_held"] <= steps * layers * 2 * k
    assert engine.metrics.snapshot()["serving/step_counters/steps"] == steps
    stats = engine.memory_stats()
    per_slot = 3 * (3 * 128 + 4 * 16 * 16) * 4  # three linear layers: conv window + S, float32 here
    assert stats["slot_state_bytes"] == 2 * per_slot and stats["slot_state_bytes_per_slot"] == per_slot
    assert stats["block_pool/pool_bytes"] == stats["slot_pool_bytes"] - stats["slot_state_bytes"]


@pytest.mark.parametrize("argument", [{"prefix_cache": True}, {"kv_tier": True},
                                      {"speculation": "ngram"}, {"mesh": (1, 2)}])
def test_engine_refuses_what_recurrent_state_cannot_do(model_cfg, params, argument):
    with pytest.raises(ValueError, match="recurrent state"):
        engine_for(Qwen3NextForCausalLM(model_cfg), params, **argument)


def test_a_multi_token_segment_on_top_of_state_raises(cfg, model_cfg, params):
    module = Qwen3NextForCausalLM(dataclasses.replace(model_cfg, kv_cache_per_slot=True))
    ids = jnp.zeros((1, 4), jnp.int32)
    cache = module.init(jax.random.key(0), ids[:, :1], decode=True)["cache"]
    with pytest.raises(NotImplementedError, match="recurrent state"):
        module.apply({"params": params, "cache": cache}, ids, decode=True,
                     position_offset=jnp.asarray([3]), mutable=["cache"])


# --------------------------------------------------------------- the contract
def test_gpt2_declares_keys_and_values_only_and_counts_nothing():
    contract = GPT2Config.tiny().cache_contract()
    assert (contract.kv_heads, contract.head_dim) == (2, 32)
    assert contract.state_leaves == () and contract.step_counters == ()
    module = GPT2LMHead(GPT2Config.tiny())
    engine = ServingEngine(module, module.init_params(jax.random.key(0)), max_concurrency=2,
                           prompt_buckets=(32,), paged_kv=True)
    serve(engine, [[1, 2, 3]], 4)
    assert engine.metrics.step_counters == {} and "slot_state_bytes" not in engine.memory_stats()
    snap = engine.metrics.snapshot()
    assert not any(k.startswith("serving/step_counters") for k in snap)


def test_a_model_without_a_contract_is_refused():
    class Bare(nn.Module):
        config: object = None

    with pytest.raises(TypeError, match="cache contract"):
        ServingEngine(Bare(config=object()), {})


def test_qwen3_next_contract(model_cfg):
    contract = model_cfg.cache_contract()
    assert (contract.kv_heads, contract.head_dim) == (2, 32)
    assert contract.state_leaves == ("conv_state", "delta_state")
    assert contract.step_counters == ("moe_picks_held", "moe_experts_touched")
    assert [model_cfg.is_full_attention(i) for i in range(4)] == [False, False, False, True]
