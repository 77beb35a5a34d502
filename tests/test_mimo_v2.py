"""MiMo-V2 behind the serving engine, against the plain reference
(`benchmarks/chip/reference/mimo_v2.py`: float32, every query against the
keys it may see under explicit causal and window masks, the sink as its own
term of the softmax's sum, no cache; it imports nothing of the program): the
two kinds of attention layer (8 key/value heads, a window of 8 and a sink a
query head on the sliding ones, 4 heads and no sink on the full ones, keys
wider than values on both), the pre-norm blocks, the expert layer with no
shared expert on its expert-parallel share, prefill in one padded bucket then
decode far past the window through the per-slot rings and the paged pool
against the reference's full forward pass (logits, not tokens), the fused
kernel's and the band kernel's sinks and split value widths against the XLA
path, and the parameter count of the cut configuration (the kernels' lowering
for a v5e, old and new, is `tests/test_tpu_aot_compile.py`'s). CPU, tiny
widths, seeded weights."""

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

import flops_mimo_v2 as F  # noqa: E402
import harness  # noqa: E402
import weights_mimo_v2 as W  # noqa: E402
from reference import mimo_v2 as ref  # noqa: E402

from accelerate_tpu.models.k_exaone import STEP_COUNTERS, WINDOW_LEAVES  # noqa: E402
from accelerate_tpu.models.kimi_k2 import KimiK2Config, SigmoidMoE  # noqa: E402
from accelerate_tpu.models.kv_cache import leaf_name, state_nbytes, tree_nbytes  # noqa: E402
from accelerate_tpu.models.mimo_v2 import MiMoV2Attention, MiMoV2Config, MiMoV2ForCausalLM  # noqa: E402
from accelerate_tpu.ops.attention import attention  # noqa: E402
from accelerate_tpu.ops.flash_attention import flash_attention, paged_decode_attention  # noqa: E402
from accelerate_tpu.ops.moe import held_experts_mlp, route_sigmoid_top_k  # noqa: E402
from accelerate_tpu.serving import Request, SamplingParams, ServingEngine  # noqa: E402

pytestmark = pytest.mark.serving
SEED = 7
# float32 both sides, "highest" matmuls: sums in another order (the ring's
# rows out of position order, the online softmax chunk by chunk, the sink's
# term added first); bfloat16 anywhere reads a thousand times this
TOL = 3e-5
CONFIG = "mimo-v2.5.json"


@pytest.fixture(scope="module")
def cfg():
    """The benchmark configuration's rehearsal sizes: every width tiny (keys
    24 wide, values 16, 4 query heads on 2 key/value heads on a sliding layer
    and 1 on a full one), the cell's seven layers (full at 0 and 5; layer 0
    dense), a window of 8, the router 16 wide with experts 0-3 held, float32."""
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


def normed(cfg, shape, key=0):
    return jax.random.normal(jax.random.key(key), shape + (cfg["hidden_size"],), jnp.float32)


def positions_of(b, t):
    return jnp.broadcast_to(jnp.arange(t)[None], (b, t))


def far(a, b):
    return float(jnp.abs(a - b).max()) > 100 * TOL


# ------------------------------------------------------------------ the layers
def test_sliding_layer_has_its_sinks_window_and_heads(cfg, model_cfg, params, ref_params):
    x = normed(cfg, (2, 30))  # 30 positions, a window of 8
    p = ref_params["layers"][1]
    got = MiMoV2Attention(model_cfg, True).apply({"params": params["layer_1"]["attn"]}, x, positions_of(2, 30))
    want = ref.attention(p, x, cfg, sliding=True)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert float(jnp.abs(want).max()) > 1e-3
    assert far(want, ref.attention(p, x, cfg, sliding=True, low="no_window"))
    assert far(want, ref.attention(p, x, cfg, sliding=True, low="no_sinks"))
    assert params["layer_1"]["attn"]["k_proj"]["kernel"].shape == (64, 2 * 24)
    assert params["layer_1"]["attn"]["v_proj"]["kernel"].shape == (64, 2 * 16)
    assert params["layer_1"]["attn"]["attention_sink_bias"].shape == (4,)


def test_full_layer_has_no_sink_and_four_times_fewer_heads(cfg, model_cfg, params, ref_params):
    x = normed(cfg, (2, 30), key=1)
    got = MiMoV2Attention(model_cfg, False).apply({"params": params["layer_0"]["attn"]}, x, positions_of(2, 30))
    want = ref.attention(ref_params["layers"][0], x, cfg, sliding=False)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert "attention_sink_bias" not in params["layer_0"]["attn"]
    assert params["layer_0"]["attn"]["k_proj"]["kernel"].shape == (64, 1 * 24)
    assert params["layer_0"]["attn"]["o_proj"]["kernel"].shape == (4 * 16, 64)


def test_expert_layer_has_no_shared_expert(cfg, model_cfg, params, ref_params):
    x = normed(cfg, (2, 12), key=2)
    tree = params["layer_1"]["moe"]
    assert not any(name.startswith("shared") for name in tree)
    got, counted = SigmoidMoE(model_cfg).apply({"params": tree}, x, mutable=["counters"])
    want = ref.moe(ref_params["layers"][1], x, dict(cfg, routed_scaling_factor=1.0),
                   held=W.held_experts(cfg), shared=False)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert float(jnp.abs(want).max()) > 1e-3  # the held picks are not nothing
    assert set(counted["counters"]) == {"moe_picks_held", "moe_experts_touched"}


@pytest.mark.parametrize("n_shared", [None, 0])
def test_an_expert_layer_without_shared_experts_declares_no_shared_weights(n_shared):
    kimi = dataclasses.replace(KimiK2Config(), hidden_size=16, moe_intermediate_size=8, n_routed_experts=4,
                               num_experts_per_tok=2, n_shared_experts=n_shared, dtype=jnp.float32,
                               param_dtype=jnp.float32)
    shapes = jax.eval_shape(lambda: SigmoidMoE(kimi).init(jax.random.key(0), jnp.zeros((1, 3, 16))))
    assert sorted(shapes["params"]) == ["e_score_correction_bias", "router", "w_down", "w_gate_up"]


def test_whole_model_matches_reference(cfg, model_cfg, params, ref_params):
    ids = jax.random.randint(jax.random.key(1), (2, 41), 0, cfg["vocab_size"])
    got = MiMoV2ForCausalLM(model_cfg).apply({"params": params}, ids)
    want = ref.forward(ref_params, ids, cfg, held=W.held_experts(cfg))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_the_model_without_its_sinks_is_told_from_the_reference(cfg, model_cfg, params, ref_params):
    """The comparison sees the sinks: the program with them off reads far
    from the reference, by more than the int8 control does."""
    ids = jax.random.randint(jax.random.key(1), (2, 41), 0, cfg["vocab_size"])
    want = ref.forward(ref_params, ids, cfg, held=W.held_experts(cfg))
    plain = dataclasses.replace(model_cfg, add_swa_attention_sink_bias=False)
    no_sinks = jax.tree.map(lambda x: x, params)
    for i in range(7):
        no_sinks[f"layer_{i}"]["attn"].pop("attention_sink_bias", None)
    got = MiMoV2ForCausalLM(plain).apply({"params": no_sinks}, ids)
    int8 = ref.forward(ref_params, ids, cfg, held=W.held_experts(cfg), low="int8")
    assert float(jnp.abs(got - want).max()) > 3 * float(jnp.abs(int8 - want).max())


def test_layer_pattern_and_contract(model_cfg):
    assert [model_cfg.is_sliding(i) for i in range(7)] == [False, True, True, True, True, False, True]
    assert [model_cfg.is_dense(i) for i in range(7)] == [True] + [False] * 6
    full = MiMoV2Config()
    assert sum(full.is_sliding(i) for i in range(48)) == 39 and full.sliding_window == 128
    assert [i for i in range(48) if not full.is_sliding(i)] == [0] + list(range(5, 48, 6))
    assert full.rotary_dim == 64 and full.heads(True) == (64, 8, 192, 128) and full.heads(False) == (64, 4, 192, 128)
    contract = model_cfg.cache_contract()
    assert (contract.kv_heads, contract.head_dim, contract.value_head_dim, contract.value_dim) == (1, 24, 16, None)
    assert contract.state_leaves == WINDOW_LEAVES and contract.step_counters == STEP_COUNTERS
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        MiMoV2Config(num_hidden_layers=3, hybrid_layer_pattern=(0, 2, 1))
    with pytest.raises(ValueError, match="full_attention_sink"):
        MiMoV2Config(add_full_attention_sink_bias=True)


# ----------------------------------------------------------------- the kernels
def _sinks(h, key=9):
    return jnp.log(8.0) + jax.random.normal(jax.random.key(key), (h,))


@pytest.mark.parametrize("dv", [16, 24])
@pytest.mark.parametrize("with_sinks", [True, False])
def test_fused_kernel_takes_sinks_and_a_narrower_value_pool(dv, with_sinks):
    """The fused paged kernel against the XLA path over the gathered rows: a
    row shorter than the window (3 of 8 live), a whole one, and a row that
    spans chunks of a paged pool; 8 query heads on 2 key/value heads."""
    ks = jax.random.split(jax.random.key(4), 3)
    b, hq, hkv, d, bt = 3, 8, 2, 24, 8
    q = jax.random.normal(ks[0], (b, hq, d))
    k_pool = jax.random.normal(ks[1], (16, bt, hkv * d))
    v_pool = jax.random.normal(ks[2], (16, bt, hkv * dv))
    tables = jnp.asarray([[2, 9, 0, 0], [5, 0, 0, 0], [1, 3, 7, 11]], jnp.int32)
    lengths = jnp.asarray([3, 8, 29], jnp.int32)
    sinks = _sinks(hq) if with_sinks else None
    got = paged_decode_attention(q, k_pool, v_pool, tables, lengths, sinks=sinks)
    assert got.shape == (b, hq, dv)
    keys = k_pool[tables].reshape(b, -1, hkv, d)
    values = v_pool[tables].reshape(b, -1, hkv, dv)
    live = (jnp.arange(keys.shape[1])[None] < lengths[:, None])[:, None, None, :]
    want = attention(q[:, None], keys, values, mask=live, implementation="xla", sinks=sinks)[:, 0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    if with_sinks:
        assert far(got, attention(q[:, None], keys, values, mask=live, implementation="xla")[:, 0])


@pytest.mark.parametrize("seq, window", [(32, 8), (8, 16)])  # a row shorter than the window
@pytest.mark.parametrize("with_sinks", [True, False])
def test_band_kernel_takes_sinks_and_a_narrower_value(seq, window, with_sinks):
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (2, seq, 4, 24))
    k = jax.random.normal(ks[1], (2, seq, 2, 24))
    v = jax.random.normal(ks[2], (2, seq, 2, 16))
    sinks = _sinks(4) if with_sinks else None
    got = flash_attention(q, k, v, causal=True, window=window, sinks=sinks, interpret=True)
    want = attention(q, k, v, causal=True, window=window, implementation="xla", sinks=sinks)
    assert got.shape == (2, seq, 4, 16)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    # the reference's own attention, sinks and all
    np.testing.assert_allclose(want, ref.attend(q, k, v, window, sinks), atol=TOL, rtol=TOL)


def test_rectangular_grid_pads_a_narrower_value_and_sinks_need_the_band():
    ks = jax.random.split(jax.random.key(6), 3)
    q, k = (jax.random.normal(key, (1, 32, 2, 24)) for key in ks[:2])
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    got = flash_attention(q, k, v, causal=True, block_q=16, block_kv=16, interpret=True)
    np.testing.assert_allclose(got, attention(q, k, v, causal=True, implementation="xla"), atol=TOL, rtol=TOL)
    with pytest.raises(ValueError, match="band grid"):
        flash_attention(q, k, v, causal=True, block_q=16, block_kv=16, sinks=_sinks(2), interpret=True)


def test_band_kernel_with_sinks_has_no_backward():
    x = jax.random.normal(jax.random.key(7), (1, 16, 2, 24))

    def loss(q):
        return flash_attention(q, x, x, causal=True, window=8, sinks=_sinks(2), interpret=True).sum()

    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(loss)(x)


# -------------------------------------------------------------- the share test
def test_sixteen_expert_shares_make_the_uncut_layer(cfg, ref_params):
    """The router's 16 experts as 16 expert-parallel shares of 1, as the
    deployment's 16 chips hold 16 of 256: every chip computes its routed part
    from the one router, and the sum is the uncut reference layer."""
    width, shares = F.router_width(cfg), 16
    held = width // shares
    whole_cfg = dict(cfg, n_routed_experts=width, published={"n_routed_experts": width})
    whole = W.upcast(W.make_layer(SEED, whole_cfg, 1, jnp.float32))  # all 16 experts, one router
    x = normed(cfg, (3, 10), key=7).reshape(30, -1)
    k = int(cfg["num_experts_per_tok"])
    weights, idx = route_sigmoid_top_k(x, whole["router"], whole["bias"], k, 1.0)
    one = dict(cfg, routed_scaling_factor=1.0)
    total, picks = 0.0, 0
    for first in range(0, width, held):
        part = slice(first, first + held)
        gate_up = jnp.concatenate([whole["wg"][part], whole["wu"][part]], -1)
        out, n, _ = held_experts_mlp(x, weights, idx, gate_up, whole["wd"][part], first)
        total, picks = total + out, picks + int(n)
        alone = ref.moe({**whole, "wg": whole["wg"][part], "wu": whole["wu"][part], "wd": whole["wd"][part]},
                        x, one, held=(first, held), shared=False)
        np.testing.assert_allclose(out, alone, atol=TOL, rtol=TOL)  # one share is the reference given that share
    assert picks == 30 * k  # no token dropped, every pick held exactly once
    np.testing.assert_allclose(total, ref.moe(whole, x, one, shared=False), atol=TOL, rtol=TOL)


# ------------------------------------------------------- the cut configuration
def test_cut_configuration_holds_3430_million_parameters():
    """The cut's arithmetic: sliding attention 94.37 M a layer, full 89.13 M,
    the router 1.05 M, 16 held experts 402.65 M a layer, the dense MLP
    201.33 M, embedding and head 156.24 M: about 3.430 B, 6.87 GB; and the
    program's own tree holds exactly that many."""
    cfg = harness.load_json("configs", CONFIG)
    m = 1e6
    assert round(F.attention_matmul_params(cfg, True) / m, 2) == 94.37
    assert round(F.attention_matmul_params(cfg, False) / m, 2) == 89.13
    assert round(F.router_params(cfg) / m, 2) == 1.05
    assert round(16 * F.expert_params(cfg) / m, 2) == 402.65 and round(F.dense_mlp_params(cfg) / m, 2) == 201.33
    assert round(2 * cfg["vocab_size"] * cfg["hidden_size"] / m, 2) == 156.24
    total = F.total_params(cfg)
    print(f"mimo-v2.5, chip 0 of stage 0: {total:,} parameters, {F.param_bytes(cfg) / 1e9:.3f} GB")
    assert round(total / 1e6, 2) == 3429.96 and round(F.param_bytes(cfg) / 1e9, 3) == 6.872
    module = MiMoV2ForCausalLM(W.model_config(cfg))
    shapes = jax.eval_shape(lambda: module.init(jax.random.key(0), jnp.zeros((1, 2), jnp.int32)))["params"]
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) == total
    assert sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree.leaves(shapes)) \
        == F.param_bytes(cfg)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "n_routed_experts": 256, "vocab_size": 152576}
    assert cfg["deployment"]["chips_sharing_a_layer"] * int(cfg["n_routed_experts"]) == 256
    assert 8 * cfg["vocab_size"] == 152576
    # every published width kept
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["swa_num_key_value_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["v_head_dim"], cfg["sliding_window"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"], cfg["intermediate_size"]) \
        == (4096, 64, 8, 4, 192, 128, 128, 2048, 8, 16384)


# ------------------------------------------------------------------ the engine
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


class Probe(nn.Module):
    """The model with its logits handed to the test as they are computed."""

    config: MiMoV2Config
    seen = []

    @nn.compact
    def __call__(self, input_ids, **kw):
        logits = MiMoV2ForCausalLM(self.config, name="lm")(input_ids, **kw)
        jax.debug.callback(lambda x: Probe.seen.append(np.asarray(x)), logits, ordered=True)
        return logits


@pytest.mark.parametrize("paged_attention", ["fused", "gather"])
def test_prefill_in_a_bucket_then_decode_past_the_window_gives_the_reference_logits(
        cfg, model_cfg, params, ref_params, paged_attention):
    """Four requests admitted together in one padded bucket, prompts of 3, 8
    (the window), 21 and 31 tokens (the window and the sinks on the sliding
    layers, causal attention on the two full ones, each prompt's last 8 keys
    and values into its rings), then 20 decode turns: every ring wraps at
    least twice while the full layers read the paged pool at their own head
    count and value width (the fused kernel with the rings' sinks)."""
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


def test_cache_tree_holds_rings_at_their_heads_beside_the_pool_at_its_own(cfg, model_cfg, params):
    engine = engine_for(MiMoV2ForCausalLM(model_cfg), params)
    flat = jax.tree_util.tree_flatten_with_path(engine._cache)[0]
    names = sorted(leaf_name(path) for path, _ in flat)
    assert names == sorted(list(WINDOW_LEAVES) * 5 + ["cached_key", "cached_value"] * 2 + ["cache_index"] * 2)
    w = model_cfg.sliding_window
    widths = {"window_key": 2 * 24, "window_value": 2 * 16, "cached_key": 24, "cached_value": 16}
    for path, leaf in flat:
        name = leaf_name(path)
        if name in WINDOW_LEAVES:
            assert leaf.shape == (4, w, widths[name])
        elif name != "cache_index":
            assert leaf.shape[1:] == (16, widths[name])  # the value pool stored 16 wide, not 24
    serve(engine, prompts_of(cfg, (10, 12)), [12, 5])
    stats = engine.memory_stats()
    rings = 5 * 4 * w * (2 * 24 + 2 * 16) * 4
    assert stats["slot_state_bytes"] == rings == state_nbytes(engine._cache, WINDOW_LEAVES)
    assert stats["block_pool/pool_bytes"] + rings == tree_nbytes(engine._cache)
    counters, steps = engine.metrics.step_counters, engine.metrics.counted_steps.value
    assert set(counters) == set(STEP_COUNTERS) and steps >= 4
    assert 0 < counters["window_rows"] <= steps * 2 * 5 * w


@pytest.mark.parametrize("argument, named", [
    ({"prefix_cache": True}, "prefix_cache"), ({"kv_tier": True}, "kv_tier"),
    ({"speculation": 2}, "speculation"), ({"mesh": (1, 1)}, "mesh")])
def test_rings_are_refused_what_needs_a_token_range(model_cfg, params, argument, named):
    with pytest.raises(ValueError, match=rf"per-slot recurrent state.*{named} is not supported"):
        engine_for(MiMoV2ForCausalLM(model_cfg), params, **argument)
