"""MoE layer: routing correctness vs naive per-token loop, EP sharding, training."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu.ops import moe
from accelerate_tpu.ops.moe import MoEConfig, MoEMLP, held_experts_mlp, moe_sharding_rules
from accelerate_tpu.parallel.mesh import ParallelismConfig
from accelerate_tpu.state import AcceleratorState, GradientState


def _cfg(**kw):
    return MoEConfig(**{**dict(num_experts=4, top_k=2, hidden_size=16, intermediate_size=32,
                               capacity_factor=2.0, dtype=jnp.float32), **kw})


def _naive_moe(params, x, cfg):
    """Per-token loop reference (no capacity dropping when capacity is ample)."""
    b, s, e = x.shape
    xt = np.asarray(x).reshape(-1, e)
    router = np.asarray(params["router"]["kernel"])
    w_up = np.asarray(params["w_up"])
    w_down = np.asarray(params["w_down"])
    logits = xt @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        top = np.argsort(-probs[t])[: cfg.top_k]
        gates = probs[t][top] / probs[t][top].sum()
        for gate, eidx in zip(gates, top):
            h = xt[t] @ w_up[eidx]
            # approximate gelu to match nn.gelu(approximate=True)
            h = 0.5 * h * (1 + np.tanh(np.sqrt(2 / np.pi) * (h + 0.044715 * h**3)))
            out[t] += gate * (h @ w_down[eidx])
    return out.reshape(b, s, e)


def test_moe_matches_naive_loop():
    cfg = _cfg()
    module = MoEMLP(cfg)
    x = jax.random.normal(jax.random.key(0), (2, 8, 16))
    params = module.init(jax.random.key(1), x)["params"]
    out = module.apply({"params": params}, x)
    ref = _naive_moe(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def test_moe_capacity_drops_tokens():
    cfg = _cfg(capacity_factor=0.25, top_k=1)
    module = MoEMLP(cfg)
    x = jax.random.normal(jax.random.key(2), (2, 8, 16))
    params = module.init(jax.random.key(3), x)["params"]
    out = module.apply({"params": params}, x)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())


def test_moe_aux_loss_sown():
    cfg = _cfg()
    module = MoEMLP(cfg)
    x = jax.random.normal(jax.random.key(4), (2, 8, 16))
    params = module.init(jax.random.key(5), x)["params"]
    _, inter = module.apply({"params": params}, x, mutable=["intermediates"])
    aux = inter["intermediates"]["aux_loss"]
    assert float(aux) > 0


def test_moe_ep_sharded_matches_replicated():
    AcceleratorState._reset_state()
    GradientState._reset_state()
    from accelerate_tpu.accelerator import Accelerator

    cfg = _cfg()
    module = MoEMLP(cfg)
    x = jax.random.normal(jax.random.key(6), (4, 8, 16))
    params = module.init(jax.random.key(7), x)["params"]
    ref = module.apply({"params": params}, x)
    acc = Accelerator(
        parallelism_config=ParallelismConfig(data_parallel_size=2, tensor_size=4),
        sharding_rules=moe_sharding_rules(),
    )
    model = acc.prepare_model(((lambda p, x: module.apply({"params": p}, x)), params))
    out = model(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
    # expert dim actually sharded
    w = model.params["w_up"]
    assert w.sharding.shard_shape(w.shape)[0] == cfg.num_experts // 4
    AcceleratorState._reset_state()
    GradientState._reset_state()


def test_moe_trains():
    cfg = _cfg()
    module = MoEMLP(cfg)
    key = jax.random.key(8)
    x = jax.random.normal(key, (4, 8, 16))
    target = jnp.tanh(x) * 2.0
    params = module.init(jax.random.key(9), x)["params"]
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            out, inter = module.apply({"params": p}, x, mutable=["intermediates"])
            aux = inter["intermediates"]["aux_loss"]
            return ((out - target) ** 2).mean() + aux

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7


# ------------------------------------------- the held experts' grouped products
def _small_tiles(k, n):
    return 16, min(k, 128), min(n, 128)


HELD = 6  # of a router 8 wide: ids 6 and 7 are another chip's


@pytest.mark.parametrize("picks_of, tokens, hidden, width, tiling", [
    # expert ids a token's picks cycle through
    pytest.param([(0, 2), (2, 5), (5, 0)], 24, 128, 64, _small_tiles, id="empty-groups-between-full-ones"),
    pytest.param([(6, 7)], 16, 128, 64, _small_tiles, id="every-pick-absent"),
    pytest.param([(6, 7)], 13, 64, 64, None, id="every-pick-absent-padded-rows"),
    pytest.param([(1, 3)] * 9 + [(0, 7)], 30, 128, 64, _small_tiles, id="a-group-straddles-row-tiles"),
    pytest.param([(0, 1, 4), (2, 7, 5)], 13, 64, 64, None, id="rows-not-a-multiple-of-128"),
    pytest.param([(0, 1, 4), (2, 7, 5)], 13, 128, 64, _small_tiles, id="rows-not-a-multiple-of-16"),
    pytest.param([(3, 4), (4, 6), (0, 3)], 24, 192, 64, _small_tiles, id="k-not-a-multiple-of-its-tile"),
    # the rule itself under a weight tile of 128 x 128: gate and up [128, 256]
    # in tiles of the whole k by half of n (the split Ling 3.0 flash's take),
    # down whole
    pytest.param([(0, 3), (3, 5), (1, 0)], 40, 128, 128, 128 * 128, id="the-rule-whole-k-split-n"),
])
def test_pallas_grouped_product_matches_ragged_dot(monkeypatch, picks_of, tokens, hidden, width, tiling):
    """`held_experts_mlp` through the Pallas grouped matmul (interpreted; the
    path a TPU takes) against the same call through `jax.lax.ragged_dot`.
    ``tiling`` is a tiling function in place of `grouped_tiling`, or an int:
    the weight tile `grouped_tiling` itself is held to."""
    from jax.experimental.pallas.ops.tpu import megablox

    from accelerate_tpu.utils import environment

    ks = jax.random.split(jax.random.key(len(picks_of) + tokens), 4)
    x = jax.random.normal(ks[0], (tokens, hidden), jnp.float32).astype(jnp.bfloat16)
    idx = jnp.asarray([picks_of[t % len(picks_of)] for t in range(tokens)], jnp.int32)
    weights = jax.nn.softmax(jax.random.normal(ks[1], idx.shape), -1)
    w_gate_up = (jax.random.normal(ks[2], (HELD, hidden, 2 * width)) * 0.1).astype(jnp.bfloat16)
    w_down = (jax.random.normal(ks[3], (HELD, width, hidden)) * 0.1).astype(jnp.bfloat16)
    want, picks, touched = held_experts_mlp(x, weights, idx, w_gate_up, w_down)

    monkeypatch.setattr(environment, "on_tpu_platform", lambda: True)
    monkeypatch.setattr(megablox, "gmm", functools.partial(megablox.gmm, interpret=True))
    if isinstance(tiling, int):
        monkeypatch.setattr(moe, "GMM_WEIGHT_TILE", tiling)
        assert moe.grouped_tiling(hidden, 2 * width) == (128, hidden, width)
        assert moe.grouped_tiling(width, hidden) == (128, width, hidden)
    elif tiling is not None:
        monkeypatch.setattr(moe, "grouped_tiling", tiling)
    before = moe.GROUPED_PRODUCT_TRACES.copy()
    got, got_picks, got_touched = held_experts_mlp(x, weights, idx, w_gate_up, w_down)
    assert moe.GROUPED_PRODUCT_TRACES - before == {("pallas", idx.size): 2}
    assert (int(got_picks), int(got_touched)) == (int(picks), int(touched))
    assert np.isfinite(np.asarray(got)).all()
    if int(picks) == 0:
        np.testing.assert_array_equal(got, np.zeros_like(got))  # no tile visited: exactly nothing
    else:
        assert float(jnp.abs(want).max()) > 1e-2
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


# (k, n) of each expert matrix of the three MoE serving cells: gate and up, down
QWEN3_NEXT = [(2048, 1024), (512, 2048)]
KIMI_K2 = [(7168, 4096), (2048, 7168)]
LING3 = [(2560, 1536), (768, 2560)]


@pytest.mark.parametrize("k, n, tiles", [
    pytest.param(*QWEN3_NEXT[0], (2048, 1024), id="qwen3next-gate-up"),
    pytest.param(*QWEN3_NEXT[1], (512, 2048), id="qwen3next-down"),
    pytest.param(*KIMI_K2[0], (2048, 1024), id="kimik2-gate-up"),
    pytest.param(*KIMI_K2[1], (2048, 1024), id="kimik2-down"),
    pytest.param(*LING3[0], (2560, 768), id="ling3-gate-up"),
    pytest.param(*LING3[1], (768, 2560), id="ling3-down"),
])
def test_grouped_tiling_at_the_moe_cells(k, n, tiles):
    """The weight tile at each MoE cell's matrices: at most `GMM_WEIGHT_TILE`
    elements, each side the whole dimension or a multiple of 128, and never
    a tile whose product over its overhang outlasts the read of the weights
    (Ling 3.0 flash's gate and up took 2,048 x 1,024 over 2.13 times their
    area). Every tile divides its matrix but Kimi K2's gate and up, which
    overhang k 1.14 times and keep their tile; Qwen3-Next's keep theirs,
    whole."""
    row_tile, tile_k, tile_n = moe.grouped_tiling(k, n)
    assert (row_tile, tile_k, tile_n) == (moe.GMM_ROW_TILE, *tiles)
    assert tile_k * tile_n <= moe.GMM_WEIGHT_TILE
    for side, dim in ((tile_k, k), (tile_n, n)):
        assert side == dim or side % 128 == 0
    computed = -(-k // tile_k) * tile_k * -(-n // tile_n) * tile_n
    assert row_tile * computed <= moe.GMM_FLOPS_PER_BYTE * k * n
    assert (k % tile_k, n % tile_n) == ((1024, 0) if (k, n) == KIMI_K2[0] else (0, 0))
    if (k, n) in QWEN3_NEXT or (k, n) in KIMI_K2:  # 2,048 of k at most, by the rest of the budget
        assert (tile_k, tile_n) == (min(k, 2048), min(n, moe.GMM_WEIGHT_TILE // min(k, 2048)))


@pytest.mark.parametrize("k, n, tiles", [
    # no multiple of 128 divides 20,000; the 2,048 x 1,024 tile overhangs k by
    # 2% and keeps its masked remainder
    pytest.param(20000, 4096, (2048, 1024), id="k-without-a-128-divisor"),
    # the 2,048 x 1,024 tile would compute 2.98 times the area; no multiple of
    # 128 divides 1,100, so n is whole and k the largest divisor beside it
    pytest.param(2560, 1100, (1280, 1100), id="n-without-a-128-divisor"),
    # 3.63 times the area, and no pair of dividing sides fits 4 MB: the
    # overhanging tile stays
    pytest.param(2100, 1100, (2048, 1024), id="no-dividing-tile-fits"),
])
def test_grouped_tiling_falls_back(k, n, tiles):
    assert moe.grouped_tiling(k, n) == (moe.GMM_ROW_TILE, *tiles)


# ------------------------------------------ the held picks in windows of rows
ROUTER = 16  # a router this wide, of which `HELD` experts from `first` are held here
WINDOW_HIDDEN, WINDOW_WIDTH = 64, 32  # 704 bytes a pick in bfloat16: `expert_pass_rows` reads n at n * 704


def _window_picks(tokens, k, held_a_token, first, held_ids=None, seed=0):
    """``[tokens, k]`` distinct ids a token, ``held_a_token`` of them among
    ``held_ids`` (default: every held expert), the rest absent ones."""
    rng = np.random.default_rng(seed)
    held_ids = np.arange(first, first + HELD) if held_ids is None else np.asarray(held_ids) + first
    absent = np.asarray([e for e in range(ROUTER) if not first <= e < first + HELD])
    rows = [np.concatenate([rng.choice(held_ids, held_a_token, replace=False),
                            rng.choice(absent, k - held_a_token, replace=False)]) for _ in range(tokens)]
    return jnp.asarray(np.stack([rng.permutation(r) for r in rows]), jnp.int32)


@pytest.mark.parametrize("tokens, k, held_a_token, first, held_ids, router, cap, window, windows, path", [
    pytest.param(64, 4, 1, 0, None, None, 128, 128, 1, "ragged_dot", id="one-window"),
    pytest.param(64, 4, 3, 0, None, None, 128, 128, 2, "ragged_dot", id="two-windows"),
    pytest.param(96, 4, 3, 0, None, None, 128, 128, 3, "ragged_dot", id="three-windows"),
    # experts 0 and 1 take 75 picks each: rows 75-149, expert 1's, straddle 128
    pytest.param(75, 4, 2, 0, (0, 1), None, 128, 128, 2, "ragged_dot", id="a-group-straddles-two-windows"),
    pytest.param(80, 4, 4, 2, None, None, 128, 128, 3, "ragged_dot", id="every-pick-held"),
    pytest.param(64, 4, 0, 0, None, None, 128, 128, 0, "ragged_dot", id="no-pick-held"),
    pytest.param(64, 4, 2, 5, None, None, 128, 128, 1, "ragged_dot", id="first-expert-5"),
    # 128 picks: one window holds them all, so the single pass, with no loop
    pytest.param(32, 4, 2, 0, None, None, 128, None, None, "ragged_dot", id="picks-fit-one-window"),
    # a router 16 wide over 6 held experts: 640 picks bring 240 held ones
    # expected, a window of 256 under a budget of 512
    pytest.param(160, 4, 1, 0, None, ROUTER, 512, 256, 1, "ragged_dot", id="expected-held-picks-one-window"),
    pytest.param(160, 4, 2, 0, None, ROUTER, 512, 256, 2, "ragged_dot", id="expected-held-picks-two-windows"),
    # 384 picks bring 144 expected, 256 rows to the tile, over the budget of 128
    pytest.param(96, 4, 3, 0, None, ROUTER, 128, 128, 3, "ragged_dot", id="the-budget-under-the-expected"),
    # the windows through the Pallas grouped matmul (interpreted), row tiles of 16
    pytest.param(75, 4, 2, 1, (0, 1), None, 128, 128, 2, "pallas", id="pallas-a-group-straddles-two-windows"),
])
def test_held_experts_by_windows_match_one_pass(monkeypatch, tokens, k, held_a_token, first, held_ids, router,
                                                cap, window, windows, path):
    """`held_experts_mlp` with `EXPERT_PASS_BYTES` cut to ``cap`` picks,
    against the same call in one pass: each product is the same, so the sums
    agree to float32 rounding, and the counts are the same. A window takes
    the held picks expected of a ``router``-wide router (``None``: every pick
    may be held), at most ``cap``. ``windows`` is how many the held picks
    fill; ``None`` where the ``T * k`` picks fit the budget, which traces the
    single pass and no loop."""
    if path == "pallas":
        from jax.experimental.pallas.ops.tpu import megablox

        from accelerate_tpu.utils import environment

        monkeypatch.setattr(environment, "on_tpu_platform", lambda: True)
        monkeypatch.setattr(megablox, "gmm", functools.partial(megablox.gmm, interpret=True))
        monkeypatch.setattr(moe, "grouped_tiling", _small_tiles)
    ks = jax.random.split(jax.random.key(tokens * k + held_a_token), 4)
    x = jax.random.normal(ks[0], (tokens, WINDOW_HIDDEN), jnp.float32).astype(jnp.bfloat16)
    idx = _window_picks(tokens, k, held_a_token, first, held_ids)
    weights = jax.nn.softmax(jax.random.normal(ks[1], idx.shape), -1)
    w_gate_up = (jax.random.normal(ks[2], (HELD, WINDOW_HIDDEN, 2 * WINDOW_WIDTH)) * 0.1).astype(jnp.bfloat16)
    w_down = (jax.random.normal(ks[3], (HELD, WINDOW_WIDTH, WINDOW_HIDDEN)) * 0.1).astype(jnp.bfloat16)
    args = (x, weights, idx, w_gate_up, w_down)
    call = lambda: lambda *a: held_experts_mlp(  # noqa: E731  (a new trace each)
        *a, first_expert=first, n_experts=router)
    want, picks, touched = jax.jit(call())(*args)

    monkeypatch.setattr(moe, "EXPERT_PASS_BYTES", cap * 704)
    assert moe.expert_pass_rows(WINDOW_HIDDEN, WINDOW_WIDTH, jnp.bfloat16) == cap
    looped = "while" in str(jax.make_jaxpr(call())(*args))
    before = moe.GROUPED_PRODUCT_TRACES.copy()
    got, got_picks, got_touched = jax.jit(call())(*args)
    assert looped == (windows is not None) == (tokens * k > cap)
    assert moe.GROUPED_PRODUCT_TRACES - before == (
        {("window", window): 1, (path, window): 2} if looped else {(path, tokens * k): 2})
    assert (int(got_picks), int(got_touched)) == (int(picks), int(touched))
    assert int(picks) == tokens * held_a_token
    if windows is not None:
        assert -(-int(picks) // window) == windows
    if held_ids is not None:  # the straddle the case is named for
        sizes = np.bincount(np.asarray(idx).ravel(), minlength=ROUTER)[first: first + HELD]
        assert any(end - size < window < end for end, size in zip(np.cumsum(sizes), sizes))
    if int(picks) == 0:
        np.testing.assert_array_equal(got, np.zeros_like(got))
    else:
        assert float(jnp.abs(want).max()) > 1e-2
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("hidden, width, held, router, k, tokens, window", [
    # (widths, held experts of the router's, picks a token) of each MoE serving cell
    pytest.param(2048, 512, 256, 512, 10, 6144, None, id="qwen3-next-4x1536"),
    pytest.param(7168, 2048, 12, 384, 8, 256, None, id="kimi-k2-decode-step"),
    pytest.param(7168, 2048, 12, 384, 8, 1536, None, id="kimi-k2-1x1536"),
    pytest.param(7168, 2048, 12, 384, 8, 2048, None, id="kimi-k2-4x512"),
    pytest.param(7168, 2048, 12, 384, 8, 3072, 768, id="kimi-k2-2x1536"),
    pytest.param(7168, 2048, 12, 384, 8, 6144, 1536, id="kimi-k2-4x1536"),
    pytest.param(2560, 768, 128, 512, 8, 3072, None, id="ling3-2x1536"),
    pytest.param(2560, 768, 128, 512, 8, 6144, 12288, id="ling3-4x1536"),
    pytest.param(6144, 2048, 16, 128, 8, 128, None, id="k-exaone-decode-step"),
    pytest.param(6144, 2048, 16, 128, 8, 4096, 4096, id="k-exaone-4096"),
    pytest.param(6144, 2048, 16, 128, 8, 8192, 8192, id="k-exaone-8192"),
])
def test_held_experts_pass_or_windows_at_the_cells_shapes(hidden, width, held, router, k, tokens, window):
    """At the serving cells' widths (traced, not run): every decode step and
    every segment up to Qwen3-Next's 4 x 1,536-token admit goes through the
    single pass; a longer one through windows of the held picks it is
    expected to bring (``window``)."""
    sds = jax.ShapeDtypeStruct
    args = (sds((tokens, hidden), jnp.bfloat16), sds((tokens, k), jnp.float32), sds((tokens, k), jnp.int32),
            sds((held, hidden, 2 * width), jnp.bfloat16), sds((held, width, hidden), jnp.bfloat16))
    before = moe.GROUPED_PRODUCT_TRACES.copy()
    jaxpr = str(jax.make_jaxpr(lambda *a: held_experts_mlp(*a, n_experts=router))(*args))
    assert ("while" in jaxpr) == (window is not None)
    assert moe.GROUPED_PRODUCT_TRACES - before == (
        {("window", window): 1, ("ragged_dot", window): 2} if window else {("ragged_dot", tokens * k): 2})
