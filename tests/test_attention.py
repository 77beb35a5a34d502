"""Flash-attention kernel vs XLA reference: forward and gradients, causal and not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.ops.attention import dot_product_attention
from accelerate_tpu.ops.flash_attention import flash_attention


def _rand(shape, key):
    return jax.random.normal(jax.random.key(key), shape, dtype=jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 128, 4, 64), (1, 256, 2, 32)])
def test_flash_matches_xla_forward(causal, shape):
    b, s, h, d = shape
    q, k, v = _rand(shape, 0), _rand(shape, 1), _rand(shape, 2)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match(causal):
    shape = (1, 128, 2, 32)
    q, k, v = _rand(shape, 3), _rand(shape, 4), _rand(shape, 5)

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=causal) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4, rtol=5e-4)


def test_flash_bf16():
    shape = (1, 128, 2, 64)
    q = _rand(shape, 6).astype(jnp.bfloat16)
    k = _rand(shape, 7).astype(jnp.bfloat16)
    v = _rand(shape, 8).astype(jnp.bfloat16)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32), atol=3e-2, rtol=3e-2
    )


def test_flash_rejects_indivisible():
    shape = (1, 100, 2, 32)
    q = _rand(shape, 9)
    with pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=64, block_kv=64)


@pytest.mark.parametrize("shape,block", [((2, 128, 4, 64), 32), ((1, 256, 2, 32), 64)])
def test_flash_triangle_matches_xla_forward(shape, block):
    """Lower-triangle causal grid (scalar-prefetch block maps) vs XLA."""
    b, s, h, d = shape
    q, k, v = _rand(shape, 0), _rand(shape, 1), _rand(shape, 2)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, triangle_block=block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_triangle_gradients_match():
    shape = (1, 128, 2, 32)
    q, k, v = _rand(shape, 3), _rand(shape, 4), _rand(shape, 5)

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

    def loss_tri(q, k, v):
        return (flash_attention(q, k, v, causal=True, triangle_block=32) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_tri = jax.grad(loss_tri, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_tri, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4, rtol=5e-4)


def test_flash_triangle_single_block_and_env(monkeypatch):
    """block == seq degenerates to one diagonal cell per (b, h); env knob routes."""
    shape = (1, 64, 2, 32)
    q, k, v = _rand(shape, 6), _rand(shape, 7), _rand(shape, 8)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, triangle_block=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    monkeypatch.setenv("ACCELERATE_TPU_FLASH_TRIANGLE", "32")
    out_env = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_env), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_triangle_explicit_arg_is_strict():
    """An explicit triangle_block must error on configs it can't serve —
    silently measuring the rectangular kernel would poison perf sweeps."""
    q = _rand((1, 64, 2, 32), 9)
    kx = _rand((1, 128, 2, 32), 10)
    with pytest.raises(ValueError, match="causal self-attention"):
        flash_attention(q, kx, kx, causal=False, triangle_block=32)
    with pytest.raises(ValueError, match="mutually exclusive"):
        flash_attention(q, q, q, causal=True, triangle_block=32, block_q=32)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q, q, causal=True, triangle_block=48)


def test_flash_triangle_env_knob_falls_back_for_cross_attention(monkeypatch):
    """The env knob is a global default: cross-attention in the same model must
    silently keep the rectangular path."""
    monkeypatch.setenv("ACCELERATE_TPU_FLASH_TRIANGLE", "32")
    q = _rand((1, 64, 2, 32), 9)
    k = v = _rand((1, 128, 2, 32), 10)
    ref = dot_product_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


class TestSlidingWindow:
    """Sliding-window (band) attention: query i attends to keys in (i-W, i]."""

    def _ref(self, q, k, v, window):
        s = q.shape[1]
        q_idx = np.arange(s)[:, None]
        k_idx = np.arange(s)[None, :]
        mask = (k_idx <= q_idx) & (k_idx > q_idx - window)
        return dot_product_attention(q, k, v, mask=mask)

    @pytest.mark.parametrize("window", [1, 17, 48, 200])
    def test_xla_window_matches_explicit_mask(self, window):
        shape = (1, 96, 2, 32)
        q, k, v = _rand(shape, 11), _rand(shape, 12), _rand(shape, 13)
        ref = self._ref(q, k, v, window)
        out = dot_product_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("window,block", [(32, 32), (48, 32), (100, 32), (128, 64)])
    def test_band_kernel_matches_xla(self, window, block):
        shape = (2, 128, 2, 32)
        q, k, v = _rand(shape, 14), _rand(shape, 15), _rand(shape, 16)
        ref = dot_product_attention(q, k, v, causal=True, window=window)
        out = flash_attention(q, k, v, causal=True, window=window, triangle_block=block)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_band_kernel_gradients_match(self):
        shape = (1, 128, 2, 32)
        q, k, v = _rand(shape, 17), _rand(shape, 18), _rand(shape, 19)

        def loss_ref(q, k, v):
            return (dot_product_attention(q, k, v, causal=True, window=48) ** 2).sum()

        def loss_band(q, k, v):
            return (flash_attention(q, k, v, causal=True, window=48, triangle_block=32) ** 2).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_band = jax.grad(loss_band, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_band, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4, rtol=5e-4)

    def test_window_untileable_seq_raises(self):
        # prime seq > 512 has no block divisor >= 8 under the 512 cap: the
        # default band grid would be 1-wide (pathological) — the kernel must
        # refuse with guidance instead
        shape = (1, 1031, 2, 32)
        q, k, v = _rand(shape, 31), _rand(shape, 32), _rand(shape, 33)
        with pytest.raises(ValueError, match="block divisor"):
            flash_attention(q, k, v, causal=True, window=16)

    def test_dispatcher_routes_window(self):
        shape = (1, 64, 2, 32)
        q, k, v = _rand(shape, 20), _rand(shape, 21), _rand(shape, 22)
        from accelerate_tpu.ops.attention import attention

        ref = dot_product_attention(q, k, v, causal=True, window=16)
        out = attention(q, k, v, causal=True, window=16, implementation="xla")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_window_requires_causal_self_attention(self):
        q = _rand((1, 64, 2, 32), 23)
        with pytest.raises(ValueError, match="causal self-attention"):
            flash_attention(q, q, q, causal=False, window=16)


def test_llama_sliding_window_config():
    """sliding_window plumbs through LlamaConfig into the attention mask —
    a tiny model's logits must differ from the unwindowed model past W."""
    import jax.numpy as jnp

    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    ids = np.arange(24)[None, :] % 7
    outs = {}
    for w in (None, 4):
        cfg = LlamaConfig.tiny(dtype=jnp.float32, sliding_window=w, attention_impl="xla")
        m = LlamaForCausalLM(cfg)
        params = m.init(jax.random.key(0), jnp.asarray(ids, jnp.int32))["params"]
        outs[w] = np.asarray(m.apply({"params": params}, jnp.asarray(ids, jnp.int32)))
    # same weights, same prefix: first W positions identical, later ones differ
    np.testing.assert_allclose(outs[None][:, :4], outs[4][:, :4], atol=1e-5)
    assert np.abs(outs[None][:, 10:] - outs[4][:, 10:]).max() > 1e-4


def test_window_nondivisible_seq_picks_valid_block():
    """window with sq not a multiple of 512 must auto-pick a dividing block."""
    shape = (1, 96, 2, 32)
    q, k, v = _rand(shape, 24), _rand(shape, 25), _rand(shape, 26)
    ref = dot_product_attention(q, k, v, causal=True, window=40)
    out = flash_attention(q, k, v, causal=True, window=40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_window_without_causal_raises_on_xla_too():
    q = _rand((1, 64, 2, 32), 27)
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, q, q, causal=False, window=16)


def test_ring_attention_rejects_sliding_window():
    import jax.numpy as jnp

    from accelerate_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(dtype=jnp.float32, sliding_window=4, attention_impl="ring")
    m = LlamaForCausalLM(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(NotImplementedError, match="sliding_window"):
        m.init(jax.random.key(0), ids)


class TestGQA:
    """Grouped-query attention: the band grid reads kv head h//groups directly;
    K/V are never repeated in HBM and dk/dv come back in kv-head shape."""

    def _ref(self, q, k, v, groups, window=None):
        k_rep = jnp.repeat(k, groups, axis=2)
        v_rep = jnp.repeat(v, groups, axis=2)
        return dot_product_attention(q, k_rep, v_rep, causal=True, window=window)

    @pytest.mark.parametrize("groups,window", [(2, None), (4, None), (2, 48)])
    def test_band_gqa_matches_repeated_xla(self, groups, window):
        s, hq, d = 128, 4, 32
        q = _rand((2, s, hq, d), 30)
        k = _rand((2, s, hq // groups, d), 31)
        v = _rand((2, s, hq // groups, d), 32)
        ref = self._ref(q, k, v, groups, window)
        out = flash_attention(q, k, v, causal=True, window=window, triangle_block=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_band_gqa_gradients_match_kv_head_shape(self):
        s, hq, groups, d = 128, 4, 2, 32
        q = _rand((1, s, hq, d), 33)
        k = _rand((1, s, hq // groups, d), 34)
        v = _rand((1, s, hq // groups, d), 35)

        def loss_ref(q, k, v):
            return (self._ref(q, k, v, groups) ** 2).sum()

        def loss_band(q, k, v):
            return (flash_attention(q, k, v, causal=True, triangle_block=32) ** 2).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_band = jax.grad(loss_band, argnums=(0, 1, 2))(q, k, v)
        assert g_band[1].shape == k.shape and g_band[2].shape == v.shape
        for a, b_ in zip(g_band, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4, rtol=5e-4)

    def test_rect_path_repeats_internally(self):
        s, hq, groups, d = 64, 4, 2, 32
        q = _rand((1, s, hq, d), 36)
        k = _rand((1, s, hq // groups, d), 37)
        v = _rand((1, s, hq // groups, d), 38)
        ref = self._ref(q, k, v, groups)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_rejects_nondivisible_heads(self):
        q = _rand((1, 64, 4, 32), 39)
        k = _rand((1, 64, 3, 32), 40)
        with pytest.raises(ValueError, match="multiple of kv heads"):
            flash_attention(q, k, k, causal=True, triangle_block=32)


@pytest.mark.parametrize("nq,block,window", [
    (1, 64, None), (4, 32, None), (8, 16, None),
    (4, 32, 1), (4, 32, 32), (4, 32, 40), (8, 16, 100), (8, 16, 1000),
])
def test_band_map_enumeration_properties(nq, block, window):
    """Structural invariants of the scalar-prefetch maps: every in-band block
    appears exactly once, flags mark exactly the accumulator boundaries, and
    row/column enumerations cover the same cell set."""
    from accelerate_tpu.ops.flash_attention import (
        _band_lo,
        _band_maps_col,
        _band_maps_row,
    )

    expected = {
        (iq, ik)
        for iq in range(nq)
        for ik in range(_band_lo(iq, block, window), iq + 1)
    }

    iqm, ikm, first, last = _band_maps_row(nq, block, window)
    cells = list(zip(iqm.tolist(), ikm.tolist()))
    assert sorted(cells) == sorted(expected)
    assert len(set(cells)) == len(cells)
    # row-major: first/last flags fire exactly at each row's band edges
    for t, (iq, ik) in enumerate(cells):
        assert first[t] == (ik == _band_lo(iq, block, window))
        assert last[t] == (ik == iq)
    # every row flushes exactly once
    assert sum(last.tolist()) == nq

    iqm2, ikm2, gm2, first2, last2 = _band_maps_col(nq, block, window, groups=2)
    cells2 = list(zip(gm2.tolist(), iqm2.tolist(), ikm2.tolist()))
    assert sorted(set((iq, ik) for _, iq, ik in cells2)) == sorted(expected)
    # each column's pair sequence is contiguous with exactly one first/one last
    cols = ikm2.tolist()
    for ik in set(cols):
        span = [t for t, c in enumerate(cols) if c == ik]
        assert span == list(range(span[0], span[-1] + 1)), "column not contiguous"
        assert first2[span[0]] == 1 and last2[span[-1]] == 1
        assert sum(first2[t] for t in span) == 1 and sum(last2[t] for t in span) == 1
        # both groups' cells present for this column
        assert {g for g, _, c in cells2 if c == ik} == {0, 1}


def test_flash_stays_sharded_under_tensor_parallel():
    """Under a live TP mesh the dispatcher runs the Pallas kernel per head
    shard via shard_map — XLA cannot partition a custom call, so unwrapped it
    would all-gather and compute attention replicated on every device."""
    import accelerate_tpu as at
    from accelerate_tpu.ops.attention import attention
    from accelerate_tpu.parallel.mesh import ParallelismConfig
    from accelerate_tpu.state import AcceleratorState, GradientState
    from jax.sharding import NamedSharding, PartitionSpec as P

    AcceleratorState._reset_state()
    GradientState._reset_state()
    acc = at.Accelerator(parallelism_config=ParallelismConfig(data_parallel_size=4, tensor_size=2))
    q = _rand((4, 128, 8, 32), 50)
    k = _rand((4, 128, 4, 32), 51)  # GQA 2:1
    v = _rand((4, 128, 4, 32), 52)
    sh = NamedSharding(acc.mesh, P("data", None, "tensor", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    @jax.jit
    def f(q, k, v):
        return attention(q, k, v, causal=True, window=48, implementation="flash",
                         block_q=None, block_kv=None)

    import os
    os.environ["ACCELERATE_TPU_FLASH_TRIANGLE"] = "64"
    try:
        out = f(qs, ks, vs)
    finally:
        os.environ.pop("ACCELERATE_TPU_FLASH_TRIANGLE", None)
    try:
        _run_tp_shard_assertions(out, f, q, k, v, qs, ks, vs)
    finally:
        AcceleratorState._reset_state()
        GradientState._reset_state()


def _run_tp_shard_assertions(out, f, q, k, v, qs, ks, vs):
    from jax.sharding import PartitionSpec as P

    from accelerate_tpu.ops.attention import attention

    assert out.sharding.spec == P("data", None, "tensor", None), out.sharding
    ref = dot_product_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True, window=48
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    # the custom VJP must compose with shard_map (training path)
    def loss_tp(q, k, v):
        return (attention(q, k, v, causal=True, implementation="flash",
                          block_q=None, block_kv=None) ** 2).sum()

    def loss_ref(q, k, v):
        return (dot_product_attention(
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True) ** 2).sum()

    g_tp = jax.jit(jax.grad(loss_tp, argnums=(0, 1, 2)))(qs, ks, vs)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_tp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4, rtol=5e-4)

    # undivisible batch (e.g. batch-1 eval) must fall back, not crash
    q1, k1, v1 = q[:1], k[:1], v[:1]
    out1 = attention(q1, k1, v1, causal=True, implementation="flash",
                     block_q=None, block_kv=None)
    ref1 = dot_product_attention(
        q1, jnp.repeat(k1, 2, axis=2), jnp.repeat(v1, 2, axis=2), causal=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref1), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seq,want", [(1024, "flash"), (2048, "flash"), (1536, "xla"), (512, "xla")])
def test_auto_routes_a_length_the_default_blocks_do_not_divide_to_xla(monkeypatch, seq, want):
    """On a TPU `auto` takes the flash kernel from 1024 tokens on; a length its
    1024-wide rectangular blocks do not divide (a 1536 prompt bucket) raised
    in the kernel and now goes to xla."""
    from accelerate_tpu.ops import flash_attention as fa
    from accelerate_tpu.ops.attention import attention
    from accelerate_tpu.utils import environment

    monkeypatch.setattr(environment, "on_tpu_platform", lambda: True)
    took = []
    monkeypatch.setattr(fa, "flash_attention", lambda q, k, v, **kw: took.append("flash") or q)
    q = k = v = _rand((1, seq, 2, 8), 0)
    out = attention(q, k, v, causal=True)
    assert out.shape == q.shape and (took or ["xla"]) == [want]
