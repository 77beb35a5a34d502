"""The delta rule's decode update as one Pallas pass over the state
(`ops/gated_delta.delta_step_kernel`), run here under the Pallas interpreter
against the XLA body that every other backend keeps: both forms of the decay,
the published head shape (32 heads of 128 x 128) at several slot counts and
head blocks, live and finished rows, several steps in a row. Then the two
models' decode path with the TPU's dispatch taken: it runs through the
kernel and tallies it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.models.ling3 import KimiDeltaAttention, Ling3Config
from accelerate_tpu.models.qwen3_next import GatedDeltaNet, Qwen3NextConfig
from accelerate_tpu.ops import gated_delta
from accelerate_tpu.utils import environment

H, D = 32, 128  # both delta-rule cells' heads and key = value width
STEPS = 3


def step_inputs(slots, per_channel, key, h=H, d=D):
    ks = jax.random.split(jax.random.key(key), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (slots, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (slots, h, d)))
    v = jax.random.normal(ks[2], (slots, h, d))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (slots, h, d) if per_channel else (slots, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (slots, h)))
    return q, k, v, g, beta


def bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("vmem, head_block", [(gated_delta.DELTA_STEP_VMEM, H), (2 * 2**20, 8)],
                         ids=["all-heads", "head-block-8"])
@pytest.mark.parametrize("slots", [1, 3, 8])
@pytest.mark.parametrize("per_channel", [False, True], ids=["scalar-decay", "channel-decay"])
def test_kernel_matches_the_xla_body(monkeypatch, per_channel, slots, vmem, head_block):
    """``STEPS`` decode steps from one state through the interpreted kernel
    and through the XLA body. Every third row is finished (``g = 0, beta =
    0``, as the models pass it) and the first of them holds ``inf`` and
    ``nan``: its state comes back bit-equal; the live rows match to float32
    rounding. A single slot is finished on the middle step only. A smaller
    VMEM budget gives a grid of head blocks of 8."""
    monkeypatch.setattr(gated_delta, "DELTA_STEP_VMEM", vmem)
    assert gated_delta.delta_head_block(H, D, D) == head_block
    kernel = jax.jit(functools.partial(gated_delta.delta_step_kernel, interpret=True))
    state = jax.random.normal(jax.random.key(slots), (slots, H, D, D)) * 0.3
    if slots > 1:  # row 1 is finished on every step
        state = state.at[1, 3, 5, 7].set(jnp.inf).at[1, 30, 0, 127].set(jnp.nan)
    want = got = state
    for t in range(STEPS):
        live = (jnp.arange(slots) % 3 != 1) if slots > 1 else jnp.asarray([t != 1])
        q, k, v, g, beta = step_inputs(slots, per_channel, key=10 * slots + t)
        g, beta = gated_delta.mask_pad(g[:, None], beta[:, None], live.astype(jnp.int32))
        g, beta = g[:, 0], beta[:, 0]
        before, before_want = got, want
        want, want_o = gated_delta.gated_delta_step(want, q, k, v, g, beta)
        got, got_o = kernel(got, q, k, v, g, beta)
        rows = np.asarray(live)
        np.testing.assert_allclose(got[rows], want[rows], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got_o[rows], want_o[rows], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(bits(got[~rows]), bits(before[~rows]))
        np.testing.assert_array_equal(bits(want[~rows]), bits(before_want[~rows]))
    assert float(jnp.abs(want_o[rows]).max()) > 0.01
    if slots > 1:
        assert np.isinf(np.asarray(got[1, 3, 5, 7])) and np.isnan(np.asarray(got[1, 30, 0, 127]))


MIXERS = {
    "qwen3-next": (GatedDeltaNet, Qwen3NextConfig.tiny, "delta_state", False),
    "ling3": (KimiDeltaAttention, Ling3Config.tiny, "kda_state", True),
}


@pytest.mark.parametrize("model", list(MIXERS))
def test_the_models_decode_step_runs_through_the_kernel(monkeypatch, model):
    """A mixer's one-token decode with the TPU's dispatch (`on_tpu_platform`
    true, the kernel interpreted) gives what the XLA body gives, counts one
    ``pallas`` update, and leaves a finished slot's state as it was."""
    mixer, tiny, leaf, per_channel = MIXERS[model]
    module = mixer(tiny())
    slots, hidden = 3, tiny().hidden_size
    x = jax.random.normal(jax.random.key(1), (slots, 1, hidden), jnp.float32)
    variables = module.init(jax.random.key(0), x, decode=True)
    cache = jax.tree.map(lambda a: jax.random.normal(jax.random.key(2), a.shape, a.dtype), variables["cache"])
    live = jnp.asarray([True, False, True])

    def decode():
        return module.apply({"params": variables["params"], "cache": cache}, x, decode=True,
                            cache_write_mask=live, mutable=["cache"])

    want, want_cache = decode()
    monkeypatch.setattr(environment, "on_tpu_platform", lambda: True)
    monkeypatch.setattr(gated_delta, "delta_step_kernel",
                        functools.partial(gated_delta.delta_step_kernel, interpret=True))
    before = gated_delta.DELTA_STEP_TRACES.copy()
    got, got_cache = decode()
    assert gated_delta.DELTA_STEP_TRACES - before == {("pallas", slots): 1}
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name in (leaf, "conv_state"):
        old, new = cache[name], got_cache["cache"][name]
        np.testing.assert_allclose(new, want_cache["cache"][name], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(bits(new[1]), bits(old[1]))
        assert not np.array_equal(np.asarray(new[0]), np.asarray(old[0]))
    assert (cache[leaf].ndim == 4) and (per_channel == (model == "ling3"))
