"""The plain reference and the benchmark's weights against the program's
model at a test size: the same numbers in, the same numbers out."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import weights
from reference import gpt2 as ref

CFG = {"vocab_size": 256, "n_positions": 128, "n_embd": 64, "n_layer": 2, "n_head": 2}
HP = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4}
BIG_SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def program():
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead

    return GPT2LMHead(GPT2Config.tiny(dtype=jnp.float32, attention_impl="xla"))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, CFG["vocab_size"], (4, 48)).astype(np.int32)


def test_weights_have_the_programs_tree(program):
    want = jax.eval_shape(program.init_params, jax.random.key(0))
    got = weights.make_program(BIG_SEED, CFG)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)))


def test_weights_follow_the_seed_and_round_trip():
    a, b = weights.make_stacked(BIG_SEED, CFG), weights.make_stacked(BIG_SEED, CFG)
    c = weights.make_stacked(BIG_SEED + 1, CFG)
    assert all(bool((x == y).all()) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool((a["wte"] == c["wte"]).all())
    back = weights.from_program(weights.make_program(BIG_SEED, CFG))
    assert all(bool((x == y).all()) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(back)))


def test_logits_agree_with_the_program(program, ids):
    stacked, tree = weights.make_stacked(5, CFG), weights.make_program(5, CFG)
    with jax.default_matmul_precision("highest"):
        want = program.apply({"params": tree}, ids)
    got = ref.forward(stacked, ids, CFG["n_head"])
    positions = jnp.asarray([[3, 0, ids.shape[1] - 1]] * ids.shape[0])
    picked = ref.logits_at(stacked, jnp.asarray(ids), positions, CFG["n_head"])
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale
    assert float(jnp.abs(picked - jnp.take_along_axis(want, positions[..., None], 1)).max()) <= 1e-5 * scale


def test_loss_and_gradients_agree_with_the_program(program, ids):
    from accelerate_tpu.models.gpt2 import cross_entropy_loss

    stacked, tree = weights.make_stacked(6, CFG), weights.make_program(6, CFG)

    def program_loss(p):
        logits = program.apply({"params": p}, ids)
        labels = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)), constant_values=-100)
        return cross_entropy_loss(logits, labels)

    with jax.default_matmul_precision("highest"):
        want_loss, want_grads = jax.value_and_grad(program_loss)(tree)
    got_loss, got_grads = jax.jit(
        lambda p: ref.loss_and_grads(p, jnp.asarray(ids), CFG["n_head"], rows_per_block=2))(stacked)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    want = ref.leaf_norms(weights.from_program(want_grads))
    got = ref.leaf_norms(got_grads)
    assert len(ref.leaf_names(stacked)) == got.shape[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=1e-9)


def test_key_bias_has_no_gradient(ids):
    """Softmax is blind to a constant added to every key's score: the rule
    that drops such leaves from the change has something to find."""
    stacked = weights.make_stacked(7, CFG)
    _, grads = jax.jit(lambda p: ref.loss_and_grads(p, jnp.asarray(ids), CFG["n_head"]))(stacked)
    norms = dict(zip(ref.leaf_names(stacked), np.asarray(ref.leaf_norms(grads))))
    assert norms["block_0/k_b"] < 1e-3 * np.median(list(norms.values()))
    assert norms["block_0/q_b"] > 1e-3 * np.median(list(norms.values()))


def test_adamw_agrees_with_optax():
    import optax

    params = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(5, 3)), jnp.float32)}
    grads = {"w": jnp.asarray(np.random.default_rng(2).normal(size=(5, 3)), jnp.float32)}
    tx = optax.adamw(**HP)
    state = tx.init(params)
    want = params
    mine, mine_state = params, ref.adamw_init(params)
    for _ in range(3):
        updates, state = tx.update(grads, state, want)
        want = optax.apply_updates(want, updates)
        mine, mine_state = ref.adamw_update(mine, grads, mine_state, HP)
    np.testing.assert_allclose(np.asarray(mine["w"]), np.asarray(want["w"]), rtol=1e-6, atol=1e-8)


def test_int8_control_is_coarser_than_float32(ids):
    stacked = weights.make_stacked(8, CFG)
    exact = ref.forward(stacked, ids, CFG["n_head"])
    coarse = ref.forward(stacked, ids, CFG["n_head"], quant="int8")
    err = float(jnp.abs(coarse - exact).max() / jnp.abs(exact).max())
    assert 1e-4 < err < 0.2


def test_configuration_files_state_the_published_sizes():
    chip = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    published = {"gpt2-medium": (24, 1024, 16), "gpt2-large": (36, 1280, 20)}
    for name, (layers, width, heads) in published.items():
        with open(os.path.join(chip, "configs", f"{name}.json")) as f:
            cfg = json.load(f)
        assert (cfg["n_layer"], cfg["n_embd"], cfg["n_head"]) == (layers, width, heads)
        assert cfg["vocab_size"] == 50257 and cfg["n_positions"] == 1024 and cfg["reduced"] == []
