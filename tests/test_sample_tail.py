"""The serving step's sampling tail (`engine._sample_rows`): one branch for
the whole batch, chosen on device from what its live rows ask for.

The contract is bit-equality with the per-row body it replaced (kept here as
the oracle, `_oracle_slot`): whichever branch runs, every live row's token is
the one ``jax.vmap(_oracle_slot)`` gives with the same keys. The engine tests
(`test_serving.py` and the rest) hold the same thing end to end against
`generate`; these hold it branch by branch, and hold the host's count of the
branches (`serving/sample_tail/*`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = pytest.mark.serving

from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.serving import Request, SamplingParams, ServingEngine
from accelerate_tpu.serving.engine import _sample_rows

ROWS, VOCAB = 6, 61


def _oracle_slot(logits, key, temperature, top_k):
    """The body every row ran before `_sample_rows`: sort and draw always."""
    greedy = jnp.argmax(logits, axis=-1)
    vocab = logits.shape[-1]
    safe_t = jnp.where(temperature > 0, temperature, jnp.ones_like(temperature))
    scaled = logits / safe_t
    ordered = jnp.sort(scaled, axis=-1)
    kth = jnp.take(ordered, vocab - jnp.clip(top_k, 1, vocab))
    masked = jnp.where((top_k > 0) & (scaled < kth), -jnp.inf, scaled)
    sampled = jax.random.categorical(key, masked, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def _branch(temps, top_ks, live):
    """The branch `_sample_rows` takes, computed on the host."""
    draws = np.asarray(live) & (np.asarray(temps) > 0)
    return int(draws.any()) + int((draws & (np.asarray(top_ks) > 0)).any())


ALL = [True] * ROWS
# (name, temperatures, top_ks, live, row of NaN logits or None, branch expected)
CASES = [
    ("all_greedy", [0.0] * ROWS, [0] * ROWS, ALL, None, 0),
    ("greedy_with_stale_top_k", [0.0] * ROWS, [0, 5, 0, 3, 0, 0], ALL, None, 0),
    ("all_sampled_no_top_k", [0.7, 1.0, 1.3, 0.2, 2.0, 0.9], [0] * ROWS, ALL,
     None, 1),
    ("mixed_greedy_sampled", [0.0, 0.7, 0.0, 1.3, 0.0, 0.0], [0] * ROWS, ALL,
     None, 1),
    ("mixed_greedy_sampled_top_k", [0.0, 0.7, 1.0, 0.0, 1.3, 0.5],
     [0, 0, 5, 3, 1, 0], ALL, None, 2),
    ("only_top_k_row_not_live_rest_greedy", [0.0, 0.0, 1.0, 0.0, 0.0, 0.0],
     [0, 0, 5, 0, 0, 0], [True, True, False, True, True, True], None, 0),
    ("only_top_k_row_not_live_rest_draw", [0.7, 0.0, 1.0, 1.3, 0.0, 0.0],
     [0, 0, 5, 0, 0, 0], [True, True, False, True, True, True], None, 1),
    ("nan_row_greedy_batch", [0.0] * ROWS, [0] * ROWS, ALL, 2, 0),
    ("nan_row_drawing_batch", [0.0, 0.7, 1.0, 0.0, 1.3, 0.5], [0] * ROWS, ALL,
     2, 1),
    ("nan_row_top_k_batch", [0.0, 0.7, 1.0, 0.0, 1.3, 0.5], [0, 4, 4, 0, 0, 2],
     ALL, 2, 2),
    ("top_k_at_and_past_vocab", [1.0, 0.7, 1.0, 0.0, 1.3, 0.5],
     [VOCAB, VOCAB + 1, 10 * VOCAB, VOCAB, 0, 1], ALL, None, 2),
    ("nothing_live", [0.7] * ROWS, [5] * ROWS, [False] * ROWS, None, 0),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_sample_rows_bit_equal_to_per_row_body(case):
    _, temps, top_ks, live, nan_row, want_branch = case
    logits = jax.random.normal(jax.random.key(3), (ROWS, VOCAB), jnp.float32) * 3
    if nan_row is not None:
        logits = logits.at[nan_row].set(jnp.nan)
    keys = jax.random.split(jax.random.key(11), ROWS)
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)
    live = np.asarray(live)
    assert _branch(temps, top_ks, live) == want_branch
    got = np.asarray(jax.jit(_sample_rows)(logits, keys, temps, top_ks,
                                           jnp.asarray(live)))
    want = np.asarray(jax.jit(jax.vmap(_oracle_slot))(logits, keys, temps, top_ks))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got[live], want[live])
    if want_branch == 2:
        # the heavy branch is the old body for every row, live or not
        np.testing.assert_array_equal(got, want)


def test_sample_rows_is_one_conditional_with_the_sort_inside():
    """The sort sits in a branch computation of a ``conditional``, not in the
    program every call runs (a `cond` under the `vmap` would lower to a
    select and put it back)."""
    args = (jnp.zeros((ROWS, VOCAB)), jax.random.split(jax.random.key(0), ROWS),
            jnp.zeros((ROWS,)), jnp.zeros((ROWS,), jnp.int32),
            jnp.ones((ROWS,), bool))
    hlo = jax.jit(_sample_rows).lower(*args).as_text(dialect="hlo")
    assert " conditional(" in hlo
    entry = hlo[hlo.index("ENTRY"):]
    entry = entry[:entry.index("\n}")]
    assert " sort(" not in entry
    assert " sort(" in hlo


@pytest.mark.parametrize("k_iters", [1, 3])
def test_sample_rows_inside_a_scan_matches_the_oracle(k_iters):
    """`tokens_per_sync > 1` puts the switch inside a `lax.scan` body."""
    logits = jax.random.normal(jax.random.key(5), (k_iters, ROWS, VOCAB))
    temps = jnp.asarray([0.0, 0.7, 1.0, 0.0, 1.3, 0.5], jnp.float32)
    top_ks = jnp.asarray([0, 0, 5, 3, 1, 0], jnp.int32)
    rngs = jax.random.split(jax.random.key(9), ROWS)

    def run(tail):
        def body(rngs, step_logits):
            split = jax.vmap(jax.random.split)(rngs)
            return split[:, 0], tail(step_logits, split[:, 1])
        return jax.jit(lambda: jax.lax.scan(body, rngs, logits))()

    new_rngs, got = run(lambda x, keys: _sample_rows(
        x, keys, temps, top_ks, jnp.ones((ROWS,), bool)))
    old_rngs, want = run(lambda x, keys: jax.vmap(_oracle_slot)(
        x, keys, temps, top_ks))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(new_rngs)),
                                  np.asarray(jax.random.key_data(old_rngs)))


# ------------------------------------------------------------ host counters
@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    return module, module.init_params(jax.random.key(0))


def _tail_counts(engine):
    snap = engine.metrics.snapshot()
    return tuple(snap[f"serving/sample_tail/{name}_steps"]
                 for name in ("greedy", "draw", "top_k"))


def _prompts(seed, lengths):
    r = np.random.default_rng(seed)
    return [r.integers(0, 256, (n,)).astype(np.int32).tolist() for n in lengths]


def test_all_greedy_traffic_counts_every_step_greedy(model):
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(8, 16), max_queue=8)
    engine.run([Request(p, SamplingParams(max_new_tokens=6))
                for p in _prompts(0, [3, 7, 12])])
    greedy, draw, top_k = _tail_counts(engine)
    # one count a decode dispatch (`dispatch_depth` is observed at each)
    assert greedy == engine.metrics.dispatch_depth.count > 0
    assert (draw, top_k) == (0, 0)


def test_top_k_request_moves_its_counter_while_it_lives(model):
    """A greedy long request shares the engine with one short sampled top-k
    request: `top_k_steps` moves for the turns the host holds that slot, and
    `greedy_steps` resumes once it retires."""
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(16,), max_queue=8)
    long_p, short_p = _prompts(2, [5, 9])
    assert engine.submit(Request(long_p, SamplingParams(max_new_tokens=24))).accepted
    for _ in range(3):
        engine.step()
    before = _tail_counts(engine)
    assert before[0] > 0 and before[1:] == (0, 0)
    assert engine.submit(Request(short_p, SamplingParams(
        max_new_tokens=5, temperature=0.8, top_k=4, seed=3))).accepted
    done = []
    while not done:
        done = engine.step()
    assert done[0].prompt_len == len(short_p)
    during = _tail_counts(engine)
    # the step that reaps the finished request retires it before it
    # dispatches, so that one dispatch may already count greedy again
    assert during[0] - before[0] <= 1 and during[1] == 0
    assert during[2] >= 3  # 5 tokens: one from the admit, the rest by steps
    assert (engine._draw_slots, engine._top_k_slots) == (0, 0)
    while engine.has_work:
        engine.step()
    after = _tail_counts(engine)
    assert after[0] > during[0] and after[1:] == during[1:]


def test_sampled_request_without_top_k_counts_draw_steps(model):
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(16,), max_queue=8)
    engine.run([Request(p, SamplingParams(max_new_tokens=6, temperature=0.9,
                                          seed=i))
                for i, p in enumerate(_prompts(4, [4, 6]))])
    greedy, draw, top_k = _tail_counts(engine)
    assert draw == engine.metrics.dispatch_depth.count > 0
    assert (greedy, top_k) == (0, 0)
    assert (engine._draw_slots, engine._top_k_slots) == (0, 0)
