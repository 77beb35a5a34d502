"""Prefix KV-cache reuse (`serving/prefix_cache.py`): token identity against
solo `generate` across the cache on/off x pipeline_depth x admit_batch matrix,
ref-count pinning, deterministic LRU eviction, donation policy, and the block
scatter/gather primitives.

The load-bearing contract is the same as the serving suite's, strengthened: a
request whose prompt prefix is served FROM THE CACHE must emit exactly the
tokens the cold engine — and a solo ``generate`` — would, including under
eviction pressure and watchdog re-prefill.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = [pytest.mark.serving, pytest.mark.prefix_cache]

from accelerate_tpu.models.generation import generate
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.models.kv_cache import (
    BlockAllocator,
    gather_block_rows,
    scatter_rows_to_blocks,
)
from accelerate_tpu.reliability import FaultSpec
from accelerate_tpu.serving import (
    FINISH_ERROR,
    PagedKVConfig,
    PrefixCache,
    Request,
    SamplingParams,
    ServingEngine,
)

BT = 16  # GPT2Config.tiny has n_positions=128 -> 8 blocks per row at 16


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(0))
    return module, params


def _solo(module, params, prompt, n, temperature=0.0, top_k=None, seed=0):
    ids = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    out = generate(module, params, ids, max_new_tokens=n,
                   temperature=temperature, top_k=top_k, rng=jax.random.key(seed))
    return np.asarray(out)[0].tolist()


def _shared_prefix_requests(n=6, prefix_len=37, n_new=8):
    """Requests sharing a long common prefix (2 full blocks at BT=16) with
    short distinct tails, mixing greedy and sampled rows."""
    r = np.random.default_rng(0)
    prefix = r.integers(0, 256, (prefix_len,)).astype(np.int32).tolist()
    reqs = []
    for i in range(n):
        tail = [100 + i, 7, (3 * i) % 256]
        temp = 0.0 if i % 2 == 0 else 0.8
        reqs.append(Request(
            prompt=prefix + tail,
            params=SamplingParams(max_new_tokens=n_new, temperature=temp,
                                  top_k=None if i % 3 else 5, seed=i),
        ))
    return reqs


# ------------------------------------------------------------- unit: primitives
def test_block_scatter_gather_roundtrip():
    """scatter_rows_to_blocks then gather_block_rows (the pair the admit
    programs use) reproduces the prefilled rows bit-for-bit through the
    pool's folded ``kv_heads * head_dim`` layout, drops out-of-range dest
    ids, and stamps the resume index into the slots' cursor and the gathered
    rows' ``cache_index``."""
    nb, bucket, heads, dim = 2, 16, 2, 3
    key = jnp.arange(nb * bucket * heads * dim, dtype=jnp.float32).reshape(
        nb, bucket, heads, dim)
    rows = {"cached_key": key, "cached_value": key * 0.5 + 1.0,
            "cache_index": jnp.full((nb,), bucket, jnp.int32)}
    pool = {"cached_key": jnp.zeros((5, 4, heads * dim)),
            "cached_value": jnp.zeros((5, 4, heads * dim)),
            "cache_index": jnp.zeros((3,), jnp.int32)}  # three slots
    # row 0's first two 4-token blocks land in pool blocks 3 and 0, row 1's
    # first in block 1; entries == num_blocks (5) must be dropped, not clamped
    dest = jnp.asarray([[3, 0, 5, 5], [1, 5, 5, 5]], jnp.int32)
    pool = scatter_rows_to_blocks(
        pool, rows, jnp.asarray([2, 0], jnp.int32), dest,
        jnp.asarray([7, 3], jnp.int32), 4)
    folded = np.asarray(key).reshape(nb, bucket, heads * dim)
    np.testing.assert_array_equal(np.asarray(pool["cached_key"][3]), folded[0, 0:4])
    np.testing.assert_array_equal(np.asarray(pool["cached_key"][0]), folded[0, 4:8])
    np.testing.assert_array_equal(np.asarray(pool["cached_key"][1]), folded[1, 0:4])
    assert not np.asarray(pool["cached_key"])[[2, 4]].any()  # dropped, untouched
    np.testing.assert_array_equal(np.asarray(pool["cache_index"]), [3, 0, 7])
    like = jax.eval_shape(lambda: jax.tree.map(lambda x: x[:1], rows))
    got = gather_block_rows(pool, jnp.asarray([[3, 0, 3, 3]], jnp.int32),
                            jnp.asarray([8], jnp.int32), like=like)
    assert got["cached_key"].shape == (1, bucket, heads, dim)
    np.testing.assert_array_equal(np.asarray(got["cached_key"][0, :8]),
                                  np.asarray(key[0, :8]))
    np.testing.assert_array_equal(
        np.asarray(got["cached_value"][0, :8]), np.asarray(rows["cached_value"][0, :8])
    )
    np.testing.assert_array_equal(np.asarray(got["cache_index"]), [8])


def _trie(num_blocks, max_len=16, block_tokens=4):
    return PrefixCache(BlockAllocator(num_blocks), max_len=max_len,
                       block_tokens=block_tokens)


def _donate(pc, prompt):
    """What a slot does from admission to retirement: reserve its full
    prompt blocks from the shared allocator, evicting unpinned LRU leaves on
    a shortfall (`ServingEngine._reserve_blocks`), and hand them to the trie.
    Returns how many blocks the trie newly holds; 0 when the pool could not
    seat the prompt."""
    n = len(prompt) // pc.block_tokens
    if pc.allocator.free_count < n:
        pc.reclaim(n - pc.allocator.free_count)
    ids = pc.allocator.alloc(n)
    if ids is None:
        return 0
    return pc.adopt(prompt, ids, owned_from=0)


def test_trie_refcount_pins_blocks_against_eviction():
    """Pinned nodes (in-flight sharers) are never evicted; donation that
    cannot place a block stops without corrupting the trie; release/trim
    drop the pins."""
    pc = _trie(2)
    a = list(range(10))  # 2 full blocks + partial
    assert _donate(pc, a) == 2
    m1, m2 = pc.acquire(a), pc.acquire(a)  # two in-flight sharers
    assert m1.tokens == m2.tokens == 8 and m1.block_ids == m2.block_ids
    assert all(n.ref == 2 for n in m1.nodes)
    # pool is full and fully pinned: a competing donation places nothing
    assert _donate(pc, list(range(50, 60))) == 0
    assert pc.match_len(a) == 8  # trie untouched by the failed donation
    pc.release(m1)
    m2 = pc.trim(m2, 1)  # trim releases the pins past the cut
    assert m2.tokens == 4 and m1.nodes[1].ref == 0
    pc.release(m2)
    assert all(n.ref == 0 for n in m1.nodes)
    # everything unpinned: the competing donation can now evict its way in
    assert _donate(pc, list(range(50, 60))) == 2
    assert pc.match_len(list(range(50, 60))) == 8 and pc.match_len(a) == 0


def test_lru_eviction_is_deterministic_and_leaf_only():
    """Under a full pool, eviction removes the least-recently-TOUCHED unpinned
    leaf (monotonic tick, no wall clock) — interior nodes survive until their
    subtree is gone, so a refreshed prefix keeps its chain."""
    pc = _trie(3)
    a = list(range(9))  # blocks A1, A2
    b = list(range(100, 105))  # block B1
    assert _donate(pc, a) == 2
    assert _donate(pc, b) == 1
    pc.release(pc.acquire(a))  # refresh A's whole chain: B is now LRU
    c = list(range(200, 209))  # needs 2 blocks -> 2 evictions
    assert _donate(pc, c) == 2
    assert pc.metrics is None  # unit-level: no metrics bag attached
    # B went first (oldest leaf), then A's leaf A2 (A1 is interior until A2
    # dies, then still fresher than nothing else); A keeps one block
    assert pc.match_len(b) == 0
    assert pc.match_len(a) == 4
    assert pc.match_len(c) == 8
    assert pc.node_count() == 3 and pc.allocator.free_count == 0


def test_prefix_cache_validates_config():
    with pytest.raises(ValueError):
        _trie(4, block_tokens=6)  # not a power of two
    with pytest.raises(ValueError):
        _trie(4, max_len=10)  # does not divide
    with pytest.raises(ValueError):
        _trie(0)  # an empty pool


def test_match_capped_below_full_prompt():
    """A fully-cached prompt still leaves >= 1 token for the suffix prefill
    (admission samples the first output from the last prompt token)."""
    pc = _trie(4)
    a = list(range(8))  # exactly 2 blocks
    _donate(pc, a)
    assert pc.match_len(a) == 4  # NOT 8: the last block is held back
    assert pc.match_len(a + [99]) == 8  # a longer prompt may use both


# ------------------------------------------------------------ engine: parity
@pytest.mark.parametrize("cache_on", [False, True])
@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("admit", [1, 4])
def test_parity_matrix_cached_vs_solo(model, cache_on, depth, admit):
    """The full matrix: cache on/off x pipeline_depth {1,2} x admit_batch
    {1,4} — every request token-identical to its solo generate, so prefix
    reuse (gather + suffix prefill + donation) never perturbs a stream."""
    module, params = model
    reqs = _shared_prefix_requests()
    refs = [_solo(module, params, r.prompt, r.params.max_new_tokens,
                  temperature=r.params.temperature, top_k=r.params.top_k,
                  seed=r.params.seed) for r in reqs]
    engine = ServingEngine(
        module, params, max_concurrency=3, prompt_buckets=(8, 16, 64),
        pipeline_depth=depth, admit_batch=admit,
        prefix_cache=cache_on,
    )
    outs = engine.run(reqs)
    for out, ref in zip(sorted(outs, key=lambda o: o.request_id), refs):
        assert out.tokens == ref
    if cache_on:
        m = engine.metrics
        assert m.prefix_hits.value > 0 and m.prefix_tokens_reused.value > 0
        assert m.prefix_blocks_donated.value > 0
        # the reused tokens were NOT prefilled
        total_prompt = sum(len(r.prompt) for r in reqs)
        assert m.prefill_tokens.value <= total_prompt - m.prefix_tokens_reused.value
        assert m.ttft_hit_s.count == m.prefix_hits.value
        assert m.ttft_miss_s.count == m.prefix_misses.value


def test_parity_under_eviction_pressure(model):
    """A pool far too small for the working set keeps evicting hot blocks;
    outputs must stay token-identical regardless (eviction only loses reuse,
    never correctness)."""
    module, params = model
    r = np.random.default_rng(7)
    reqs = []
    for i in range(6):
        prefix = r.integers(0, 256, (35,)).astype(np.int32).tolist()
        reqs.append(Request(prompt=prefix + [i], params=SamplingParams(max_new_tokens=6)))
    reqs.extend(Request(prompt=list(q.prompt), params=q.params) for q in reqs[:3])
    # one row's blocks: two requests of 3 blocks each in flight leave the
    # trie 2 of the 8, and every retirement donates 2 more
    engine = ServingEngine(
        module, params, max_concurrency=2, prompt_buckets=(8, 64),
        prefix_cache=True, paged_kv=PagedKVConfig(block_tokens=BT, num_blocks=8),
    )
    outs = engine.run(reqs)
    for out, req in zip(sorted(outs, key=lambda o: o.request_id), reqs):
        assert out.tokens == _solo(module, params, req.prompt, 6)
    assert engine.metrics.prefix_evictions.value > 0
    mem = engine.memory_stats()
    assert mem["block_pool/blocks_private"] == 0
    assert (mem["block_pool/blocks_free"] + engine.prefix_cache.node_count()
            == mem["block_pool/blocks_total"] == 8)


def test_two_inflight_sharers_pin_the_same_blocks(model):
    """Two concurrent requests admitted off the same cached prefix hold the
    same blocks pinned (ref == 2) until retirement releases them."""
    module, params = model
    reqs = _shared_prefix_requests(n=3, n_new=16)
    engine = ServingEngine(
        module, params, max_concurrency=2, prompt_buckets=(8, 64),
        admit_batch=2, prefix_cache=True,
    )
    # warm the trie: serve one request to completion so it donates
    engine.run([reqs[0]])
    assert engine.metrics.prefix_blocks_donated.value == 2
    for q in reqs[1:]:
        assert engine.submit(q).accepted
    engine.step()  # admits both sharers off the cached prefix
    pinned = [m for m in engine._slot_match if m is not None]
    assert len(pinned) == 2
    assert pinned[0].block_ids == pinned[1].block_ids
    assert all(n.ref == 2 for n in pinned[0].nodes)
    while engine.has_work:
        engine.step()
    assert all(m is None for m in engine._slot_match)
    assert all(n.ref == 0 for n in pinned[0].nodes)


def test_cache_prefix_opt_out(model):
    """cache_prefix=False requests neither read nor feed the cache — and stay
    token-identical (the opt-out is a policy knob, not a behavior change)."""
    module, params = model
    reqs = _shared_prefix_requests(n=4)
    for q in reqs:
        q.cache_prefix = False
    engine = ServingEngine(
        module, params, max_concurrency=2, prompt_buckets=(8, 64),
        prefix_cache=True,
    )
    outs = engine.run(reqs)
    for out, req in zip(sorted(outs, key=lambda o: o.request_id), reqs):
        assert out.tokens == _solo(
            module, params, req.prompt, req.params.max_new_tokens,
            temperature=req.params.temperature, top_k=req.params.top_k,
            seed=req.params.seed,
        )
    m = engine.metrics
    assert m.prefix_hits.value == 0 and m.prefix_misses.value == 0
    assert m.prefix_blocks_donated.value == 0
    assert engine.prefix_cache.node_count() == 0


# ------------------------------------------------- engine: faults and donation
@pytest.mark.fault
def test_finish_error_slot_never_donates(model, fault_injection):
    """A twice-poisoned request retires FINISH_ERROR; its (garbage) KV must
    not be donated to the shared pool."""
    module, params = model
    prompt = np.random.default_rng(3).integers(0, 256, (36,)).tolist()
    fault_injection(FaultSpec.poison(at_steps=(1, 4), slots=(0,)))
    engine = ServingEngine(
        module, params, max_concurrency=1, prompt_buckets=(8, 64),
        prefix_cache=True,
    )
    out = engine.run([Request(prompt=prompt, params=SamplingParams(max_new_tokens=16))])[0]
    assert out.finish_reason == FINISH_ERROR
    assert engine.metrics.prefix_blocks_donated.value == 0
    assert engine.prefix_cache.node_count() == 0


@pytest.mark.fault
def test_watchdog_reprefill_parity_with_cache_hits(model, fault_injection):
    """A poisoned slot's re-prefill may now HIT the cache (its own donation or
    a sibling's) — the replay must still be token-identical to solo."""
    module, params = model
    reqs = _shared_prefix_requests(n=3, n_new=8)
    fault_injection(FaultSpec.poison(at_steps=(3,), slots=(1,)))
    engine = ServingEngine(
        module, params, max_concurrency=2, prompt_buckets=(8, 64),
        prefix_cache=True,
    )
    outs = engine.run(reqs)
    assert engine.metrics.requests_retried.value == 1
    for out, req in zip(sorted(outs, key=lambda o: o.request_id), reqs):
        assert out.tokens == _solo(
            module, params, req.prompt, req.params.max_new_tokens,
            temperature=req.params.temperature, top_k=req.params.top_k,
            seed=req.params.seed,
        )
