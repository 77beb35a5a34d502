"""Paged KV serving (`ServingEngine(paged_kv=...)`): block-table KV as the
engine's one store, with copy-free prefix aliasing and block-gated admission.

The load-bearing contract is threefold. PARITY: the engine emits exactly the
tokens a solo ``generate`` emits, through the gather path and the fused
kernel alike, across the pipeline depth x admit batch matrix, through prefix-cache-hit admissions, and on the
(2, 2) mesh. BACKPRESSURE: block exhaustion delays admission, it never
crashes a decode (reservation is all-or-nothing, up front). ACCOUNTING: every
block is either free, trie-resident, or privately held by a live slot, the
three always sum to the pool, and retirement reclaims exactly the unpinned
blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

flax_nn = pytest.importorskip("flax.linen")

pytestmark = [pytest.mark.serving, pytest.mark.paged]

from accelerate_tpu.models.generation import generate
from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
from accelerate_tpu.models.kv_cache import BlockAllocator
from accelerate_tpu.reliability import FaultSpec
from accelerate_tpu.serving import (
    FINISH_EOS,
    FINISH_LENGTH,
    PagedKVConfig,
    Request,
    SamplingParams,
    ServingEngine,
)

BT = 16  # GPT2Config.tiny has n_positions=128 -> 8 blocks per slot at 16


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


def _prompts(rng_seed, lengths, vocab=256):
    r = np.random.default_rng(rng_seed)
    return [r.integers(0, vocab, (n,)).astype(np.int32).tolist() for n in lengths]


def _requests(prompts, n_new=12, greedy=True):
    return [
        Request(prompt=list(p),
                params=SamplingParams(
                    max_new_tokens=n_new,
                    temperature=0.0 if greedy else 0.8,
                    top_k=None if greedy else 7,
                    seed=i,
                ))
        for i, p in enumerate(prompts)
    ]


# ------------------------------------------------------------ allocator unit
def test_block_allocator_all_or_nothing_and_double_free():
    a = BlockAllocator(4)
    assert a.free_count == 4 and a.owned_count == 0
    got = a.alloc(3)
    assert got is not None and len(got) == 3
    assert a.free_count == 1 and a.owned_count == 3
    # all-or-nothing: a request for 2 must not consume the last block
    assert a.alloc(2) is None
    assert a.free_count == 1
    assert a.alloc(0) == []
    last = a.alloc(1)
    assert a.free_count == 0
    a.free(got + last)
    assert a.free_count == 4 and a.owned_count == 0
    a.alloc(1)
    with pytest.raises(ValueError, match="double free"):
        a.free([got[0], got[0]])


def test_engine_validates_paged_config(model):
    module, params = model
    kw = dict(max_concurrency=2, prompt_buckets=(16,))
    for bad_bt in (6, 256):  # not a power of two; does not divide n_positions
        with pytest.raises(ValueError, match="power of two dividing"):
            ServingEngine(module, params,
                          paged_kv=PagedKVConfig(block_tokens=bad_bt), **kw)
    with pytest.raises(ValueError, match="num_blocks"):
        # fewer blocks than one full-length row: admission could never seat
        # a worst-case request -> loud at construction, not a silent hang
        ServingEngine(module, params,
                      paged_kv=PagedKVConfig(block_tokens=BT, num_blocks=4), **kw)
    cfg8 = GPT2Config.tiny(dtype=jnp.float32, kv_cache_dtype=jnp.int8)
    m8 = GPT2LMHead(cfg8)
    p8 = m8.init_params(jax.random.key(0))
    # kv_cache_dtype=int8 now COMPOSES with paging (the pool stores int8
    # payload + sibling fp32 scale planes, tests/test_quant_serving.py) —
    # construction must succeed and the pool must really be quantized
    eng8 = ServingEngine(m8, p8, paged_kv=True, **kw)
    assert eng8.quant_stats()["kv_bits"] == 8
    # the block quantum is decided once: the trie takes the pool's
    eng32 = ServingEngine(module, params, prefix_cache=True,
                          paged_kv=PagedKVConfig(block_tokens=32), **kw)
    assert eng32.prefix_cache.block_tokens == 32
    assert eng32.prefix_cache.allocator is eng32._allocator


@pytest.mark.parametrize("off", [False, None, 0])
def test_the_slot_store_is_gone(model, off):
    """``paged_kv`` only sizes the pool: the per-slot contiguous store it
    used to switch off is removed, and asking for it says so."""
    module, params = model
    with pytest.raises(ValueError, match="removed"):
        ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,),
                      paged_kv=off)
    default = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,))
    assert default.memory_stats()["block_pool/blocks_total"] == 2 * 128 // BT


def test_engine_validates_fused_and_sync_config(model):
    module, params = model
    kw = dict(max_concurrency=2, prompt_buckets=(16,))
    with pytest.raises(ValueError, match="gather.*fused|fused.*gather"):
        ServingEngine(module, params, paged_kv=True,
                      paged_attention="pallas", **kw)
    # the fused kernel reads the block pool every engine has: no store to ask for
    assert ServingEngine(module, params, paged_attention="fused",
                         **kw).module.config.kv_paged_attention == "fused"
    with pytest.raises(ValueError, match="tokens_per_sync"):
        ServingEngine(module, params, tokens_per_sync=0, **kw)


# ------------------------------------------------------------------- parity
@pytest.fixture(scope="module")
def parity_refs(model):
    module, params = model
    prompts = _prompts(7, (5, 23, 40, 9))
    return prompts, {i: _solo(module, params, p, 12, seed=i)
                     for i, p in enumerate(prompts)}


@pytest.mark.parametrize("sync", [1, 4])
@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("admit", [1, 4])
def test_paged_parity_matrix(model, parity_refs, depth, admit, sync):
    """Fused kernel == gather path == solo generate, bit-for-bit, across the depth x admit x tokens_per_sync matrix — the
    tentpole oracle. The fused cell runs the Pallas paged-decode kernel in
    interpret mode on CPU; the multi-token cells run the whole decode loop
    inside one jitted lax.scan per dispatch."""
    module, params = model
    prompts, refs = parity_refs

    def serve(**kw):
        engine = ServingEngine(module, params, max_concurrency=4,
                               prompt_buckets=(16, 64), pipeline_depth=depth,
                               admit_batch=admit, tokens_per_sync=sync, **kw)
        return {o.request_id: o.tokens for o in engine.run(_requests(prompts))}

    gather = serve()
    fused = serve(paged_attention="fused")
    assert fused == gather == refs


def test_eos_and_budget_landing_mid_scan(model, parity_refs):
    """With ``tokens_per_sync=4`` a finish source can fire at any iteration
    of the scan, not just the last: a 6-token budget lands at iteration 2 of
    the second dispatch, and an EOS planted mid-stream lands wherever the
    reference emits it. The on-device finished mask must freeze the row for
    the scan's remaining iterations and the host must append exactly the
    pre-finish prefix — no tokens past the stop, none missing."""
    module, params = model
    prompts, refs = parity_refs

    def serve(n_new, eos=None, pa="gather"):
        engine = ServingEngine(module, params, max_concurrency=4,
                               prompt_buckets=(16, 64), pipeline_depth=2,
                               admit_batch=4, paged_kv=True, tokens_per_sync=4,
                               paged_attention=pa, eos_token_id=eos)
        return {o.request_id: o for o in engine.run(_requests(prompts, n_new))}

    for pa in ("gather", "fused"):
        # budget mid-scan: 1 admit token + 5 decode tokens = iteration 1 of
        # the second 4-iteration scan
        outs = serve(6, pa=pa)
        for rid, o in outs.items():
            assert o.tokens == refs[rid][:6]
            assert o.finish_reason == FINISH_LENGTH
    # EOS mid-scan: pick a stream position whose token makes its FIRST
    # appearance at a decode step that is not the last iteration of a scan
    # (decode step t sits mid-scan when t % 4 != 0), and declare that token
    # the EOS — the earlier decode steps must not emit it, and every other
    # stream runs to budget or stops wherever it happens to emit the same id
    rid_eos, cut = next(
        (rid, t) for rid in sorted(refs) for t in range(2, 12)
        if t % 4 != 0 and refs[rid][t] not in refs[rid][:t])
    eos = refs[rid_eos][cut]
    outs = serve(12, eos=eos)
    assert outs[rid_eos].tokens == refs[rid_eos][:cut + 1]
    assert outs[rid_eos].finish_reason == FINISH_EOS
    for rid, o in outs.items():
        if rid == rid_eos:
            continue
        if eos in refs[rid]:
            stop = refs[rid].index(eos) + 1
            assert o.tokens == refs[rid][:stop]
        else:
            assert o.tokens == refs[rid]


@pytest.mark.fault
def test_quarantine_mid_scan_replays_token_identical(model, fault_injection):
    """A slot poisoned inside a multi-token scan freezes on device at the
    poisoned iteration (health is a finish source), the host quarantines it
    at that token, and the re-prefill replays the request token-identical —
    while the co-resident healthy slot is untouched."""
    module, params = model
    prompts = _prompts(10, (4, 6))
    n_new = 10
    refs = {i: _solo(module, params, p, n_new, seed=i)
            for i, p in enumerate(prompts)}
    fault_injection(FaultSpec.poison(at_steps=(2,), slots=(1,)))
    engine = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(8,), paged_kv=True,
                           tokens_per_sync=4)
    outs = engine.run(_requests(prompts, n_new))
    assert engine.metrics.steps_poisoned.value == 1
    assert engine.metrics.requests_retried.value == 1
    for o in outs:
        assert o.finish_reason == FINISH_LENGTH
        assert o.tokens == refs[o.request_id]


def test_paged_parity_with_a_merged_width_that_tiles_nothing():
    """3 heads of 16: the pool's folded last dim is 48 lanes, no multiple of
    128 nor of 64, with an odd head count. Fused == gather == solo, and the
    pool leaves really are ``[num_blocks, block_tokens, kv_heads * head_dim]``."""
    cfg = GPT2Config.tiny(dtype=jnp.float32, n_embd=48, n_head=3)
    module = GPT2LMHead(cfg)
    params = module.init_params(jax.random.key(1))
    prompts = _prompts(11, (5, 23, 40, 9))

    def serve(**kw):
        engine = ServingEngine(module, params, max_concurrency=4,
                               prompt_buckets=(16, 64), admit_batch=2, **kw)
        tokens = {o.request_id: o.tokens for o in engine.run(_requests(prompts))}
        return tokens, engine

    refs = {i: _solo(module, params, p, 12, seed=i) for i, p in enumerate(prompts)}
    gather, _ = serve()
    fused, engine = serve(paged_attention="fused")
    assert fused == gather == refs
    kv_shapes = {leaf.shape for path, leaf in
                 jax.tree_util.tree_leaves_with_path(engine._cache)
                 if path[-1].key in ("cached_key", "cached_value")}
    assert kv_shapes == {(4 * 128 // BT, BT, 48)}


@pytest.mark.parametrize("heads, kv_heads, head_dim, quant", [
    (2, 2, 32, False),  # the tiny config: 64 merged lanes, half a tile
    (4, 2, 32, False),  # GQA, groups of 2
    (6, 2, 16, True),  # GQA, groups of 3, int8 pool: per-head scale lanes
    (8, 8, 16, False),  # 128 merged lanes: exactly one tile
    (3, 3, 16, True),  # odd head count, 48 lanes, int8
])
def test_fused_kernel_reads_the_folded_pool_bit_for_bit(heads, kv_heads, head_dim, quant):
    """`paged_decode_attention` on a ``[num_blocks, block_tokens, kv_heads *
    head_dim]`` pool against XLA attention over the gathered, unfolded view
    (what `paged_decode_update` hands the gather path): the same bits."""
    from accelerate_tpu.models.kv_cache import _dq, _q
    from accelerate_tpu.ops.attention import attention
    from accelerate_tpu.ops.flash_attention import paged_decode_attention

    rows, bps, num_blocks = 3, 4, 16
    span = bps * BT
    rng = np.random.default_rng(heads * 100 + head_dim)
    dtype = jnp.float32  # the parity bar is the engine tests': float32 compute
    q = jnp.asarray(rng.normal(size=(rows, heads, head_dim)), dtype)
    k4, v4 = (jnp.asarray(rng.normal(size=(num_blocks, BT, kv_heads, head_dim)), dtype)
              for _ in range(2))
    tables = jnp.asarray(rng.permutation(num_blocks)[: rows * bps].reshape(rows, bps), jnp.int32)
    tables = tables.at[2, 2:].set(num_blocks)  # a released tail: the clamped sentinel
    lengths = jnp.asarray([span, 21, 32], jnp.int32)  # full, mid-block, block-exact

    def fold(x):
        return x.reshape(num_blocks, BT, kv_heads * head_dim)

    def view(pool, *tail):
        return pool[jnp.minimum(tables, num_blocks - 1)].reshape((rows, span) + tail)

    if quant:
        (kq, ks), (vq, vs) = _q(k4), _q(v4)
        got = paged_decode_attention(q, fold(kq), fold(vq), tables, lengths,
                                     k_scale_pool=ks, v_scale_pool=vs)
        k_all = _dq(view(kq, kv_heads, head_dim), view(ks, kv_heads), dtype)
        v_all = _dq(view(vq, kv_heads, head_dim), view(vs, kv_heads), dtype)
    else:
        got = paged_decode_attention(q, fold(k4), fold(v4), tables, lengths)
        k_all, v_all = view(k4, kv_heads, head_dim), view(v4, kv_heads, head_dim)
    mask = (jnp.arange(span)[None, :] < lengths[:, None])[:, None, None, :]
    want = attention(q[:, None], k_all, v_all, causal=False, mask=mask,
                     implementation="xla")[:, 0]
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_fused_kernel_refuses_an_unfolded_pool():
    from accelerate_tpu.ops.flash_attention import paged_decode_attention

    q = jnp.zeros((2, 2, 32))
    tables, lengths = jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match=r"kv_heads \* head_dim"):
        paged_decode_attention(q, jnp.zeros((4, BT, 2, 32)), jnp.zeros((4, BT, 2, 32)),
                               tables, lengths)
    with pytest.raises(ValueError, match="no multiple of q head_dim"):
        paged_decode_attention(q, jnp.zeros((4, BT, 48)), jnp.zeros((4, BT, 48)),
                               tables, lengths)


def test_paged_frontier_partial_fill_masking(model):
    """Prompt lengths straddling the block quantum — mid-block frontier
    (21), exactly-full block (16, 32), one-short (15, 31) — decode appends
    into a partially filled frontier block and must mask the unwritten tail
    of that block exactly (any leak changes the argmax)."""
    module, params = model
    prompts = _prompts(3, (21, 16, 32, 15, 31))
    engine = ServingEngine(module, params, max_concurrency=5,
                           prompt_buckets=(16, 32), pipeline_depth=2,
                           admit_batch=2, paged_kv=True)
    outs = engine.run(_requests(prompts, n_new=20))
    for o in outs:
        assert o.tokens == _solo(module, params, prompts[o.request_id], 20,
                                 seed=o.request_id)


def test_paged_sampling_parity(model):
    """Seeded sampling rides the same paged data path as greedy: per-request
    streams match solo generate bit-for-bit (same host, same reductions)."""
    module, params = model
    prompts = _prompts(11, (6, 19, 33))
    engine = ServingEngine(module, params, max_concurrency=3,
                           prompt_buckets=(8, 64), pipeline_depth=2,
                           admit_batch=2, paged_kv=True)
    outs = engine.run(_requests(prompts, n_new=10, greedy=False))
    for o in outs:
        assert o.tokens == _solo(module, params, prompts[o.request_id], 10,
                                 temperature=0.8, top_k=7, seed=o.request_id)


def test_paged_prefix_hit_parity_zero_copy_aliasing(model):
    """Prefix-cache hits under paged KV are table aliasing, not copies: the
    sharer's table rows point at the SAME pool blocks the trie pins, streams
    stay solo-identical, and the gauges balance at every step."""
    module, params = model
    r = np.random.default_rng(5)
    shared = r.integers(0, 256, (40,)).astype(np.int32).tolist()
    prompts = [shared + [100 + i] for i in range(4)]
    engine = ServingEngine(
        module, params, max_concurrency=2, prompt_buckets=(8, 64),
        pipeline_depth=2, admit_batch=2, paged_kv=True,
        prefix_cache=True,
    )
    # warm: first request donates its 2 full prompt blocks at retirement
    first = engine.run(_requests(prompts[:1], n_new=6))[0]
    assert first.tokens == _solo(module, params, prompts[0], 6, seed=0)
    assert engine.metrics.prefix_blocks_donated.value == 2
    trie_blocks = set()
    for req in _requests(prompts[1:], n_new=6):
        assert engine.submit(req).accepted
    outs = []
    while engine.has_work:
        outs.extend(engine.step())
        mem = engine.memory_stats()
        assert (mem["block_pool/blocks_free"]
                + mem["block_pool/blocks_resident"]
                + mem["block_pool/blocks_private"]
                == mem["block_pool/blocks_total"])
        # zero-copy check: every in-flight sharer's aliased table entries ARE
        # the trie's pinned block ids (no gather copy, same storage)
        for slot in range(engine.max_concurrency):
            m = engine._slot_match[slot]
            if m is not None and m.nodes:
                aliased = int(engine._slot_aliased[slot])
                table = engine._slot_table_host[slot]
                assert ([int(x) for x in table[:aliased]]
                        == list(m.block_ids[:aliased]))
                trie_blocks.update(m.block_ids[:aliased])
    # ids are assigned in creation order, so sorted ids map 1:1 onto prompts
    by_id = {o.request_id: o.tokens for o in outs}
    for n, rid in enumerate(sorted(by_id)):
        assert by_id[rid] == _solo(module, params, prompts[1 + n], 6, seed=n)
    assert engine.metrics.prefix_hits.value == 3
    assert trie_blocks, "no aliased admission observed"
    mem = engine.memory_stats()
    assert mem["block_pool/blocks_pinned"] == 0
    assert mem["block_pool/blocks_private"] == 0


# ------------------------------------------------------------- backpressure
def test_block_exhaustion_backpressures_not_crashes(model):
    """A pool sized for ~2 reservations with 4 free slots: admission must
    wait for blocks, every request still finishes solo-identical, and the
    pool drains back to fully free."""
    module, params = model
    prompts = _prompts(9, (40, 38, 41, 39))
    reqs = _requests(prompts, n_new=20)
    engine = ServingEngine(
        module, params, max_concurrency=4, prompt_buckets=(64,),
        pipeline_depth=2, admit_batch=4,
        paged_kv=PagedKVConfig(block_tokens=BT, num_blocks=8),
    )
    for q in reqs:
        assert engine.submit(q).accepted
    peak, outs = 0, {}
    while engine.has_work:
        for o in engine.step():
            outs[o.request_id] = o.tokens
        peak = max(peak, engine.memory_stats()["slots_active"])
    # each request reserves ceil((40+20)/16)=4 blocks -> at most 2 seated
    assert peak == 2, f"block gate should cap in-flight at 2, saw {peak}"
    for n, rid in enumerate(sorted(outs)):
        assert outs[rid] == _solo(module, params, prompts[n], 20, seed=n)
    mem = engine.memory_stats()
    assert mem["block_pool/blocks_free"] == 8  # fully reclaimed
    assert engine.capacity_headroom()["blocks_free"] == 8


def test_refcount_pin_blocks_eviction_of_aliased_prefix_mid_decode(model):
    """While a sharer decodes over trie-aliased blocks, those blocks are
    pinned: a competing request whose reservation would need them is
    backpressured (requeued), NOT satisfied by evicting live storage. The
    moment the sharer retires, eviction may proceed and the waiter admits."""
    module, params = model
    r = np.random.default_rng(13)
    prefix = r.integers(0, 256, (37,)).astype(np.int32).tolist()
    big = r.integers(0, 256, (62,)).astype(np.int32).tolist()
    engine = ServingEngine(
        module, params, max_concurrency=2, prompt_buckets=(8, 64),
        pipeline_depth=1, admit_batch=1,
        paged_kv=PagedKVConfig(block_tokens=BT, num_blocks=8),
        prefix_cache=True,
    )
    # warm the trie: 2 donated blocks
    warm = engine.run(_requests([prefix], n_new=4))[0]
    assert warm.tokens == _solo(module, params, prefix, 4, seed=0)
    # sharer A aliases both trie blocks (pin), reserves 2 private
    a = Request(prefix + [1, 2, 3],
                params=SamplingParams(max_new_tokens=16, temperature=0.0, seed=0))
    assert engine.submit(a).accepted
    engine.step()
    mem = engine.memory_stats()
    assert mem["block_pool/blocks_pinned"] == 2
    assert mem["block_pool/blocks_evictable"] == 0
    # B needs ceil((62+50)/16)=7 blocks; free is 8-2(private A)=4... plus
    # nothing evictable while A pins the trie -> B must wait
    b = Request(list(big),
                params=SamplingParams(max_new_tokens=50, temperature=0.0, seed=9))
    assert engine.submit(b).accepted
    for _ in range(3):
        engine.step()
        assert engine.scheduler.queue_depth == 1, \
            "B admitted while A's pins made its reservation impossible"
        assert engine.metrics.prefix_evictions.value == 0
    outs = {}
    while engine.has_work:
        for o in engine.step():
            outs[o.request_id] = o
    assert outs[a.request_id].tokens == _solo(
        module, params, a.prompt, 16, seed=0)
    assert outs[b.request_id].tokens == _solo(
        module, params, big, 50, seed=9)
    # B's admission needed one eviction once A unpinned (7 > 6 free)
    assert engine.metrics.prefix_evictions.value >= 1
    mem = engine.memory_stats()
    assert mem["block_pool/blocks_pinned"] == 0
    assert (mem["block_pool/blocks_free"] + mem["block_pool/blocks_resident"]
            == mem["block_pool/blocks_total"])


def test_retire_reclaims_exactly_the_unpinned_blocks(model):
    """Retirement frees a slot's private blocks and (with the trie on)
    adopts the full prompt blocks: free + resident must account for every
    block, with resident exactly the donated prompt blocks."""
    module, params = model
    prompts = _prompts(21, (37, 20))
    # no trie: every block returns to the free list at retirement
    plain = ServingEngine(module, params, max_concurrency=2,
                          prompt_buckets=(64,), paged_kv=True)
    total = plain.memory_stats()["block_pool/blocks_total"]
    plain.run(_requests(prompts, n_new=6))
    assert plain.memory_stats()["block_pool/blocks_free"] == total
    assert plain._allocator.owned_count == 0
    # trie on: the full prompt blocks (37//16=2, 20//16=1) move to the trie,
    # everything else (frontier + decode blocks) returns to the free list
    cached = ServingEngine(module, params, max_concurrency=2,
                           prompt_buckets=(64,), paged_kv=True,
                           prefix_cache=True)
    cached.run(_requests(prompts, n_new=6))
    mem = cached.memory_stats()
    assert mem["block_pool/blocks_resident"] == 3
    assert mem["block_pool/blocks_free"] == mem["block_pool/blocks_total"] - 3
    assert mem["block_pool/blocks_pinned"] == 0
    assert mem["block_pool/blocks_private"] == 0


# ----------------------------------------------------------------- headroom
def test_paged_headroom_reports_blocks_and_stays_monotone(model):
    module, params = model
    engine = ServingEngine(module, params, max_concurrency=4,
                           prompt_buckets=(8,), max_queue=8, paged_kv=True)
    idle = engine.capacity_headroom()
    assert idle["blocks_free"] == engine._allocator.num_blocks
    assert idle["blocks_per_request_est"] == float(engine._blocks_per_slot)
    seen = [idle]
    for i in range(4):
        assert engine.submit(Request(
            prompt=[1 + i, 2, 3, 4],
            params=SamplingParams(max_new_tokens=40, temperature=0.0),
        )).accepted
        engine.step()
        seen.append(engine.capacity_headroom())
    assert [h["slots_free"] for h in seen] == [4, 3, 2, 1, 0]
    for prev, cur in zip(seen, seen[1:]):
        assert cur["admissible_requests"] <= prev["admissible_requests"]
        assert (cur["token_capacity_remaining"]
                <= prev["token_capacity_remaining"])
        assert cur["blocks_free"] <= prev["blocks_free"]
    # active estimate prices real reservations, not the worst case
    assert seen[-1]["blocks_per_request_est"] == 3.0  # ceil((4+40)/16)


# ------------------------------------------------------------------ sharded
@pytest.mark.sharded
def test_paged_mesh_parity_with_prefix_hits(model):
    """The (2, 2) acceptance cell: a mesh-sharded paged engine — two waves
    through one engine so wave 2 admits via CACHED aliasing — must match the
    unsharded engine and solo ``generate`` token-for-token."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    module, params = model
    r = np.random.default_rng(7)
    shared = r.integers(0, 256, (24,)).astype(np.int32).tolist()
    waves = [
        [shared + r.integers(0, 256, (k,)).astype(np.int32).tolist()
         for k in (3, 5, 4)]
        for _ in range(2)
    ]

    def serve_waves(mesh):
        engine = ServingEngine(
            module, params, max_concurrency=4, prompt_buckets=(8, 32),
            pipeline_depth=2, admit_batch=4, mesh=mesh, prefix_cache=True,
        )
        out = {}
        for wave in waves:
            for o in engine.run(_requests(wave, n_new=6)):
                out[len(out)] = (tuple(o.tokens), o.finish_reason)
        return out, engine

    base = {i: (tuple(_solo(module, params, p, 6)), FINISH_LENGTH)
            for i, p in enumerate(p for wave in waves for p in wave)}
    paged_local, _ = serve_waves(None)
    paged_mesh, engine = serve_waves((2, 2))
    assert paged_local == base
    assert paged_mesh == base
    assert engine.metrics.prefix_hits.value >= 3
    mem = engine.memory_stats()
    assert (mem["block_pool/blocks_free"] + mem["block_pool/blocks_resident"]
            == mem["block_pool/blocks_total"])
