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


CHUNK = 256  # tokens a turn of the kernel covers at these widths (`_paged_decode_chunk_blocks`)
SPAN_BLOCKS = 2 * CHUNK // BT + 8  # two chunks and half of a third
SPAN = SPAN_BLOCKS * BT


def _kernel_case(heads, kv_heads, head_dim, quant, lengths, dtype=jnp.float32,
                 released=(), poison=None, seed=None, interpret=None):
    """`paged_decode_attention` on a ``[num_blocks, block_tokens, kv_heads *
    head_dim]`` pool, and XLA attention in float32 over the gathered,
    unfolded view (what `paged_decode_update` hands the gather path).

    Row ``i`` holds ``lengths[i]`` positions in distinct blocks; its table
    entries past them, and every entry of the rows in ``released``, are the
    sentinel id the engine parks a released slot at. With ``poison`` every
    block no row holds, and the frontier block's tail past each length, reads
    that value (in the scale planes of an int8 pool): the oracle is still
    handed the clean pool."""
    from accelerate_tpu.models.kv_cache import _dq, _q
    from accelerate_tpu.ops.attention import attention
    from accelerate_tpu.ops.flash_attention import paged_decode_attention

    rows = len(lengths)
    live = [0 if i in released else -(-n // BT) for i, n in enumerate(lengths)]
    num_blocks = sum(live) + 3  # three blocks no row holds, the last among them
    rng = np.random.default_rng(
        heads * 100 + head_dim if seed is None else seed)
    q = jnp.asarray(rng.normal(size=(rows, heads, head_dim)), dtype)
    k4, v4 = (jnp.asarray(rng.normal(size=(num_blocks, BT, kv_heads, head_dim)), dtype)
              for _ in range(2))
    ids = iter(rng.permutation(num_blocks - 1))  # never the clamped sentinel's block
    tables = np.full((rows, SPAN_BLOCKS), num_blocks, np.int32)
    for i, n in enumerate(live):
        tables[i, :n] = [next(ids) for _ in range(n)]
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)

    def fold(x):
        return x.reshape(num_blocks, BT, -1)

    def view(pool, *tail):
        return pool[jnp.minimum(tables, num_blocks - 1)].reshape((rows, SPAN) + tail)

    def poisoned(x):
        """``x [num_blocks, BT, ...]`` with ``poison`` wherever no row's live
        positions are."""
        held = np.zeros((num_blocks, BT), bool)
        for i, n in enumerate(np.asarray(lengths)):
            if i not in released:
                held[np.asarray(tables)[i, : -(-n // BT)]] = True
                held[np.asarray(tables)[i, (n - 1) // BT], (n - 1) % BT + 1:] = False
        keep = jnp.asarray(held).reshape((num_blocks, BT) + (1,) * (x.ndim - 2))
        return jnp.where(keep, x, jnp.asarray(poison, x.dtype))

    if quant:
        (kq, ks), (vq, vs) = _q(k4), _q(v4)
        ks_in, vs_in = (ks, vs) if poison is None else (poisoned(ks), poisoned(vs))
        got = paged_decode_attention(q, fold(kq), fold(vq), tables, lengths,
                                     k_scale_pool=ks_in, v_scale_pool=vs_in,
                                     interpret=interpret)
        k_all = _dq(view(kq, kv_heads, head_dim), view(ks, kv_heads), dtype)
        v_all = _dq(view(vq, kv_heads, head_dim), view(vs, kv_heads), dtype)
    else:
        k_in, v_in = (k4, v4) if poison is None else (poisoned(k4), poisoned(v4))
        got = paged_decode_attention(q, fold(k_in), fold(v_in), tables, lengths,
                                     interpret=interpret)
        k_all, v_all = view(k4, kv_heads, head_dim), view(v4, kv_heads, head_dim)
    mask = (jnp.arange(SPAN)[None, :] < lengths[:, None])[:, None, None, :]
    f32 = jnp.float32
    want = attention(q[:, None].astype(f32), k_all.astype(f32), v_all.astype(f32),
                     causal=False, mask=mask, implementation="xla")[:, 0]
    return np.asarray(got, np.float32), np.asarray(want)


# float32 rounding, not the same bits: the kernel accumulates the softmax chunk
# by chunk (a running max) where the oracle takes one global max; over 640
# positions of unit-normal keys the two part by a few last places of 1.0
KERNEL_ATOL = 3e-6

# lengths of one call: the full span, mid-block, block-exact, a single
# position, one short of a block, one past a chunk boundary, chunk-exact,
# and a released row (all-sentinel table, a stale length)
KERNEL_LENGTHS = (SPAN, 21, 2 * BT, 1, BT - 1, CHUNK + 1, CHUNK, 37)


@pytest.mark.parametrize("heads, kv_heads, head_dim, quant", [
    (2, 2, 32, False),  # the tiny config: 64 merged lanes, half a tile
    (4, 2, 32, False),  # GQA, groups of 2
    (6, 2, 16, True),  # GQA, groups of 3, int8 pool: per-head scale lanes
    (8, 8, 16, False),  # 128 merged lanes: exactly one tile
    (3, 3, 16, True),  # odd head count, 48 lanes, int8
    (16, 2, 256, False),  # the Qwen3-Next attention layer: 8 queries a key/value head
    (20, 20, 64, False),  # gpt2-large: 1,280 lanes
])
def test_fused_kernel_reads_the_folded_pool_to_float32_rounding(heads, kv_heads, head_dim, quant):
    """One body on the chip and here (under the Pallas interpreter): it equals
    the gather oracle to float32 rounding at every kind of length a row can
    have, for a released row too, at MHA and GQA head shapes."""
    got, want = _kernel_case(heads, kv_heads, head_dim, quant, KERNEL_LENGTHS, released=(7,))
    np.testing.assert_allclose(got, want, rtol=0, atol=KERNEL_ATOL)


@pytest.mark.parametrize("dtype, quant, atol", [
    (jnp.float32, False, KERNEL_ATOL),
    (jnp.float32, True, KERNEL_ATOL),
    (jnp.bfloat16, False, 2e-2),  # the output is rounded to bfloat16 (8 bits)
    (jnp.bfloat16, True, 2e-2),
])
def test_fused_kernel_hands_chunk_buffers_from_row_to_row(dtype, quant, atol):
    """More rows than the two chunk buffers: a row's first chunk is fetched
    while the row before it is reduced, into whichever buffer that row's last
    turn left free, so rows of one, two and three turns in every order must
    each read their own keys and values. bfloat16 pools take the MXU path the
    cells run (bf16 x bf16 scores, probabilities as three bf16 pieces)."""
    lengths = (5, SPAN, 300, 17, CHUNK, CHUNK + 40, SPAN - 1, 2, 2 * CHUNK, 100, 2 * CHUNK + 1)
    got, want = _kernel_case(4, 2, 32, quant, lengths, dtype=dtype, seed=5)
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_fused_kernel_waits_for_every_copy_it_reads(quant):
    """The same body under JAX's TPU interpreter, which models the manual
    copies and their semaphores: a copy lands only when the kernel waits for it
    (``on_wait``), buffers start as NaN, and a happens-before detector watches
    every buffer. The double-buffered fetch across turns and rows reads nothing
    early, and gives the bits the plain interpreter gives."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter
    from jax.experimental.pallas import tpu as pltpu

    lengths = (5, SPAN, 300, CHUNK, CHUNK + 1, 2)
    plain, want = _kernel_case(4, 2, 32, quant, lengths, seed=9)
    try:
        got, _ = _kernel_case(4, 2, 32, quant, lengths, seed=9, interpret=pltpu.InterpretParams(
            detect_races=True, dma_execution_mode="on_wait"))
        assert not tpu_interpreter.races.races_found
    finally:
        pltpu.reset_tpu_interpret_mode_state()
    np.testing.assert_array_equal(got, plain)
    np.testing.assert_allclose(got, want, rtol=0, atol=KERNEL_ATOL)


@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_fused_kernel_masks_by_select(poison, quant):
    """S11. A retired request leaves what it wrote: blocks no row holds (a
    released row's clamped sentinel points at one) and the frontier block's
    tail past ``length`` read NaN or inf here. The scores there are replaced
    and the value rows selected to zero, never multiplied by a zero
    probability, so the output is finite and the same bits as from a clean
    pool. (An int8 pool's payload cannot hold either; its scale planes do.)"""
    clean, want = _kernel_case(4, 2, 32, quant, KERNEL_LENGTHS, released=(7,))
    got, _ = _kernel_case(4, 2, 32, quant, KERNEL_LENGTHS, released=(7,), poison=poison)
    # the released row reads the clamped sentinel's block as its stale length's
    # live positions, poison and all: nobody reads its output
    got, clean, want = got[:7], clean[:7], want[:7]
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    np.testing.assert_allclose(got, want, rtol=0, atol=KERNEL_ATOL)


@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
def test_engine_serves_on_over_what_retired_requests_left(model, poison):
    """S11 at the engine. A first wave retires and every block is free; all of
    them then read NaN or inf, as after a request whose state went non-finite.
    The second wave is admitted into those blocks: its prompts overwrite what
    they cover, and the frontier blocks' tails and the blocks reserved ahead
    of the cursor keep the poison under the fused kernel's mask. Every request
    still ends ``length`` with the tokens of a solo run."""
    module, params = model
    second = _prompts(22, (7, 30, 12, 18))
    refs = [_solo(module, params, p, 12, seed=i) for i, p in enumerate(second)]
    engine = ServingEngine(module, params, max_concurrency=4, prompt_buckets=(16, 64),
                           admit_batch=2, paged_attention="fused")
    engine.run(_requests(_prompts(21, (5, 23, 40, 9))))
    assert engine._allocator.owned_count == 0

    def left_behind(path, leaf):
        if path[-1].key in ("cached_key", "cached_value"):
            return jnp.full_like(leaf, poison)
        return leaf

    engine._cache = jax.tree_util.tree_map_with_path(left_behind, engine._cache)
    outs = engine.run(_requests(second))
    assert [o.finish_reason for o in outs] == [FINISH_LENGTH] * 4
    assert [o.tokens for o in outs] == refs


@pytest.mark.parametrize("lengths", [(9,), (9, 20, 5)])
def test_paged_decode_counters_follow_the_tokens_held(model, lengths):
    """`serving/paged_decode/live_tokens` adds, at each decode dispatch, the
    keys and values the held rows have (a row of prompt ``p`` that has been
    delivered ``i`` tokens attends ``p + i`` positions in its next step);
    ``span_tokens`` adds ``n_positions`` a held row. Unpipelined, the host's
    view is the device's: the sums are exact, and release returns every
    token."""
    module, params = model
    n_new, n_positions = 12, module.config.n_positions
    engine = ServingEngine(module, params, max_concurrency=4, prompt_buckets=(16, 64),
                           pipeline_depth=1, admit_batch=4)
    engine.run(_requests(_prompts(3, lengths), n_new=n_new))
    snap = engine.metrics.snapshot()
    assert snap["serving/paged_decode/live_tokens"] == sum(
        p + i for p in lengths for i in range(1, n_new))
    assert snap["serving/paged_decode/span_tokens"] == len(lengths) * (n_new - 1) * n_positions
    assert engine._held_kv_tokens == 0 and not any(engine._slot_kv_tokens)


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
