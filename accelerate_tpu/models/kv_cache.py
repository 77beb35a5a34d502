"""Decode-time KV cache: the flax decode idiom (fixed-length buffers, running
write index) shared by every autoregressive model in the zoo, with optional
int8 blockwise storage (one fp32 absmax scale per (batch, position, kv-head)).

The int8 saving is storage/capacity: the cache occupies half the HBM, so
longer contexts (or more serving slots) fit per chip. It is a *bandwidth* win
only when XLA fuses the int8->fp32 convert into the attention matmuls — the
update below dequantizes the full ``[b, max_len, kv_heads, head_dim]`` buffer
every decode step, so an unfused backend materializes a compute-dtype copy and
pays the full-precision bandwidth term anyway. Beyond the reference: its bnb
integration quantizes weights only.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class CacheContract:
    """What a causal LM declares about its decode cache, for the serving
    engine (``module.config.cache_contract()``; `docs/serving.md` "The cache
    contract"). The config also carries the engine's cache switches
    (``kv_cache_per_slot``, ``kv_cache_paged``, ``kv_num_blocks``,
    ``kv_block_tokens``, ``kv_paged_attention``, ``kv_cache_sharding``,
    ``n_positions``) and the module takes `GPT2LMHead`'s decode arguments.

    Leaves of the ``cache`` collection are told apart by name:
    ``cache_index`` is the per-slot write cursor, names in ``state_leaves``
    are per-slot recurrent state ``[slots, ...]`` (never block-addressable:
    admission writes a slot's whole state, a finished slot's is frozen by
    ``cache_write_mask``), everything else is keys/values (and their int8
    scales) in the per-slot or paged layout. A model with state leaves gets
    ``cache_write_len`` = each row's true prompt length in its admit program.

    ``step_counters`` names int32 scalars the module sows into a ``counters``
    collection (summed over layers); the decode step returns them beside its
    tokens and `ServingMetrics` accumulates them. ``param_rules`` gives the
    parameter sharding rules for a serving mesh.

    ``value_dim`` set says that a layer's cache is ONE latent leaf
    (``cached_latent``, rows of ``head_dim`` lanes shared by all query heads:
    ``kv_heads`` is 1) whose leading ``value_dim`` lanes are the value: there
    is no value pool, and the fused kernel reads the value out of the key
    chunk it already holds. ``None`` is a value pool of its own.

    ``value_head_dim`` is the head size of that value pool where it differs
    from the keys' (MiMo-V2: keys 192, values 128): the pool's value leaf is
    then ``[num_blocks, block_tokens, kv_heads * value_head_dim]``, stored at
    its own width and read so by the fused kernel. ``None`` is ``head_dim``.

    ``kv_heads``, ``head_dim`` and ``value_head_dim`` describe the PAGED
    pool's layers. A model whose layers differ in kind (MiMo-V2's and
    K-EXAONE's sliding layers) may keep other layers' caches as state leaves
    at head counts and sizes of their own (per-slot window rings): the
    engine sizes and checks the pool from this contract and counts state
    leaves by their bytes.

    ``compute_dtype_params`` names the parameter leaves the module consumes
    only through a cast to ``config.dtype``: a predicate over a leaf's path
    of key names, e.g. ``("block_0", "attn", "qkv", "kernel")``. The engine
    holds each such floating leaf that is wider than ``config.dtype`` in
    ``config.dtype``, cast once at construction; every product then gets the
    operand values it got from the wider leaf (`docs/serving.md` "Parameter
    residency"). ``None`` leaves the param tree as it is given."""

    kv_heads: int
    head_dim: int
    state_leaves: tuple[str, ...] = ()
    step_counters: tuple[str, ...] = ()
    param_rules: Callable[[], Any] | None = None
    value_dim: int | None = None
    value_head_dim: int | None = None
    compute_dtype_params: Callable[[tuple[str, ...]], bool] | None = None


LATENT_LEAF = "cached_latent"


def _kv_leaf_names(v) -> tuple[str, ...]:
    """A layer's payload leaves: keys and values, or (``v is None``) the one
    latent leaf."""
    return (LATENT_LEAF,) if v is None else ("cached_key", "cached_value")


def _check_cache_dtype(kv_cache_dtype, latent: bool) -> bool:
    """True for an int8 store; any other dtype, and int8 for a latent leaf,
    fails fast with the cause named (an arbitrary dtype would surface as an
    obscure lax dtype-mismatch deep in the cache update)."""
    if kv_cache_dtype is None:
        return False
    if np.dtype(kv_cache_dtype) != np.dtype("int8"):
        raise ValueError(
            f"kv_cache_dtype supports None (compute dtype) or int8, got {kv_cache_dtype}"
        )
    if latent:
        raise ValueError(
            "kv_cache_dtype=int8 is not supported for a latent cache leaf: its row "
            "is shared by all heads and one absmax scale a row would quantise the "
            "compressed keys and the rotary lanes together"
        )
    return True


def _q(x):
    """Blockwise int8 quantization: one fp32 absmax scale per trailing-axis
    group (per (…, kv-head) row). Returns ``(int8 values, fp32 scales)``;
    all-zero rows get scale 1.0 so the dequantized zero stays exact."""
    absmax = jnp.abs(x.astype(jnp.float32)).max(axis=-1)
    scale = jnp.where(absmax > 0, absmax, 1.0) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dq(q, scale, dtype):
    """Inverse of `_q`: int8 values × fp32 scales, cast to compute dtype."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def decode_cache_update(
    mod: Any,  # the flax module (self) owning the "cache" collection
    k: jax.Array,  # [b, s, kv_heads, head_dim] new keys
    v: jax.Array | None,  # None: k is a latent row, the one leaf of the layer
    max_len: int,
    kv_cache_dtype: Any = None,  # None = store at k.dtype; int8 = quantized
    per_slot: bool = False,  # [b]-vector write index (continuous batching)
    write_mask: jax.Array | None = None,  # [b] bool: False rows freeze (per_slot)
    write_len: jax.Array | None = None,  # [b] int32: per-row segment length cap
    sharding: Any = None,  # parallel.sharding.KVCacheSharding: in-jit mesh layout
) -> tuple[jax.Array, jax.Array, jax.Array, bool]:
    """Create/update the module's decode cache and return
    ``(k_all, v_all, write_index, is_init)``.

    ``k_all``/``v_all`` are the full ``[b, max_len, ...]`` buffers in compute
    dtype (dequantized when stored int8) — on the first (shape-init) trace they
    are just ``k``/``v`` and ``is_init`` is False. ``write_index`` is the cache
    position the new entries were written at.

    ``per_slot=True`` replaces the scalar write index shared by the whole batch
    with a ``[b]`` vector: row ``i`` writes its new entries at its own
    ``cache_index[i]`` (the serving engine's admission rows, where every row sits
    at a different position in an independent sequence — `serving/engine.py`).
    ``write_index`` is then the ``[b]`` vector and row starts clamp into range
    exactly like ``dynamic_update_slice``.

    ``write_mask`` (per_slot only) freezes rows where it is False: the row's
    buffers and write index are left bit-identical instead of being written.
    This is the serving engine's on-device finished mask — with pipelined
    dispatch the host's retirement lags the device by up to ``pipeline_depth``
    steps, and a finished slot must not keep mutating its cache while it waits
    to be recycled.

    ``sharding`` (a `parallel.sharding.KVCacheSharding`, per_slot path) pins
    the updated buffers to the serving mesh layout with in-jit sharding
    constraints — heads on the ``model`` axis, slots optionally on ``data`` —
    so XLA's propagation cannot drift the donated pool cache's layout between
    steps. ``None`` (the default, and all of training) changes nothing.

    ``v=None`` keeps ONE leaf, ``cached_latent`` (`CacheContract.value_dim`):
    ``k`` is then the latent row and ``v_all`` comes back ``None``.
    """
    quant = _check_cache_dtype(kv_cache_dtype, v is None)
    if write_mask is not None and not per_slot:
        raise ValueError(
            "write_mask requires per_slot=True (the scalar-index cache has no "
            "per-row freeze semantics)"
        )
    if write_len is not None and not per_slot:
        raise ValueError(
            "write_len requires per_slot=True (per-row segment clamping is a "
            "per-slot decode concept)"
        )
    b, s, kv_heads, head_dim = k.shape
    store_dtype = jnp.int8 if quant else k.dtype
    names = _kv_leaf_names(v)
    news = (k,) if v is None else (k, v)
    is_init = mod.has_variable("cache", names[0])
    # a value head may be narrower than the key's: each leaf at its own width
    cached = [mod.variable("cache", name, jnp.zeros,
                           (b, max_len, kv_heads, new.shape[-1]), store_dtype)
              for name, new in zip(names, news)]
    cached_k, cached_v = cached[0], cached[-1]
    if quant:
        k_scale = mod.variable("cache", "key_scale", jnp.zeros,
                               (b, max_len, kv_heads), jnp.float32)
        v_scale = mod.variable("cache", "value_scale", jnp.zeros,
                               (b, max_len, kv_heads), jnp.float32)
    cache_idx = mod.variable(
        "cache", "cache_index",
        lambda: jnp.zeros((b,) if per_slot else (), jnp.int32),
    )

    if not is_init:
        return k, v, cache_idx.value, False

    idx = cache_idx.value
    next_idx = idx + s
    if per_slot:
        # row-wise scatter: each batch row writes at its own index (vmapped
        # dynamic_update_slice keeps the update static-shape and fully jittable)
        if write_len is not None:
            # variable-length segment scatter (speculative verify,
            # serving/engine.py): row i writes only its first
            # clip(write_len[i], 0, s) new entries at idx[i].. — the rest
            # redirect past the buffer end and are dropped, so a verify
            # segment can never overrun a row's budget/context bound the way
            # a start-clamped dynamic_update_slice would (which silently
            # rewrites committed history once idx + s > max_len)
            wl = jnp.clip(write_len.astype(idx.dtype), 0, s)
            if write_mask is not None:
                wl = wl * write_mask.astype(wl.dtype)
            cols = idx[:, None] + jnp.arange(s, dtype=idx.dtype)[None, :]
            cols = jnp.where(jnp.arange(s)[None, :] < wl[:, None], cols, max_len)
            rows = jnp.arange(b)[:, None]
            row4 = lambda buf, new, i: buf.at[rows, cols].set(new, mode="drop")  # noqa: E731
            row3 = row4  # broadcasted [b, s] indices cover 3-d scale planes too
            next_idx = idx + wl
        elif write_mask is None:
            row4 = jax.vmap(lambda buf, new, i: jax.lax.dynamic_update_slice(buf, new, (i, 0, 0)))
            row3 = jax.vmap(lambda buf, new, i: jax.lax.dynamic_update_slice(buf, new, (i, 0)))
            next_idx = idx + s
        else:
            # frozen rows (mask False) re-write their CURRENT entries — a
            # bit-exact no-op — and keep their index, so a finished slot's
            # cache never moves while host retirement lags the device
            def _masked_row(lead_zeros):
                def upd(buf, new, i, m):
                    start = (i,) + (0,) * lead_zeros
                    cur = jax.lax.dynamic_slice(buf, start, new.shape)
                    return jax.lax.dynamic_update_slice(
                        buf, jnp.where(m, new, cur), start
                    )

                return jax.vmap(upd, in_axes=(0, 0, 0, 0))

            _row4, _row3 = _masked_row(2), _masked_row(1)
            row4 = lambda buf, new, i: _row4(buf, new, i, write_mask)  # noqa: E731
            row3 = lambda buf, new, i: _row3(buf, new, i, write_mask)  # noqa: E731
            next_idx = idx + s * write_mask.astype(idx.dtype)
        if quant:
            kq, ks = _q(k)
            vq, vs = _q(v)
            cached_k.value = row4(cached_k.value, kq, idx)
            cached_v.value = row4(cached_v.value, vq, idx)
            k_scale.value = row3(k_scale.value, ks, idx)
            v_scale.value = row3(v_scale.value, vs, idx)
            if sharding is not None:
                cached_k.value = jax.lax.with_sharding_constraint(cached_k.value, sharding.kv)
                cached_v.value = jax.lax.with_sharding_constraint(cached_v.value, sharding.kv)
                k_scale.value = jax.lax.with_sharding_constraint(k_scale.value, sharding.scale)
                v_scale.value = jax.lax.with_sharding_constraint(v_scale.value, sharding.scale)
            k_all = _dq(cached_k.value, k_scale.value, k.dtype)
            v_all = _dq(cached_v.value, v_scale.value, v.dtype)
        else:
            for var, new in zip(cached, news):
                var.value = row4(var.value, new, idx)
            if sharding is not None:
                for var in cached:
                    var.value = jax.lax.with_sharding_constraint(var.value, sharding.kv)
            k_all, v_all = cached_k.value, cached_v.value
        if sharding is not None:
            next_idx = jax.lax.with_sharding_constraint(next_idx, sharding.index)
    elif quant:
        kq, ks = _q(k)
        vq, vs = _q(v)
        cached_k.value = jax.lax.dynamic_update_slice(cached_k.value, kq, (0, idx, 0, 0))
        cached_v.value = jax.lax.dynamic_update_slice(cached_v.value, vq, (0, idx, 0, 0))
        k_scale.value = jax.lax.dynamic_update_slice(k_scale.value, ks, (0, idx, 0))
        v_scale.value = jax.lax.dynamic_update_slice(v_scale.value, vs, (0, idx, 0))
        k_all = _dq(cached_k.value, k_scale.value, k.dtype)
        v_all = _dq(cached_v.value, v_scale.value, v.dtype)
    else:
        for var, new in zip(cached, news):
            var.value = jax.lax.dynamic_update_slice(var.value, new, (0, idx, 0, 0))
        k_all, v_all = cached_k.value, cached_v.value
    cache_idx.value = next_idx
    return k_all, (None if v is None else v_all), idx, True


def _paged_frontier_write(
    pools: tuple[jax.Array, ...],  # per-leaf [num_blocks, block_tokens, ...] pools
    news: tuple[jax.Array, ...],  # congruent [b, s, ...] new rows to land
    idx: jax.Array,  # [b] int32 write cursors
    mask: jax.Array,  # [b] bool: False rows freeze (dropped write)
    write_len: jax.Array | None,  # [b] int32 per-row segment cap, or None (s==1)
    num_blocks: int,
    block_tokens: int,
    block_tables: jax.Array,  # [b, blocks_per_slot] int32 pool block ids
) -> tuple[tuple[jax.Array, ...], jax.Array]:
    """The append-at-frontier pool write shared by `paged_decode_update` and
    `paged_decode_write`: returns ``(new_pools, next_idx)``.

    ``pools``/``news`` are congruent tuples of pool leaves and their new
    rows — (K, V) at full precision, (K, V, K-scale, V-scale) when the pool
    stores int8 (the fp32 scale planes are ``[num_blocks, block_tokens,
    kv_heads]`` and land through the same block ids/offsets, so a KV byte and
    its scale can never diverge).

    ``write_len=None`` is the classic one-token step (``s == 1``). With
    ``write_len`` ([b] int32) the segment path lands row ``i``'s first
    ``clip(write_len[i], 0, s)`` entries at token positions ``idx[i]..``
    through the row's block table (speculative verify, `serving/engine.py`);
    the rest redirect to block id ``num_blocks`` and are dropped, so a verify
    segment can never write into blocks the row's reservation does not own.
    """
    b, s = news[0].shape[:2]
    # K/V rows arrive [b, s, kv_heads, head_dim]; the pool keeps the two merged
    # (`_paged_pool_step`): fold the few KB of new rows, never the pool
    news = tuple(new.reshape((b, s) + pool.shape[2:])
                 for pool, new in zip(pools, news))
    if write_len is None:
        bids = block_tables[jnp.arange(b), idx // block_tokens]  # [b]
        bids = jnp.where(mask, bids, num_blocks)  # frozen rows: dropped write
        offs = idx % block_tokens
        out = tuple(pool.at[bids, offs].set(new[:, 0], mode="drop")
                    for pool, new in zip(pools, news))
        return out, idx + mask.astype(idx.dtype)
    wl = jnp.clip(write_len.astype(idx.dtype), 0, s) * mask.astype(idx.dtype)
    cols = idx[:, None] + jnp.arange(s, dtype=idx.dtype)[None, :]  # [b, s]
    valid = jnp.arange(s)[None, :] < wl[:, None]
    bps = block_tables.shape[1]
    bids = block_tables[jnp.arange(b)[:, None],
                        jnp.clip(cols // block_tokens, 0, bps - 1)]
    bids = jnp.where(valid, bids, num_blocks)  # clamped/frozen: dropped write
    offs = cols % block_tokens
    out = tuple(pool.at[bids, offs].set(new, mode="drop")
                for pool, new in zip(pools, news))
    return out, idx + wl


def _paged_pool_step(
    mod: Any,
    k: jax.Array,
    v: jax.Array | None,
    num_blocks: int,
    block_tokens: int,
    block_tables: jax.Array | None,
    kv_cache_dtype: Any,
    write_mask: jax.Array | None,
    write_len: jax.Array | None,
    sharding: Any,
) -> tuple[tuple[jax.Array, ...], jax.Array, bool]:
    """Shared body of `paged_decode_update` / `paged_decode_write`: create the
    pool variables (int8 payload + fp32 scale planes when quantized), run the
    append-at-frontier write, pin shardings, commit.

    The K/V leaves are ``[num_blocks, block_tokens, kv_heads * head_dim]``,
    heads folded into the last dim: a TPU tiles an array's two trailing dims
    (bf16: 16 x 128), so ``block_tokens x (kv_heads*head_dim)`` is compact as
    plain row-major and the scatter, the Pallas kernel's DMA and the donated
    buffer all address the same bytes. Trailing ``kv_heads, head_dim`` would
    pad (20 x 64 to 32 x 128, 3.2x), and XLA then keeps the pool block-minor
    at each program's boundary and rewrites all of it on the way in and out.
    Returns
    ``(pool_leaves, write_index, is_init)`` where ``pool_leaves`` is
    ``(k_pool, v_pool)`` at full precision,
    ``(k_pool, v_pool, k_scale_pool, v_scale_pool)`` under int8, or, with
    ``v=None`` (a latent row, `CacheContract.value_dim`), the one
    ``(latent_pool,)`` of ``[num_blocks, block_tokens, head_dim]``."""
    quant = _check_cache_dtype(kv_cache_dtype, v is None)
    b, s, kv_heads, head_dim = k.shape
    store_dtype = jnp.int8 if quant else k.dtype
    names = _kv_leaf_names(v)
    is_init = mod.has_variable("cache", names[0])
    # the value leaf at its own head size (`CacheContract.value_head_dim`)
    cached = [mod.variable("cache", name, jnp.zeros,
                           (num_blocks, block_tokens, kv_heads * new.shape[-1]), store_dtype)
              for name, new in zip(names, (k,) if v is None else (k, v))]
    if quant:
        k_scale = mod.variable("cache", "key_scale", jnp.zeros,
                               (num_blocks, block_tokens, kv_heads), jnp.float32)
        v_scale = mod.variable("cache", "value_scale", jnp.zeros,
                               (num_blocks, block_tokens, kv_heads), jnp.float32)
    cache_idx = mod.variable("cache", "cache_index",
                             lambda: jnp.zeros((b,), jnp.int32))
    if not is_init:
        return (), cache_idx.value, False
    if s != 1 and write_len is None:
        raise ValueError(
            f"paged decode writes one token per step, got a length-{s} segment "
            "(prefill runs through the contiguous admission cache, then "
            "scatter_rows_to_blocks; multi-token verify segments must pass "
            "write_len)"
        )
    if block_tables is None:
        raise ValueError("paged decode needs block_tables ([b, blocks_per_slot])")
    idx = cache_idx.value  # [b]
    mask = (jnp.ones((b,), bool) if write_mask is None
            else write_mask.astype(bool))
    pools = tuple(var.value for var in cached)
    if quant:
        kq, ks = _q(k)
        vq, vs = _q(v)
        pools += (k_scale.value, v_scale.value)
        news = (kq, vq, ks, vs)
    else:
        news = (k,) if v is None else (k, v)
    new_pools, next_idx = _paged_frontier_write(
        pools, news, idx, mask, write_len,
        num_blocks, block_tokens, block_tables,
    )
    if sharding is not None:
        kv_specs = (sharding.kv,) * len(cached) + (
            (sharding.scale, sharding.scale) if quant else ())
        new_pools = tuple(
            jax.lax.with_sharding_constraint(leaf, spec)
            for leaf, spec in zip(new_pools, kv_specs)
        )
        next_idx = jax.lax.with_sharding_constraint(next_idx, sharding.index)
    for var, leaf in zip(cached, new_pools):
        var.value = leaf
    if quant:
        k_scale.value, v_scale.value = new_pools[2], new_pools[3]
    cache_idx.value = next_idx
    return new_pools, idx, True


def paged_decode_update(
    mod: Any,  # the flax module (self) owning the "cache" collection
    k: jax.Array,  # [b, s, kv_heads, head_dim] new keys (s == 1 unless write_len)
    v: jax.Array | None,  # None: k is a latent row, one pool a layer (v comes back None)
    num_blocks: int,  # pool size; block id == num_blocks is the dropped write
    block_tokens: int,
    block_tables: jax.Array | None,  # [b, blocks_per_slot] int32 pool block ids
    kv_cache_dtype: Any = None,  # None = store at k.dtype; int8 = quantized pool
    write_mask: jax.Array | None = None,  # [b] bool: False rows freeze
    write_len: jax.Array | None = None,  # [b] int32: per-row segment length cap
    sharding: Any = None,  # KVCacheSharding with pool kv / scale / index / gathered
) -> tuple[jax.Array, jax.Array, jax.Array, bool]:
    """Paged variant of `decode_cache_update`: the cache collection holds ONE
    shared ``[num_blocks, block_tokens, kv_heads * head_dim]`` block pool (per
    layer; heads folded into the last dim so the stored layout tiles, see
    `_paged_pool_step`) plus the per-slot ``[b]`` write cursor, and each row's
    KV lives wherever its block table says. Returns ``(k_all, v_all,
    write_index, is_init)`` exactly like `decode_cache_update`, with
    ``k_all``/``v_all`` the gathered ``[b, blocks_per_slot * block_tokens,
    kv_heads, head_dim]`` attended view (heads unfolded after the gather).

    Append-at-frontier write: row ``i``'s new entry lands in pool block
    ``block_tables[i, idx[i] // block_tokens]`` at offset
    ``idx[i] % block_tokens``. Rows frozen by ``write_mask`` redirect their
    write to block id ``num_blocks`` — out of range, dropped by the scatter —
    and keep their cursor, so a finished slot never mutates pool state while
    host retirement lags the device. Unallocated table entries (the engine
    leaves them 0) are never written — the cursor cannot reach past the
    blocks admission reserved for the row's prompt + budget — and reads of
    them are masked out of attention at the frontier, so stale pool contents
    cannot perturb a stream (the parity bar of `docs/serving.md`).

    ``kv_cache_dtype=int8`` stores the pool quantized: the int8 payload rides
    the same ``[num_blocks, block_tokens, kv_heads * head_dim]`` leaves and
    the fp32 absmax scales ride sibling ``key_scale``/``value_scale`` pool
    leaves of shape ``[num_blocks, block_tokens, kv_heads]`` — per-block
    planes addressed through the SAME block table, mirroring the contiguous
    cache's per-(batch, position, kv-head) scheme. The gathered attended view is
    dequantized here (scales gathered alongside the payload), so attention
    sees compute-dtype K/V either way.
    """
    b, s, kv_heads, head_dim = k.shape
    new_pools, idx, is_init = _paged_pool_step(
        mod, k, v, num_blocks, block_tokens, block_tables, kv_cache_dtype,
        write_mask, write_len, sharding,
    )
    if not is_init:
        return k, v, idx, False
    # the attended view: each row's table blocks concatenated in token order —
    # position p of row i sits at gathered index p (block p // block_tokens,
    # offset p % block_tokens), the same layout a contiguous per-slot cache
    # has, so the caller's frontier mask is the same for both
    blocks_per_slot = block_tables.shape[1]
    span = blocks_per_slot * block_tokens

    def _view(pool, *tail):
        # heads unfold on the gathered [b, span, ...] copy, not on the pool
        return pool[block_tables].reshape((b, span) + tail)

    if kv_cache_dtype is not None:
        new_k, new_v, new_ks, new_vs = new_pools
        k_all = _dq(_view(new_k, kv_heads, head_dim), _view(new_ks, kv_heads), k.dtype)
        v_all = _dq(_view(new_v, kv_heads, head_dim), _view(new_vs, kv_heads), v.dtype)
    elif v is None:
        return _view(new_pools[0], kv_heads, head_dim), None, idx, True
    else:
        new_k, new_v = new_pools
        k_all = _view(new_k, kv_heads, head_dim)
        v_all = _view(new_v, kv_heads, v.shape[-1])
    if sharding is not None and getattr(sharding, "gathered", None) is not None:
        k_all = jax.lax.with_sharding_constraint(k_all, sharding.gathered)
        v_all = jax.lax.with_sharding_constraint(v_all, sharding.gathered)
    return k_all, v_all, idx, True


def paged_decode_write(
    mod: Any,  # the flax module (self) owning the "cache" collection
    k: jax.Array,  # [b, s, kv_heads, head_dim] new keys (s == 1 unless write_len)
    v: jax.Array | None,  # None: k is a latent row, one pool a layer (v comes back None)
    num_blocks: int,  # pool size; block id == num_blocks is the dropped write
    block_tokens: int,
    block_tables: jax.Array | None,  # [b, blocks_per_slot] int32 pool block ids
    kv_cache_dtype: Any = None,  # None = store at k.dtype; int8 = quantized pool
    write_mask: jax.Array | None = None,  # [b] bool: False rows freeze
    write_len: jax.Array | None = None,  # [b] int32: per-row segment length cap
    sharding: Any = None,  # KVCacheSharding with pool kv / scale / index
) -> tuple[jax.Array, jax.Array, jax.Array, bool, tuple[jax.Array, jax.Array] | None]:
    """Write-only variant of `paged_decode_update` for the fused attention
    path: identical append-at-frontier write and cursor semantics, but returns
    the UPDATED POOL leaves — ``(k_pool, v_pool, write_index, is_init,
    scale_pools)`` with the pool still ``[num_blocks, block_tokens,
    kv_heads * head_dim]``, updated in place under donation — instead of
    gathering the contiguous ``[b, span, ...]`` attended view. The
    Pallas kernel (`ops.flash_attention.paged_decode_attention`) then reads
    the blocks in place through the block table, so no per-layer per-step
    gather copy is ever materialized. Frozen rows (``write_mask`` False) still
    redirect their write to the dropped block id and keep their cursor.

    ``scale_pools`` is ``None`` at full precision; under
    ``kv_cache_dtype=int8`` it is ``(k_scale_pool, v_scale_pool)`` — the fp32
    absmax planes (``[num_blocks, block_tokens, kv_heads]``) the kernel needs
    to dequantize each chunk in VMEM, so the pool is never
    materialized at fp32."""
    new_pools, idx, is_init = _paged_pool_step(
        mod, k, v, num_blocks, block_tokens, block_tables, kv_cache_dtype,
        write_mask, write_len, sharding,
    )
    if not is_init:
        return k, v, idx, False, None
    if kv_cache_dtype is not None:
        new_k, new_v, new_ks, new_vs = new_pools
        return new_k, new_v, idx, True, (new_ks, new_vs)
    if v is None:
        return new_pools[0], None, idx, True, None
    new_k, new_v = new_pools
    return new_k, new_v, idx, True, None


def leaf_name(path) -> str | None:
    """The variable name a cache (or sown) leaf was declared under."""
    return getattr(path[-1], "key", None)


def _is_index_leaf(path) -> bool:
    return leaf_name(path) == "cache_index"


def state_nbytes(cache: Any, state_leaves: tuple[str, ...]) -> int:
    """Device bytes of the per-slot recurrent-state leaves of a cache tree."""
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    return sum(int(leaf.nbytes) for path, leaf in flat if leaf_name(path) in state_leaves)


def rewind_frontier(cache: Any, new_index: jax.Array) -> Any:
    """Move every ``cache_index`` cursor leaf to ``new_index`` ([b] int32)
    without touching a single KV byte — the speculative-decoding rollback
    (`serving/engine.py`). A rejected draft's KV entries stay behind in the
    slot buffer / block pool, but the cursor retreat makes them dead state:
    the next write lands on top of them and the frontier mask keeps attention
    from ever reading past the cursor. Works unchanged for the contiguous
    per-slot, paged-gather, and paged-fused layouts because all three share
    the ``[b]`` cursor leaf — for the paged pool this is the promised
    block-table rollback with no pool copy."""

    def stamp(path, leaf):
        if _is_index_leaf(path):
            return new_index.astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(stamp, cache)


class BlockAllocator:
    """Host-side free-list over a device block pool's ids (paged KV serving,
    `docs/serving.md` "Paged KV").

    The pool itself is device state (the paged decode module's cache leaves,
    `make_cache`); this tracks
    which block ids are owned — by a slot's private frontier or by the prefix
    trie — purely on the host, so admission never round-trips the device to
    find space. Allocation is all-or-nothing: a request that cannot get every
    block it needs gets none (backpressure, never a half-placed request), and
    a double free fails loudly (an aliasing bug would otherwise corrupt two
    requests' KV silently).
    """

    def __init__(self, num_blocks: int):
        num_blocks = int(num_blocks)
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.num_blocks = num_blocks
        self._free: deque[int] = deque(range(num_blocks))
        self._owned: set[int] = set()

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def owned_count(self) -> int:
        return len(self._owned)

    def alloc(self, n: int) -> list[int] | None:
        """``n`` distinct block ids, or None when fewer than ``n`` are free
        (all-or-nothing — the caller evicts or backs off, never partial)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        ids = [self._free.popleft() for _ in range(n)]
        self._owned.update(ids)
        return ids

    def free(self, ids) -> None:
        """Return block ids to the free list (slot retirement / trie eviction)."""
        for b in ids:
            b = int(b)
            if b not in self._owned:
                raise ValueError(f"double free of block {b}")
            self._owned.discard(b)
            self._free.append(b)


# --------------------------------------------------------- byte accounting
def tree_nbytes(tree: Any) -> int:
    """Total device bytes of every array leaf in a cache/pool pytree — the
    exact allocation cost (`sum(leaf.nbytes)`), counting the int8 path's fp32
    absmax scales and the cache_index cursors alongside the KV buffers. The
    serving telemetry gauges (`serving/telemetry.py`) are contracted to match
    this number exactly; tests/test_telemetry.py holds them to it."""
    return sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(tree))


def tree_bytes_by_dtype(tree: Any) -> dict[str, int]:
    """Per-dtype byte split of a cache/pool pytree (dtype name -> bytes,
    sorted by name). Separates what int8 KV storage actually buys: the int8
    buffers shrink, the fp32 scale planes ride along at full precision."""
    out: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        name = str(np.dtype(leaf.dtype))
        out[name] = out.get(name, 0) + int(leaf.nbytes)
    return dict(sorted(out.items()))


def make_cache(module: Any, batch: int, shardings: Any = None) -> Any:
    """Allocate ``module``'s zeroed decode cache pytree for ``batch`` slots
    (the serving engine's block pool and per-slot cursor when the module's
    config is paged, ``[batch, n_positions, ...]`` rows when it is not)
    without running a real forward: shapes come from `jax.eval_shape` over
    ``module.init``, so no throwaway init compute touches the device.

    ``shardings`` is an optional congruent pytree of NamedShardings
    (`parallel.sharding.infer_cache_shardings`): each leaf is then allocated
    directly into its mesh placement — a model-sharded pool never materializes
    unsharded on one device, which is the whole point of serving models that
    do not fit a single chip.
    """
    shapes = jax.eval_shape(
        lambda: module.init(
            jax.random.key(0), jnp.zeros((batch, 1), jnp.int32), decode=True
        )["cache"]
    )
    if shardings is None:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return jax.tree.map(
        lambda s, sh: jax.device_put(jnp.zeros(s.shape, s.dtype), sh),
        shapes, shardings,
    )


def _constrain_tree(tree: Any, shardings: Any) -> Any:
    """Apply a congruent pytree of NamedShardings as in-jit constraints."""
    if shardings is None:
        return tree
    return jax.tree.map(jax.lax.with_sharding_constraint, tree, shardings)


def gather_block_rows(
    block_pool: Any,  # [num_blocks, block_tokens, ...] pool pytree
    block_tables: jax.Array,  # [nb, blocks_per_row] int32 pool block ids
    cache_index: jax.Array,  # [nb] int32 resume index (the cached prefix length)
    shardings: Any = None,  # congruent NamedShardings for the assembled rows
    like: Any = None,  # congruent [1, n_positions, ...] row shapes to unfold into
) -> Any:
    """Assemble ``nb`` cache rows from pool blocks in ONE gather per leaf: row
    ``i`` is ``block_tables[i]``'s blocks concatenated along the token axis
    (``blocks_per_row * block_tokens`` positions — the engine sizes the table
    so this equals ``n_positions``). Table entries past a row's real prefix
    may point anywhere valid: the positions they fill are overwritten by the
    suffix prefill or masked out of attention before anything reads them.
    ``cache_index`` leaves are set to ``cache_index`` so the suffix prefill
    writes (and attends) from each row's cached-prefix end.

    ``like`` (the admit module's cache shapes) gives each assembled row its
    trailing dims: the paged pool folds ``kv_heads * head_dim`` and the
    contiguous cache it is gathered into does not. Without it the rows keep
    the pool's.
    """

    def gather(path, leaf, row_like=None):
        if _is_index_leaf(path):
            return cache_index.astype(leaf.dtype)
        rows = leaf[block_tables]  # [nb, blocks_per_row, block_tokens, ...]
        tail = rows.shape[3:] if row_like is None else row_like.shape[2:]
        return rows.reshape((rows.shape[0], rows.shape[1] * rows.shape[2]) + tail)

    trees = (block_pool,) if like is None else (block_pool, like)
    return _constrain_tree(
        jax.tree_util.tree_map_with_path(gather, *trees), shardings
    )


def scatter_rows_to_blocks(
    paged_cache: Any,  # paged cache pytree: KV [num_blocks, block_tokens, ...], cache_index [B]
    new_cache: Any,  # an [nb, bucket, ...] freshly prefilled cache pytree
    slots: jax.Array,  # [nb] int32 slot rows whose write cursor to stamp
    dest_blocks: jax.Array,  # [nb, ceil(bucket / block_tokens)] pool ids; >= num_blocks drops
    cache_index: jax.Array,  # [nb] int32 per-row resume index (true prefill length)
    block_tokens: int,
    shardings: Any = None,  # congruent NamedShardings keeping the pool's layout
    state_leaves: tuple[str, ...] = (),  # names of per-slot recurrent-state leaves
) -> Any:
    """Paged admission: carve each freshly prefilled contiguous row into
    ``block_tokens``-sized pieces and scatter them into the row's allocated
    pool blocks in ONE op per leaf. ``dest_blocks[i, j]`` is where row ``i``'s
    ``j``-th piece lands; entries pointing past the pool (``num_blocks``)
    are dropped — that is how a cache hit's ALIASED prefix blocks (already
    resident, trie-pinned, shared zero-copy through the block table) and the
    pad region past a short bucket are skipped without a second compile.

    The ``cache_index`` leaf rows ``slots`` are stamped with ``cache_index``
    (the true prefill length — decode's append frontier: the prefill advanced
    the fresh rows' cursor to the padded bucket length, but decode must
    resume, and overwrite the pad entries, from each row's true prompt end).
    ``state_leaves`` are not paged: their fresh
    ``[nb, ...]`` rows overwrite the slots' whole state.
    """

    def scatter(path, pool_leaf, new_leaf):
        if _is_index_leaf(path):
            return pool_leaf.at[slots].set(cache_index.astype(pool_leaf.dtype))
        if leaf_name(path) in state_leaves:
            return pool_leaf.at[slots].set(new_leaf.astype(pool_leaf.dtype))
        nb, bucket = new_leaf.shape[:2]
        n_blk = dest_blocks.shape[1]
        pad = n_blk * block_tokens - bucket
        if pad:
            new_leaf = jnp.pad(
                new_leaf, [(0, 0), (0, pad)] + [(0, 0)] * (new_leaf.ndim - 2)
            )
        # the fresh rows are [nb, bucket, kv_heads, head_dim]; the pool folds
        # the last two (`_paged_pool_step`): fold the rows, a few MB
        pieces = new_leaf.reshape((nb * n_blk, block_tokens) + pool_leaf.shape[2:])
        return pool_leaf.at[dest_blocks.reshape(-1)].set(
            pieces.astype(pool_leaf.dtype), mode="drop"
        )

    return _constrain_tree(
        jax.tree_util.tree_map_with_path(scatter, paged_cache, new_cache), shardings
    )
