"""Fused LM-head + cross-entropy as a Pallas TPU kernel, with custom VJP.

Role: the profiled train step (docs/PERF_NOTES.md) spends ~15% on the LM head
and CE softmax over the [tokens, vocab] fp32 logits — written, re-read by
log-softmax, and re-materialized in the backward. This kernel streams vocab
tiles flash-attention-style: for each row chunk the logits tile lives only in
VMEM, reduced online to (logsumexp, label-logit); the backward recomputes tiles
against the saved lse. The full logits tensor never exists in HBM, and unlike
the `lax.scan` chunked CE (`models.gpt2.chunked_cross_entropy`) there is no
serialized scan carry — row chunks run as parallel grid cells.

Design (pallas_guide.md idioms):
  - grid = (row_chunks, vocab_chunks); vocab is the last (sequential) dim so
    the running max / sum / label-logit live in VMEM scratch.
  - logits accumulate in fp32 via the MXU (preferred_element_type); the label
    gather is a one-hot compare-and-reduce on the VPU (no dynamic indexing).
  - vocab padded to the tile width; padded columns masked to -inf statically.
  - per-row outputs stored 8-lane broadcast ([N, 8]) — narrowest Mosaic tile.
  - backward = two kernels: dH (rows parallel, vocab sequential) and dW
    (vocab parallel, rows sequential), both recomputing p = exp(logits - lse).
  - block_v default 1024: at 2048 the backward's per-cell working set
    (double-buffered [block_v, e] weight tile + the fused logits/p/dlogits
    intermediates) was measured by Mosaic at 18.68 MiB — over the 16 MiB
    scoped-VMEM limit on v5e at e=768. HBM traffic is unchanged by block_v
    (the full vocab streams once per row chunk either way).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _on_tpu() -> bool:
    from ..utils.environment import on_tpu_platform

    return on_tpu_platform()


# --------------------------------------------------------------------- forward
def _fwd_kernel(h_ref, w_ref, lab_ref, lse_ref, ll_ref, m_scr, l_scr, ll_scr, *, vocab, block_v, nv):
    jv = pl.program_id(1)

    @pl.when(jv == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        ll_scr[:] = jnp.zeros_like(ll_scr)

    h = h_ref[...]  # [R, e]
    w = w_ref[...]  # [block_v, e]
    logits = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [R, block_v]
    col = jv * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < vocab, logits, NEG_INF)
    lab = lab_ref[...][:, :1]  # [R, 1]
    ll_scr[:, :1] += jnp.sum(jnp.where(col == lab, logits, 0.0), axis=-1, keepdims=True)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    l_scr[:, :1] = l_scr[:, :1] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=-1, keepdims=True
    )
    m_scr[:, :1] = m_new

    @pl.when(jv == nv - 1)
    def _():
        safe_l = jnp.where(l_scr[:, :1] == 0.0, 1.0, l_scr[:, :1])
        lse_ref[...] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(safe_l), lse_ref.shape)
        ll_ref[...] = jnp.broadcast_to(ll_scr[:, :1], ll_ref.shape)


def _dh_kernel(h_ref, w_ref, lab_ref, lse_ref, glse_ref, gll_ref, dh_ref, dh_scr, *, vocab, block_v, nv):
    jv = pl.program_id(1)

    @pl.when(jv == 0)
    def _():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    h = h_ref[...]
    w = w_ref[...]
    logits = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    col = jv * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < vocab, logits, NEG_INF)
    p = jnp.exp(logits - lse_ref[...][:, :1])
    lab = lab_ref[...][:, :1]
    dlogits = glse_ref[...][:, :1] * p + gll_ref[...][:, :1] * (col == lab)
    dh_scr[:] += jax.lax.dot_general(
        dlogits.astype(w.dtype), w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(jv == nv - 1)
    def _():
        dh_ref[...] = dh_scr[:].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, lab_ref, lse_ref, glse_ref, gll_ref, dw_ref, dw_scr, *, vocab, block_v, nr):
    ir = pl.program_id(1)  # rows sequential
    jv = pl.program_id(0)

    @pl.when(ir == 0)
    def _():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    h = h_ref[...]
    w = w_ref[...]
    logits = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    col = jv * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < vocab, logits, NEG_INF)
    p = jnp.exp(logits - lse_ref[...][:, :1])
    lab = lab_ref[...][:, :1]
    dlogits = glse_ref[...][:, :1] * p + gll_ref[...][:, :1] * (col == lab)
    dw_scr[:] += jax.lax.dot_general(
        dlogits.astype(h.dtype), h, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ir == nr - 1)
    def _():
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)


def _lse_ll(h, w, labels, vocab_true, block_r, block_v, interpret):
    n, e = h.shape
    vpad, _ = w.shape
    nr, nv = n // block_r, vpad // block_v
    lab8 = jnp.broadcast_to(labels[:, None], (n, 8)).astype(jnp.int32)
    lse, ll = pl.pallas_call(
        functools.partial(_fwd_kernel, vocab=vocab_true, block_v=block_v, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((block_r, e), lambda ir, jv: (ir, 0)),
            pl.BlockSpec((block_v, e), lambda ir, jv: (jv, 0)),
            pl.BlockSpec((block_r, 8), lambda ir, jv: (ir, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, 8), lambda ir, jv: (ir, 0)),
            pl.BlockSpec((block_r, 8), lambda ir, jv: (ir, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 8), jnp.float32),
            jax.ShapeDtypeStruct((n, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_r, 128), jnp.float32),
            pltpu.VMEM((block_r, 128), jnp.float32),
            pltpu.VMEM((block_r, 128), jnp.float32),
        ],
        interpret=interpret,
    )(h, w, lab8)
    return lse[:, 0], ll[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_head_lse(h, w, labels, vocab, block_r, block_v, interpret):
    return _lse_ll(h, w, labels, vocab, block_r, block_v, interpret)


def _fused_fwd(h, w, labels, vocab, block_r, block_v, interpret):
    lse, ll = _lse_ll(h, w, labels, vocab, block_r, block_v, interpret)
    return (lse, ll), (h, w, labels, lse)


def _fused_bwd(vocab, block_r, block_v, interpret, res, g):
    h, w, labels, lse = res
    glse, gll = g
    n, e = h.shape
    vpad = w.shape[0]
    nr, nv = n // block_r, vpad // block_v
    lab8 = jnp.broadcast_to(labels[:, None], (n, 8)).astype(jnp.int32)
    lse8 = jnp.broadcast_to(lse[:, None], (n, 8)).astype(jnp.float32)
    glse8 = jnp.broadcast_to(glse[:, None], (n, 8)).astype(jnp.float32)
    gll8 = jnp.broadcast_to(gll[:, None], (n, 8)).astype(jnp.float32)

    dh = pl.pallas_call(
        functools.partial(_dh_kernel, vocab=vocab, block_v=block_v, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((block_r, e), lambda ir, jv: (ir, 0)),
            pl.BlockSpec((block_v, e), lambda ir, jv: (jv, 0)),
            pl.BlockSpec((block_r, 8), lambda ir, jv: (ir, 0)),
            pl.BlockSpec((block_r, 8), lambda ir, jv: (ir, 0)),
            pl.BlockSpec((block_r, 8), lambda ir, jv: (ir, 0)),
            pl.BlockSpec((block_r, 8), lambda ir, jv: (ir, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, e), lambda ir, jv: (ir, 0)),
        out_shape=jax.ShapeDtypeStruct((n, e), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_r, e), jnp.float32)],
        interpret=interpret,
    )(h, w, lab8, lse8, glse8, gll8)

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, vocab=vocab, block_v=block_v, nr=nr),
        grid=(nv, nr),
        in_specs=[
            pl.BlockSpec((block_r, e), lambda jv, ir: (ir, 0)),
            pl.BlockSpec((block_v, e), lambda jv, ir: (jv, 0)),
            pl.BlockSpec((block_r, 8), lambda jv, ir: (ir, 0)),
            pl.BlockSpec((block_r, 8), lambda jv, ir: (ir, 0)),
            pl.BlockSpec((block_r, 8), lambda jv, ir: (ir, 0)),
            pl.BlockSpec((block_r, 8), lambda jv, ir: (ir, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, e), lambda jv, ir: (jv, 0)),
        out_shape=jax.ShapeDtypeStruct((vpad, e), w.dtype),
        scratch_shapes=[pltpu.VMEM((block_v, e), jnp.float32)],
        interpret=interpret,
    )(h, w, lab8, lse8, glse8, gll8)
    import numpy as np

    dlabels = np.zeros(labels.shape, jax.dtypes.float0)  # int primal: zero cotangent
    return dh, dw, dlabels


_fused_head_lse.defvjp(_fused_fwd, _fused_bwd)


def _loss_sum_count(hidden, wte, labels, ignore_index, block_r, block_v, interpret):
    """Summed CE over the valid rows of one shard, and how many were valid."""
    n, e = hidden.shape
    v = wte.shape[0]
    mask = labels != ignore_index
    safe = jnp.where(mask, labels, 0).astype(jnp.int32)
    # shrink blocks BEFORE padding so tiny inputs don't pad up to a full
    # 512/2048 block of wasted rows/columns (Mosaic minimum tile: 8 x 128)
    block_r = min(block_r, -(-n // 8) * 8)
    block_v = min(block_v, -(-v // 128) * 128)
    rpad = (-n) % block_r
    if rpad:
        hidden = jnp.pad(hidden, ((0, rpad), (0, 0)))
        safe = jnp.pad(safe, (0, rpad))
        mask = jnp.pad(mask, (0, rpad))
    vpad = (-v) % block_v
    if vpad:
        wte = jnp.pad(wte, ((0, vpad), (0, 0)))
    lse, ll = _fused_head_lse(hidden, wte, safe, v, block_r, block_v, interpret)
    return ((lse - ll) * mask).sum(), mask.sum()


def fused_cross_entropy(
    hidden: jax.Array,  # [N, e] compute dtype
    wte: jax.Array,  # [V, e]
    labels: jax.Array,  # [N] int
    ignore_index: int = -100,
    block_r: int = 512,
    block_v: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Mean CE over valid rows with the tied head fused in; the [N, V] logits
    tensor never reaches HBM. Differentiable w.r.t. hidden and wte.

    Under a live multi-device mesh the kernels run per shard
    (`parallel.mesh.pallas_shard_axes`): rows split over the batch axes, every
    shard holds the whole head, and the row sum and valid count are reduced
    across shards before the division."""
    if interpret is None:
        interpret = not _on_tpu()
    local = functools.partial(
        _loss_sum_count, ignore_index=ignore_index, block_r=block_r, block_v=block_v,
        interpret=interpret,
    )
    from ..parallel.mesh import pallas_shard_axes

    live = pallas_shard_axes(hidden.shape[0])
    if live is None:
        total, count = local(hidden, wte, labels)
        return total / jnp.maximum(count, 1)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, rows = live

    def per_shard(hidden, wte, labels):
        total, count = local(hidden, wte, labels)
        if rows is not None:
            total, count = jax.lax.psum((total, count), rows)
        return total / jnp.maximum(count, 1)

    return shard_map(
        per_shard, mesh=mesh, in_specs=(P(rows, None), P(None, None), P(rows)),
        out_specs=P(), check_vma=False,
    )(hidden, wte, labels)
