"""Blockwise (flash) attention as a Pallas TPU kernel, with custom VJP.

Role in the framework: the reference delegates fused attention to external native
engines (Megatron fused kernels / TransformerEngine — SURVEY.md §2.4, §2.8); here
the hot op is a first-party TPU kernel. O(S) memory instead of the O(S^2) logits
buffer, so long-context training doesn't spill HBM.

Design (TPU-idiomatic, per /opt/skills/guides/pallas_guide.md):
  - grid = (batch, heads, q_blocks, kv_blocks); the *last* grid dim runs
    sequentially on a TensorCore, so the running max/denominator/accumulator live
    in VMEM scratch across kv-block iterations — no atomics, no reduction pass.
  - logits/softmax accumulate in fp32 (MXU output precision) while tensor blocks
    stay in the input dtype (bf16 on TPU).
  - causal masking skips fully-masked kv blocks via predication.
  - backward = two kernels (dq; dkv) re-streaming K/V and Q respectively against
    the saved logsumexp, plus an XLA-fused delta = rowsum(dO*O) preprocess.
  - on CPU (tests) the same kernels run under the Pallas interpreter.

Layouts are [batch, seq, heads, head_dim] at the API, transposed to
[batch, heads, seq, head_dim] internally so each (b, h) grid cell addresses a
contiguous [seq, head_dim] tile. Head dims stay NATIVE (64 for GPT-2-class
models): Mosaic lane-pads tiles in VMEM but the HBM traffic is the real 64
columns — the round-2 kernel zero-padded to 128 in HBM, which doubled every
Q/K/V/dO tensor's bytes AND the dot FLOPs (profiled at 30% of the train step).
The per-row log-sum-exp / delta tensors use an 8-lane broadcast [b, h, s, 8]
(the narrowest layout Mosaic tiles) instead of the previous 128-lane broadcast
— 1/16 the fp32 bytes (50 MB -> 3 MB per tensor at bench shapes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _on_tpu() -> bool:
    from ..utils.environment import on_tpu_platform

    return on_tpu_platform()


# --------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *, causal, block_q, block_kv, nkv):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # skip kv blocks entirely above the diagonal when causal
    run = (not causal) or (ik * block_kv <= iq * block_q + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0, 0]  # [block_q, d]
        k = k_ref[0, 0]  # [block_kv, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_kv]
        if causal:
            q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            k_idx = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(k_idx <= q_idx, s, NEG_INF)
        m_prev = m_scr[:, :1]  # [block_q, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [block_q, block_kv]
        correction = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_new = correction * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nkv - 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(safe_l), lse_ref.shape[2:])


def _fwd(q, k, v, causal, block_q, block_kv, interpret):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nq, nkv = sq // block_q, skv // block_kv
    grid = (b, h, nq, nkv)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_kv=block_kv, nkv=nkv
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 8), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# -------------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc, *, causal, block_q, block_kv, nkv):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (not causal) or (ik * block_kv <= iq * block_q + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]  # [block_q, 1] (8-lane broadcast storage)
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            k_idx = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(k_idx <= q_idx, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_kv]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == nkv - 1)
    def _():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, causal, block_q, block_kv, nq):
    iq = pl.program_id(3)  # sequential axis: q blocks
    ik = pl.program_id(2)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (not causal) or (ik * block_kv <= iq * block_q + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            k_idx = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(k_idx <= q_idx, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_kv]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(causal, block_q, block_kv, interpret, residuals, dout):
    q, k, v, out, lse = residuals
    b, h, sq, d = q.shape
    skv = k.shape[2]
    nq, nkv = sq // block_q, skv // block_kv
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (b, h, sq, 8))  # 8-lane broadcast

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, block_q=block_q, block_kv=block_kv, nkv=nkv),
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 8), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 8), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, block_q=block_q, block_kv=block_kv, nq=nq),
        grid=(b, h, nkv, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, ik, iq: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, ik, iq: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 8), lambda b_, h_, ik, iq: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 8), lambda b_, h_, ik, iq: (b_, h_, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, ik, iq: (b_, h_, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


# ------------------------------------------- causal band (lower-triangle) grid
# For causal self-attention the rectangular grid wastes cells: above-diagonal
# blocks are predication-skipped but still fetched and iterated, and at
# s == block (one cell per (b, h)) half the computed logits are masked. This
# path enumerates ONLY the blocks inside the causal band into the last grid
# dimension, with block indices and first/last flags routed through
# scalar-prefetched maps (the splash-attention idiom). With window=None the
# band is the full lower triangle (T = nq(nq+1)/2 cells instead of nq^2, mask
# only on diagonal cells); with a sliding window W the band narrows to
# ~ceil(W/block)+1 cells per row, so compute scales with W, not seq^2 —
# Mistral-class sliding-window attention at native cost. Requires sq == skv
# and square blocks.


def _band_lo(iq: int, block: int, window: int | None) -> int:
    """Lowest kv block index row ``iq`` attends to (0 for pure causal)."""
    if window is None:
        return 0
    return max(0, (iq * block - window + 1) // block)


def _band_maps_row(nq: int, block: int, window: int | None):
    """Row-major band enumeration — kv index innermost so the fwd/dq
    accumulators run init(first-in-row) -> flush(last-in-row = diagonal)."""
    import numpy as np

    pairs = [
        (iq, ik) for iq in range(nq) for ik in range(_band_lo(iq, block, window), iq + 1)
    ]
    iqm = np.asarray([p[0] for p in pairs], np.int32)
    ikm = np.asarray([p[1] for p in pairs], np.int32)
    first = np.asarray(
        [1 if ik == _band_lo(iq, block, window) else 0 for iq, ik in pairs], np.int32
    )
    last = np.asarray([1 if ik == iq else 0 for iq, ik in pairs], np.int32)
    return iqm, ikm, first, last


def _band_maps_col(nq: int, block: int, window: int | None, groups: int = 1):
    """Column-major band enumeration for the dkv pass: for each kv column the
    sequential axis walks every (q-head-in-group, q-block) pair, so dk/dv
    accumulate in KV-HEAD shape with no cross-cell races even under GQA.
    init fires on the column's first pair, flush on its last."""
    import numpy as np

    pairs = [
        (g, iq, ik)
        for ik in range(nq)
        for g in range(groups)
        for iq in range(ik, nq)
        if ik >= _band_lo(iq, block, window)
    ]
    gm = np.asarray([p[0] for p in pairs], np.int32)
    iqm = np.asarray([p[1] for p in pairs], np.int32)
    ikm = np.asarray([p[2] for p in pairs], np.int32)
    cols = [p[2] for p in pairs]
    first = np.asarray(
        [1 if i == 0 or cols[i - 1] != cols[i] else 0 for i in range(len(pairs))], np.int32
    )
    last = np.asarray(
        [1 if i + 1 == len(pairs) or cols[i + 1] != cols[i] else 0 for i in range(len(pairs))],
        np.int32,
    )
    return iqm, ikm, gm, first, last


def _band_logits(q, k, iq, ik, block_q, block_kv, window):
    """QK^T for one band cell, masked per the causal(+window) rule — shared by
    all three band kernels so the masking cannot drift between forward and
    backward. Pure causal masks only diagonal cells; a sliding window also
    masks the low side (edge cells overhang the band by up to a block)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if window is None:
        return jnp.where((ik == iq) & (k_idx > q_idx), NEG_INF, s)
    bad = (k_idx > q_idx) | (k_idx < q_idx - (window - 1))
    return jnp.where(bad, NEG_INF, s)


def _fwd_band_kernel(iqm, ikm, first, last, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *, block_q, block_kv, window):
    t = pl.program_id(2)
    iq, ik = iqm[t], ikm[t]

    @pl.when(first[t] == 1)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = _band_logits(q, k, iq, ik, block_q, block_kv, window)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    correction = jnp.exp(m_prev - m_new)
    l_scr[:, :1] = correction * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc[:] = acc[:] * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(last[t] == 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(safe_l), lse_ref.shape[2:])


def _dq_band_kernel(iqm, ikm, first, last, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc, *, block_q, block_kv, window):
    t = pl.program_id(2)
    iq, ik = iqm[t], ikm[t]

    @pl.when(first[t] == 1)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][:, :1]
    delta = delta_ref[0, 0][:, :1]
    s = _band_logits(q, k, iq, ik, block_q, block_kv, window)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dq_acc[:] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(last[t] == 1)
    def _():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_band_kernel(iqm, ikm, gm, first, last, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_kv, window):
    t = pl.program_id(2)
    iq, ik = iqm[t], ikm[t]

    @pl.when(first[t] == 1)  # first cell of this kv column
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][:, :1]
    delta = delta_ref[0, 0][:, :1]
    s = _band_logits(q, k, iq, ik, block_q, block_kv, window)
    p = jnp.exp(s - lse)
    dv_acc[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    dk_acc[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(last[t] == 1)
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _band_grid_spec(n_cells, b, h, block_q, block_kv, d, n_in, out_specs, scratch_shapes, groups=1):
    """PrefetchScalarGridSpec over the linearized band; q-indexed inputs use
    iqm, kv-indexed use ikm (the four scalar-prefetch operands lead the kernel
    args). Under GQA (``groups`` > 1) the grid's head axis is the QUERY head
    and kv blocks come from head ``h // groups`` — K/V are never repeated in
    HBM. Scratch lives in the spec — pallas_call rejects it separately when a
    grid_spec is given."""
    q_spec = pl.BlockSpec(
        (1, 1, block_q, d), lambda b_, h_, t, iqm, ikm, first, last: (b_, h_, iqm[t], 0)
    )
    kv_spec = pl.BlockSpec(
        (1, 1, block_kv, d),
        lambda b_, h_, t, iqm, ikm, first, last: (b_, h_ // groups, ikm[t], 0),
    )
    row8 = pl.BlockSpec(
        (1, 1, block_q, 8), lambda b_, h_, t, iqm, ikm, first, last: (b_, h_, iqm[t], 0)
    )
    per_input = {"q": q_spec, "kv": kv_spec, "row8": row8}
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, h, n_cells),
        in_specs=[per_input[kind] for kind in n_in],
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )


def _band_grid_spec_dkv(n_cells, b, hk, block, d, out_specs, scratch_shapes, groups=1):
    """dkv-pass grid spec: head axis is the KV head; q-side inputs come from
    query head ``h * groups + gm[t]`` (five scalar-prefetch operands)."""
    q_spec = pl.BlockSpec(
        (1, 1, block, d),
        lambda b_, h_, t, iqm, ikm, gm, first, last: (b_, h_ * groups + gm[t], iqm[t], 0),
    )
    kv_spec = pl.BlockSpec(
        (1, 1, block, d), lambda b_, h_, t, iqm, ikm, gm, first, last: (b_, h_, ikm[t], 0)
    )
    row8 = pl.BlockSpec(
        (1, 1, block, 8),
        lambda b_, h_, t, iqm, ikm, gm, first, last: (b_, h_ * groups + gm[t], iqm[t], 0),
    )
    per_input = {"q": q_spec, "kv": kv_spec, "row8": row8}
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hk, n_cells),
        in_specs=[per_input[kind] for kind in ["q", "kv", "kv", "q", "row8", "row8"]],
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )


def _q_out_spec(block, d):
    return pl.BlockSpec(
        (1, 1, block, d), lambda b_, h_, t, iqm, ikm, first, last: (b_, h_, iqm[t], 0)
    )


def _kv_out_spec_dkv(block, d):
    return pl.BlockSpec(
        (1, 1, block, d), lambda b_, h_, t, iqm, ikm, gm, first, last: (b_, h_, ikm[t], 0)
    )


def _fwd_band(q, k, v, block, window, interpret):
    b, h, sq, d = q.shape
    groups = h // k.shape[1]
    nq = sq // block
    maps = _band_maps_row(nq, block, window)
    grid_spec = _band_grid_spec(
        len(maps[0]), b, h, block, block, d, ["q", "kv", "kv"],
        [
            _q_out_spec(block, d),
            pl.BlockSpec(
                (1, 1, block, 8), lambda b_, h_, t, iqm, ikm, first, last: (b_, h_, iqm[t], 0)
            ),
        ],
        [
            pltpu.VMEM((block, d), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
        ],
        groups=groups,
    )
    out, lse = pl.pallas_call(
        functools.partial(_fwd_band_kernel, block_q=block, block_kv=block, window=window),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 8), jnp.float32),
        ],
        interpret=interpret,
    )(*maps, q, k, v)
    return out, lse


def _bwd_band(block, window, interpret, residuals, dout):
    q, k, v, out, lse = residuals
    b, h, sq, d = q.shape
    hk = k.shape[1]
    groups = h // hk
    nq = sq // block
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (b, h, sq, 8))

    maps = _band_maps_row(nq, block, window)
    dq = pl.pallas_call(
        functools.partial(_dq_band_kernel, block_q=block, block_kv=block, window=window),
        grid_spec=_band_grid_spec(
            len(maps[0]), b, h, block, block, d,
            ["q", "kv", "kv", "q", "row8", "row8"],
            _q_out_spec(block, d),
            [pltpu.VMEM((block, d), jnp.float32)],
            groups=groups,
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*maps, q, k, v, dout, lse, delta)

    maps2 = _band_maps_col(nq, block, window, groups)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_band_kernel, block_q=block, block_kv=block, window=window),
        grid_spec=_band_grid_spec_dkv(
            len(maps2[0]), b, hk, block, d,
            [_kv_out_spec_dkv(block, d), _kv_out_spec_dkv(block, d)],
            [
                pltpu.VMEM((block, d), jnp.float32),
                pltpu.VMEM((block, d), jnp.float32),
            ],
            groups=groups,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(*maps2, q, k, v, dout, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_band(q, k, v, block, window, interpret):
    out, _ = _fwd_band(q, k, v, block, window, interpret)
    return out


def _flash_band_fwd(q, k, v, block, window, interpret):
    out, lse = _fwd_band(q, k, v, block, window, interpret)
    return out, (q, k, v, out, lse)


_flash_band.defvjp(_flash_band_fwd, _bwd_band)


# ------------------------------------------------------------------ public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_kv, interpret):
    out, _ = _fwd(q, k, v, causal, block_q, block_kv, interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_kv, interpret):
    out, lse = _fwd(q, k, v, causal, block_q, block_kv, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_kv, interpret, residuals, dout):
    return _bwd(causal, block_q, block_kv, interpret, residuals, dout)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _env_block(name: str, default: int) -> int:
    from ..utils.environment import parse_int_from_env

    return parse_int_from_env(name, default)


def band_block_default(sq: int) -> int | None:
    """Default band-grid block for a causal/windowed seq: the largest divisor
    of ``sq`` that is <= 512 (one tiling policy for the kernel and the
    dispatcher's auto routing). None when the best divisor is < 8 — a band
    grid that narrow (e.g. prime sq) degenerates to pathological 1-wide tiles."""
    best = next(b for b in range(min(512, sq), 0, -1) if sq % b == 0)
    return best if best >= 8 else None


def rect_blocks(sq: int, skv: int, block_q: int | None = None,
                block_kv: int | None = None) -> tuple[int, int]:
    """The rectangular grid's block sizes: the given ones, else the env-tunable
    defaults, shrunk to the sequence. The kernel and the dispatcher's auto
    routing share them: a length they do not divide raises in the kernel, so
    auto routes it to xla."""
    block_q = _env_block("ACCELERATE_TPU_FLASH_BLOCK_Q", 1024) if block_q is None else block_q
    block_kv = _env_block("ACCELERATE_TPU_FLASH_BLOCK_KV", 1024) if block_kv is None else block_kv
    return min(block_q, sq), min(block_kv, skv)


def flash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    triangle_block: int | None = None,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention over [batch, seq, heads, head_dim] inputs.

    Sequence lengths must divide the (auto-shrunk) block sizes. head_dim is
    used NATIVELY when it is a multiple of the 8-sublane width (64 for GPT-2
    class models — Mosaic lane-pads in VMEM, HBM moves only real bytes);
    other head dims are zero-padded up to the next multiple of 128.

    ``triangle_block`` (or env ``ACCELERATE_TPU_FLASH_TRIANGLE=<block>``)
    switches causal self-attention onto the band grid: only blocks inside the
    causal band exist as grid cells, halving attention FLOPs/fetches at large
    seq vs the rectangular grid's predication skip. ``window=W`` (sliding
    window: query i attends to keys in (i-W, i]) narrows the band so compute
    scales with W rather than seq — Mistral-class attention; it requires the
    band grid (``triangle_block``/env, defaulting to 512 when only ``window``
    is given).
    """
    b, sq, hn, d = q.shape
    skv = k.shape[1]
    if interpret is None:
        interpret = not _on_tpu()
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    if window is not None:
        if not causal or sq != skv:
            raise ValueError(
                "window applies only to causal self-attention (sq == skv); "
                f"got causal={causal}, sq={sq}, skv={skv}"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if triangle_block is None:
            triangle_block = _env_block("ACCELERATE_TPU_FLASH_TRIANGLE", 0) or None
            if triangle_block is None:
                best = band_block_default(sq)
                if best is None:  # e.g. prime sq: a 1-wide band grid is pathological
                    raise ValueError(
                        f"window={window} needs a band grid, but seq {sq} has no "
                        "block divisor >= 8. Pad the sequence to a tileable "
                        "length, pass triangle_block explicitly (or via "
                        "ACCELERATE_TPU_FLASH_TRIANGLE), or use "
                        "implementation='xla'."
                    )
                triangle_block = best
    # An EXPLICIT triangle_block is a strict request: reject configurations it
    # cannot serve rather than silently measuring the rectangular kernel. The
    # env knob is a global default (cross-attention in the same model must
    # still work), so it falls back silently instead.
    if triangle_block is not None:
        if not causal or sq != skv:
            raise ValueError(
                "triangle_block applies only to causal self-attention (sq == skv); "
                f"got causal={causal}, sq={sq}, skv={skv}"
            )
        if block_q is not None or block_kv is not None:
            raise ValueError("triangle_block and block_q/block_kv are mutually exclusive")
        if sq % min(triangle_block, sq):
            raise ValueError(
                f"triangle_block {triangle_block} must divide seq {sq}"
            )
    else:
        triangle_block = _env_block("ACCELERATE_TPU_FLASH_TRIANGLE", 0) or None

    hk = k.shape[2]
    if hn != hk and (hk == 0 or hn % hk):
        raise ValueError(f"q heads ({hn}) must be a multiple of kv heads ({hk})")

    qt = jnp.transpose(q, (0, 2, 1, 3)) * jnp.asarray(scale, q.dtype)
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    d_pad = 0 if d % 64 == 0 else (128 - d % 128) % 128
    if d_pad:
        pad = [(0, 0), (0, 0), (0, 0), (0, d_pad)]
        qt, kt, vt = jnp.pad(qt, pad), jnp.pad(kt, pad), jnp.pad(vt, pad)

    if causal and triangle_block and sq == skv and sq % min(triangle_block, sq) == 0:
        # GQA runs natively on the band grid: kv blocks are fetched from head
        # h // groups, so K/V are never repeated in HBM and dk/dv come back in
        # kv-head shape
        out = _flash_band(qt, kt, vt, min(triangle_block, sq), window, interpret)
    else:
        if hn != hk:
            groups = hn // hk
            kt = jnp.repeat(kt, groups, axis=1)
            vt = jnp.repeat(vt, groups, axis=1)
        # Block defaults are env-tunable for sweeps (ACCELERATE_TPU_FLASH_BLOCK_*).
        # 1024×1024 won the round-3 sweep (docs/PERF_NOTES.md): at s<=1024 the
        # whole (b,h) attention runs in ONE grid cell, and the [block_q, block_kv]
        # fp32 logits tile (4 MB) still fits VMEM comfortably; longer sequences
        # fall back to 1024-wide tiles.
        block_q, block_kv = rect_blocks(sq, skv, block_q, block_kv)
        if sq % block_q or skv % block_kv:
            raise ValueError(
                f"seq lengths ({sq}, {skv}) must divide block sizes ({block_q}, {block_kv})"
            )
        out = _flash(qt, kt, vt, causal, block_q, block_kv, interpret)
    if d_pad:
        out = out[..., :d]
    return jnp.transpose(out, (0, 2, 1, 3))


# ------------------------------------------------- paged decode (serving)
# The fused kernel streams a row's LIVE keys and values through two chunk
# buffers a pool (`_paged_decode_chunk_blocks` pool blocks each, 128-256
# tokens) under an online softmax, so its VMEM is a function of the folded row
# width ``kv_heads * head_dim`` alone: no term grows with ``n_positions``.
# 128 MiB is the v5e core's VMEM (measured: XLA reports "Used 128.71M of
# 128.00M" one size past the cap).
_VMEM_BYTES = 128 << 20
PAGED_DECODE_VMEM_CAP = _VMEM_BYTES - (16 << 20)  # leave XLA's own share
_PAGED_DECODE_HEADROOM = 8 << 20  # scores, probabilities, Mosaic's own temporaries


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _paged_decode_chunk_blocks(block_tokens: int, width: int, latent: bool = False) -> int:
    """Pool blocks one chunk covers: 256 tokens while a float32 working copy
    of the chunk (``tokens x width x 4``) stays within 4 MiB (rows to 4,096
    lanes), else 128 tokens; never under one block. A latent pool's chunk is
    512 tokens: its one narrow row a token makes a turn's fixed cost, not
    its bytes, what a row pays (v5e, PR 32: 1.20 against 1.38 ms a call at
    the Kimi cell's shape with blocks of 64; `PERF.md` section 6)."""
    if latent:
        tokens = 512
    else:
        tokens = 256 if 256 * _ceil_to(width, 128) * 4 <= (4 << 20) else 128
    return max(1, tokens // block_tokens)


def paged_decode_vmem_bytes(
    kv_heads: int, head_dim: int, *, q_heads: int | None = None,
    block_tokens: int = 16, itemsize: int = 4, value_dim: int | None = None,
) -> int:
    """VMEM the fused paged-decode kernel asks for. Per pool (keys, values;
    one alone for a latent leaf, ``value_dim`` set: the value is lanes of the
    key chunk) two chunk buffers ``chunk_tokens x ceil128(kv_heads * head_dim) x
    itemsize``, one float32 working copy of a chunk each (the upcast of a
    float32 or int8 pool; a bfloat16 pool feeds the MXU as it is), the folded
    query and the float32 accumulator ``[q_heads, width]`` twice over (the
    carry and its update), and fixed headroom. ``itemsize`` is the pool's
    (4 prices the widest); nothing here depends on the attended span."""
    lanes = _ceil_to(kv_heads * head_dim, 128)
    # a pool block is a buffer block: its tokens pad to the dtype's sublane tile
    tokens = (_paged_decode_chunk_blocks(block_tokens, lanes, value_dim is not None)
              * _ceil_to(block_tokens, 32 // itemsize))
    chunks = (2 if value_dim is None else 1) * tokens * lanes * (2 * itemsize + 4)
    heads = _ceil_to(q_heads or kv_heads, 8)
    return chunks + 4 * heads * lanes * 4 + _PAGED_DECODE_HEADROOM


def check_paged_decode_fits(
    kv_heads: int, head_dim: int, *, q_heads: int | None = None,
    block_tokens: int = 16, itemsize: int = 4, value_dim: int | None = None,
) -> int:
    """Raise with the sizes named when the kernel cannot hold its chunk
    buffers for rows of ``kv_heads`` x ``head_dim`` in VMEM; returns the bytes
    it will ask for. The serving engine calls this at construction, so
    ``paged_attention="fused"`` fails there instead of at first decode."""
    need = paged_decode_vmem_bytes(
        kv_heads, head_dim, q_heads=q_heads, block_tokens=block_tokens,
        itemsize=itemsize, value_dim=value_dim)
    if need > PAGED_DECODE_VMEM_CAP:
        tokens = _paged_decode_chunk_blocks(
            block_tokens, kv_heads * head_dim, value_dim is not None) * block_tokens
        raise ValueError(
            f"fused paged decode streams chunks of {tokens} positions of the "
            f"folded row through VMEM: {kv_heads} kv heads x head_dim "
            f"{head_dim} = {kv_heads * head_dim} lanes needs "
            f"{need / 2**20:.0f} MiB (two chunk buffers and a float32 working "
            f"copy a pool, the accumulators, headroom), over the "
            f"{PAGED_DECODE_VMEM_CAP / 2**20:.0f} MiB this kernel may use of "
            f"the core's {_VMEM_BYTES / 2**20:.0f} MiB. The attended span is "
            "no term of it: shard heads over the model axis, or use "
            "paged_attention='gather'."
        )
    return need


def _paged_decode_kernel(
    tables, lengths, q_ref, k_hbm, *rest,
    block_tokens, chunk_blocks, scale, groups, quant, value_dim=None,
):
    """One grid cell = one slot row; inside it a loop of ``cdiv(length,
    chunk)`` turns, each over one CHUNK of ``chunk_blocks`` pool blocks, so a
    row costs its live blocks and nothing past its frontier is fetched, staged
    or zero-filled.

    The pools stay in HBM (``memory_space=pltpu.HBM``). A turn waits for its
    chunk (one copy a live pool block, block ``tables[row, j]`` into buffer
    block ``j % chunk_blocks``) after it has started the copies of the next
    one into the other buffer: the row's next chunk, or the next row's first,
    so the fetch runs across the row boundary too (``slot_ref`` carries which
    buffer that was from one grid cell to the next; the grid is sequential).

    All heads go through the MXU at once. The pool's folded row is the
    operand: ``Q' [heads, kv_heads * d]`` holds head ``h``'s query in the lanes
    of key/value head ``h // groups`` and zeros elsewhere, so ``Q' x chunk^T``
    is every head's scores ``[heads, chunk]`` and ``P x chunk`` every head's
    output, in its own lanes among the others', picked out at the row's end.
    Scores, the running max and sum, the probabilities and the accumulator are
    float32; ``scale`` is applied after the dot. Keys and values enter with the
    values the pool holds: a bfloat16 chunk feeds the MXU as it is (bf16 x bf16
    products are exact in the float32 accumulation), and the float32
    probabilities meet it as three bfloat16 pieces whose sum they are, so
    nothing is rounded that the float32 x float32 product would keep.

    Masking is by select, never by multiply: positions at or past ``length``
    (the frontier block's tail, and whatever an earlier chunk left in the
    buffer) have their scores replaced and their value rows selected to zero,
    so a NaN a retired request wrote cannot reach the output through 0 x NaN.

    An int8 pool rides one more HBM ref: both pools' float32 absmax scales as
    the caller gathered them by the block table, ``[rows, 2 * kv_heads, span]``
    with positions on the lanes, a chunk's worth copied beside its payload
    and stood up ``[chunk, 2 * kv_heads]`` in VMEM. The chunk is dequantised
    into a staging buffer (value x its head's scale over that head's ``d``
    lanes, through the compute dtype, exactly the gather oracle's `_dq`) and
    from there on the arithmetic is the same.

    A latent pool (``value_dim`` set; no value pool, ``v_hbm`` absent) is the
    absorbed form of latent attention: one row of ``width`` lanes a token that
    every head shares (``kv_heads`` 1, ``Q'`` is the query itself), and the
    value is lanes ``[0, value_dim)`` of the key chunk already in VMEM, so a
    chunk costs one copy a block and not two; the output is ``[heads,
    value_dim]``."""
    latent = value_dim is not None
    if latent:
        v_hbm = vbuf = sc_hbm = scbuf = kstage = vstage = None
        o_ref, kbuf, *rest = rest
    elif quant:
        v_hbm, sc_hbm, o_ref, kbuf, vbuf, scbuf, kstage, vstage, *rest = rest
    else:
        sc_hbm = scbuf = kstage = vstage = None
        v_hbm, o_ref, kbuf, vbuf, *rest = rest
    sems, slot_ref, qp_ref, acc_ref = rest
    pools = ((k_hbm, kbuf),) if latent else ((k_hbm, kbuf), (v_hbm, vbuf))
    row = pl.program_id(0)
    rows = pl.num_programs(0)
    hq, d = q_ref.shape[1], q_ref.shape[2]
    width = kbuf.shape[-1]
    kvh = width // d
    chunk = chunk_blocks * block_tokens
    span_blocks = tables.shape[1]
    f32 = jnp.float32

    def live_blocks(r):  # pool blocks row r holds
        return jnp.clip(pl.cdiv(lengths[r], block_tokens), 0, span_blocks)

    def copies(r, c, slot, wait):
        """Start (or wait for) the copies of chunk ``c`` of row ``r``."""
        first = c * chunk_blocks
        n = jnp.clip(live_blocks(r) - first, 0, chunk_blocks)

        def one(i, carry):
            blk = tables[r, first + i]
            for p, (hbm, buf) in enumerate(pools):
                cp = pltpu.make_async_copy(hbm.at[blk], buf.at[slot, i], sems.at[p, slot])
                cp.wait() if wait else cp.start()
            return carry

        jax.lax.fori_loop(0, n, one, 0)
        if quant:  # the chunk's scale columns, gathered by the caller: one copy
            cp = pltpu.make_async_copy(
                sc_hbm.at[r, :, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)],
                scbuf.at[slot], sems.at[2, slot])
            cp.wait() if wait else cp.start()

    @pl.when(row == 0)
    def _():
        slot_ref[0] = 0
        qp_ref[...] = jnp.zeros_like(qp_ref)  # the off-head lanes stay zero
        copies(0, 0, 0, wait=False)

    # head h's query into the lanes of its key/value head
    for g in range(kvh):
        hs = slice(g * groups, (g + 1) * groups)
        qp_ref[hs, g * d:(g + 1) * d] = q_ref[0, hs, :].astype(f32)

    length = jnp.minimum(lengths[row], span_blocks * block_tokens)
    turns = jnp.maximum(pl.cdiv(length, chunk), 1)  # an empty row reads 0, not 0/0
    slot0 = slot_ref[0]
    # bf16 x bf16 on the MXU where both sides are bf16, else float32 operands
    narrow = q_ref.dtype == jnp.bfloat16 and (quant or kbuf.dtype == jnp.bfloat16)
    qp = qp_ref[...].astype(jnp.bfloat16 if narrow else f32)
    neg = jnp.finfo(f32).min

    def staged(buf, stage, slot, scales, first):
        """The chunk ``[chunk, width]`` as the products take it; an int8 chunk
        through ``stage``, times ``scales[:, first + h]`` over head ``h``."""
        if quant:
            for h in range(kvh):
                lanes = slice(h * d, (h + 1) * d)
                vals = buf[slot, :, :, lanes].astype(f32).reshape(chunk, d)
                stage[:, lanes] = (vals * scales[:, first + h:first + h + 1]).astype(q_ref.dtype)
            x = stage[...]
        else:
            x = buf[slot].reshape(chunk, width)
        return x if narrow else x.astype(f32)

    def dot_pv(p, v):
        if v.dtype != jnp.bfloat16:
            return jnp.dot(p, v, preferred_element_type=f32)
        # p = hi + mid + lo exactly (3 x 8 bits); each bf16 product is exact
        hi = p.astype(jnp.bfloat16)
        r = p - hi.astype(f32)
        mid = r.astype(jnp.bfloat16)
        lo = (r - mid.astype(f32)).astype(jnp.bfloat16)
        return (jnp.dot(hi, v, preferred_element_type=f32)
                + jnp.dot(mid, v, preferred_element_type=f32)
                + jnp.dot(lo, v, preferred_element_type=f32))

    def turn(c, carry):
        m, l, acc = carry
        slot = (slot0 + c) % 2
        more = c + 1 < turns
        nr = jnp.where(more, row, row + 1)

        @pl.when(nr < rows)
        def _():
            copies(nr, jnp.where(more, c + 1, 0), 1 - slot, wait=False)

        copies(row, c, slot, wait=True)
        # an int8 pool's scales come as rows [2 * kv_heads, chunk]: stand them up
        scales = scbuf[slot].T if quant else None
        k = staged(kbuf, kstage, slot, scales, 0)
        s = jax.lax.dot_general(
            qp, k, (((1,), (1,)), ((), ())), preferred_element_type=f32,
        ) * scale  # [heads, chunk]
        pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        s = jnp.where(pos < length, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        v = k[:, :value_dim] if latent else staged(vbuf, vstage, slot, scales, kvh)
        vpos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        v = jnp.where(vpos < length, v, jnp.zeros_like(v))
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        return m_new, l, alpha * acc + dot_pv(p, v)

    m, l, acc = jax.lax.fori_loop(
        0, turns, turn,
        (jnp.full((hq, 1), neg, f32), jnp.zeros((hq, 1), f32),
         jnp.zeros((hq, value_dim if latent else width), f32)),
    )
    slot_ref[0] = (slot0 + turns) % 2  # where the next row's first chunk went
    if latent:
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        return
    acc_ref[...] = acc / l
    for g in range(kvh):
        hs = slice(g * groups, (g + 1) * groups)
        o_ref[0, hs, :] = acc_ref[hs, g * d:(g + 1) * d].astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,  # [b, n_heads, head_dim] — ONE decode query per slot row
    k_pool: jax.Array,  # [num_blocks, block_tokens, kv_heads * head_dim]
    v_pool: jax.Array | None,  # None: k_pool is a latent pool, see value_dim
    block_tables: jax.Array,  # [b, blocks_per_slot] int32 pool block ids
    lengths: jax.Array,  # [b] int32 valid kv positions (frontier cursor + 1)
    *,
    value_dim: int | None = None,  # a latent pool's leading lanes that are the value
    k_scale_pool: jax.Array | None = None,  # [num_blocks, block_tokens, kv_heads]
    v_scale_pool: jax.Array | None = None,  # fp32 absmax planes (int8 pool)
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-query paged attention that reads K/V blocks IN PLACE from the
    per-layer block pool (`models/kv_cache.py` `paged_decode_write`) — the
    fused replacement for the serving engine's ``pool[table]`` gather, which
    materializes a contiguous ``[b, span, heads, head_dim]`` copy per layer
    per decode step.

    The pool folds heads into its last dim (``kv_heads = k_pool.shape[-1] //
    head_dim``, head ``h`` in ``[..., h*head_dim:(h+1)*head_dim]``): a block is
    then one contiguous, tile-aligned ``[block_tokens, kv_heads * head_dim]``
    DMA of the array as the engine stores it. A four-dim pool's trailing
    ``20 x 64`` would pad to ``32 x 128`` on a TPU, and XLA would rewrite each
    layer's whole pool (84 MB to ~270 MB) in front of this call and back.

    Row ``i`` attends positions ``0..lengths[i]-1`` of its logical sequence;
    position ``p`` lives in pool block ``block_tables[i, p // block_tokens]``
    at offset ``p % block_tokens`` (the paged admission/decode layout).
    **The cost follows the tokens a row holds**: the kernel fetches the
    ``cdiv(lengths[i], block_tokens)`` live blocks of row ``i`` and no other,
    a chunk of 128-256 tokens a turn (`_paged_decode_chunk_blocks`, from
    ``block_tokens`` and the row width), under an online softmax with all
    heads in one MXU product a chunk (`_paged_decode_kernel`). Table entries
    at or past the pool size (the engine's released-slot sentinel) are
    clamped to a real block id; what such a block holds past the frontier is
    masked by select. GQA pools read kv head ``h // (n_heads // kv_heads)``
    directly; K/V are never repeated in HBM.

    VMEM is `paged_decode_vmem_bytes`: chunk buffers and accumulators, a
    function of the row width and no function of the span (5.5 MiB and headroom at
    gpt2-large's 1,280 bfloat16 lanes, at 1,024 positions or 32,768). A row too wide
    raises (`check_paged_decode_fits`). Returns ``[b, n_heads, head_dim]`` in
    ``q.dtype``. One body runs compiled on the chip and under the Pallas
    interpreter on CPU (tests/CI); it equals the gather oracle to float32
    rounding (the softmax is accumulated chunk by chunk), not bit for bit
    (`docs/serving.md` "Fused paged decode").

    An int8 pool (`kv_cache_dtype=int8` paged serving) passes its fp32 absmax
    planes as ``k_scale_pool``/``v_scale_pool`` (``[num_blocks, block_tokens,
    kv_heads]``, addressed through the same block table); each chunk is
    dequantized in VMEM, so the quantized pool is never materialized at full
    precision.

    **A latent pool** (``v_pool=None`` with ``value_dim``; latent attention in
    absorbed form, `models/kimi_k2.py`): ``k_pool`` is ``[num_blocks,
    block_tokens, width]`` with one row a token shared by all heads, ``q`` is
    ``[b, n_heads, width]`` (each head's query already carried into the latent
    space, the rotary lanes beside it), the value is lanes ``[0, value_dim)``
    of the same row, and the result is ``[b, n_heads, value_dim]``: all heads
    against one row in the one MXU product a chunk, one copy a block. No int8
    form."""
    b, hq, d = q.shape
    latent = v_pool is None
    if latent:
        if k_pool.ndim != 3 or k_pool.shape[-1] != d or value_dim is None \
                or not 0 < value_dim <= d or k_scale_pool is not None or v_scale_pool is not None:
            raise ValueError(
                f"a latent pool is [num_blocks, block_tokens, {d}] (the query's width) with "
                f"0 < value_dim <= {d} and no scale planes, got {k_pool.shape}, "
                f"value_dim={value_dim}")
    elif value_dim is not None:
        raise ValueError("value_dim is a latent pool's (v_pool=None); got both pools")
    elif k_pool.ndim != 3 or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pools must be [num_blocks, block_tokens, kv_heads * head_dim], "
            f"got {k_pool.shape} and {v_pool.shape}"
        )
    num_blocks, block_tokens, width = k_pool.shape
    if width % d:
        raise ValueError(
            f"pool row width {width} is no multiple of q head_dim {d}")
    kvh = width // d
    if hq % kvh:
        raise ValueError(f"q heads ({hq}) must be a multiple of kv heads ({kvh})")
    if (k_scale_pool is None) != (v_scale_pool is None):
        raise ValueError("k_scale_pool and v_scale_pool must be passed together")
    quant = k_scale_pool is not None
    if quant and k_scale_pool.shape != (num_blocks, block_tokens, kvh):
        raise ValueError(
            f"scale pool shape {k_scale_pool.shape} != "
            f"{(num_blocks, block_tokens, kvh)} (per-block absmax planes)"
        )
    if interpret is None:
        interpret = not _on_tpu()
    vmem_bytes = check_paged_decode_fits(
        kvh, d, q_heads=hq, block_tokens=block_tokens, itemsize=k_pool.dtype.itemsize,
        value_dim=value_dim)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    chunk_blocks = min(_paged_decode_chunk_blocks(block_tokens, width, latent),
                       block_tables.shape[1])
    chunk = chunk_blocks * block_tokens
    # released slots park their whole table at the sentinel id num_blocks;
    # clamp to a real block (past the frontier, masked) so no copy reads out
    # of range
    tables = jnp.minimum(block_tables.astype(jnp.int32), num_blocks - 1)
    lengths = lengths.astype(jnp.int32)

    d_out = value_dim if latent else d
    row_spec, out_spec = (pl.BlockSpec((1, hq, lanes), lambda r, t, l: (r, 0, 0))
                          for lanes in (d, d_out))
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    inputs = [tables, lengths, q, k_pool] + ([] if latent else [v_pool])
    buffers = [pltpu.VMEM((2, chunk_blocks, block_tokens, width), k_pool.dtype)] * (len(inputs) - 3)
    if quant:
        # Mosaic copies no HBM slice whose minor dim is short of a lane tile,
        # so a block's [block_tokens, kv_heads] plane cannot ride beside its
        # payload. The planes (a 16th of the payload's bytes at head_dim 64)
        # are gathered here instead, both pools' in one array with positions
        # on the lanes, [b, 2 * kv_heads, span]: a chunk's scales are then one
        # aligned copy, stood up in the kernel
        turns = -(-block_tables.shape[1] // chunk_blocks)
        planes = jnp.concatenate(
            [k_scale_pool[tables], v_scale_pool[tables]], axis=-1).astype(jnp.float32)
        planes = planes.reshape(b, -1, 2 * kvh).transpose(0, 2, 1)
        planes = jnp.pad(planes, (
            (0, 0), (0, -2 * kvh % 8), (0, turns * chunk - planes.shape[2])))
        inputs.append(planes)
        buffers.append(pltpu.VMEM((2, planes.shape[1], chunk), jnp.float32))
        buffers += [pltpu.VMEM((chunk, width), q.dtype)] * 2  # dequantised chunk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row_spec] + [in_hbm] * (len(inputs) - 3),
        out_specs=out_spec,
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((len(inputs) - 3, 2)),
            pltpu.SMEM((1,), jnp.int32),  # buffer the row's first chunk is in
            pltpu.VMEM((hq, width), jnp.float32),  # Q'
            pltpu.VMEM((hq, width), jnp.float32),  # the row's output, all lanes
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, block_tokens=block_tokens, chunk_blocks=chunk_blocks,
        scale=scale, groups=hq // kvh, quant=quant, value_dim=value_dim,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d_out), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
    )(*inputs)
