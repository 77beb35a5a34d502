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
# The fused kernel keeps a row's whole attended K/V span in two fp32 VMEM
# scratch buffers ``[span, kv_heads * head_dim]``, the pool's own folded row
# (`models/kv_cache._paged_pool_step`); Mosaic tiles the last two dims (8, 128),
# so only a merged width that is no multiple of 128 pads. At gpt2-large (1024
# positions, 20 heads of 64) that is 2 x 5 MiB, at gpt2-medium 2 x 4 MiB; the
# call asks for what it needs (`vmem_limit_bytes`) and refuses spans that
# cannot fit the core at all. 128 MiB is the v5e core's VMEM (measured: XLA
# reports "Used 128.71M of 128.00M" one size past the cap).
_VMEM_BYTES = 128 << 20
PAGED_DECODE_VMEM_CAP = _VMEM_BYTES - (16 << 20)  # leave XLA's own share
_PAGED_DECODE_HEADROOM = 8 << 20  # pipelined input blocks + flush temporaries


def paged_decode_vmem_bytes(span: int, kv_heads: int, head_dim: int) -> int:
    """VMEM the fused paged-decode kernel needs for one slot row: the two fp32
    span buffers, ``ceil8(span) x ceil128(kv_heads * head_dim) x 4`` bytes
    each, plus fixed headroom."""
    padded = (-(-span // 8) * 8) * (-(-kv_heads * head_dim // 128) * 128) * 4
    return 2 * padded + _PAGED_DECODE_HEADROOM


def check_paged_decode_fits(span: int, kv_heads: int, head_dim: int) -> int:
    """Raise with the sizes named when the kernel cannot hold ``span``
    positions of ``kv_heads`` x ``head_dim`` in VMEM; returns the bytes it
    will ask for. The serving engine calls this at construction, so
    ``paged_attention="fused"`` fails there instead of at first decode."""
    need = paged_decode_vmem_bytes(span, kv_heads, head_dim)
    if need > PAGED_DECODE_VMEM_CAP:
        raise ValueError(
            f"fused paged decode keeps the whole attended span in VMEM: "
            f"{span} positions x {kv_heads} kv heads x head_dim {head_dim} "
            f"needs {need / 2**20:.0f} MiB (two fp32 [span, kv_heads*head_dim] "
            f"buffers + headroom), over the "
            f"{PAGED_DECODE_VMEM_CAP / 2**20:.0f} MiB this kernel may use of "
            f"the core's {_VMEM_BYTES / 2**20:.0f} MiB. Shorten n_positions, "
            "shard heads over the model axis, or use paged_attention='gather'."
        )
    return need


def _paged_decode_kernel(
    tables, lengths, q_ref, k_ref, v_ref, *rest,
    block_tokens, span, scale, groups, exact,
):
    """One grid cell = (slot row, table block j). The block axis is LAST —
    sequential on a TensorCore — so the K/V blocks the table names accumulate
    in VMEM scratch across iterations and the flush at the final block runs
    the whole single-query attention for ALL heads in one pass: fp32 QK^T,
    scale after the dot, finfo.min frontier mask, global-max softmax, PV.
    K/V blocks stream straight from the pool through the scalar-prefetched
    block table — nothing is materialized in HBM.

    ``exact`` (interpret mode, CPU CI) computes the flush with the head axis
    BATCHED using the same `dot_general` dimension_numbers the gather
    oracle's two einsums lower to. XLA's CPU emitter is invariant to the
    batch extent but NOT to degenerate (size-1) batch dims — a per-head
    formulation differs by ~1 ulp — so keeping heads batched makes the fused
    path bit-identical to `dot_product_attention` over the gathered view,
    which is the parity bar the serving tests hold (docs/serving.md). On TPU
    the flush unrolls per head into MXU-friendly 2-D dots instead.

    K/V blocks and the scratch are ``[block_tokens | span, kv_heads * d]``,
    head ``h`` in lanes ``h*d:(h+1)*d``: the pool's stored row, so a block is
    one contiguous DMA and nothing relays the pool in front of the call.

    An int8 pool rides two extra refs — the fp32 absmax scale planes
    (``[1, block_tokens, kv_heads]`` per block) — and each block dequantizes
    AT STAGING into the fp32 VMEM scratch (value × its head's scale over that
    head's ``d`` lanes, round-tripped through the compute dtype exactly like
    the gather oracle's `_dq`), so the quantized pool is never materialized at
    full precision in HBM and the flush math below is byte-for-byte the same
    in both modes."""
    if len(rest) == 5:
        ks_ref, vs_ref, o_ref, k_scr, v_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, k_scr, v_scr = rest
    b_ = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    length = lengths[b_]  # valid kv span for this row (frontier cursor + 1)
    window = pl.ds(j * block_tokens, block_tokens)
    hq, d = q_ref.shape[1], q_ref.shape[2]
    kvh = k_scr.shape[1] // d

    def head(h):  # kv head h's lanes of a folded row
        return slice(h * d, (h + 1) * d)

    @pl.when(j * block_tokens < length)
    def _():
        if ks_ref is None:
            k_scr[window] = k_ref[0].astype(jnp.float32)  # [bt, kv_heads * d]
            v_scr[window] = v_ref[0].astype(jnp.float32)
        else:
            cdt = q_ref.dtype
            for src, sc, dst in ((k_ref, ks_ref, k_scr), (v_ref, vs_ref, v_scr)):
                for h in range(kvh):
                    dst[window, head(h)] = (
                        src[0, :, head(h)].astype(jnp.float32) * sc[0, :, h:h + 1]
                    ).astype(cdt).astype(jnp.float32)

    @pl.when(j * block_tokens >= length)
    def _():
        # past-frontier blocks (incl. clamped sentinel table entries): every
        # position is re-masked at the flush, but the rows must be finite —
        # a stale NaN would poison the 0-weight products
        zeros = jnp.zeros((block_tokens,) + k_scr.shape[1:], jnp.float32)
        k_scr[window] = zeros
        v_scr[window] = zeros

    @pl.when(j == nj - 1)
    def _():
        neg = jnp.finfo(jnp.float32).min
        if exact:
            q4 = q_ref[...].astype(jnp.float32).reshape(1, 1, hq, d)  # [b,q,h,d]
            k4 = k_scr[...].reshape(1, span, kvh, d)  # [b,k,h,d]
            v4 = v_scr[...].reshape(1, span, kvh, d)
            if groups > 1:
                # attention() repeats kv heads before the xla path; mirror it
                k4 = jnp.repeat(k4, groups, axis=2)
                v4 = jnp.repeat(v4, groups, axis=2)
            s = jax.lax.dot_general(
                q4, k4, (((3,), (3,)), ((0, 2), (0, 2))),
                preferred_element_type=jnp.float32,
            )  # [1, h, 1, span] — einsum "bqhd,bkhd->bhqk"
            s = s * scale
            pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, span), 3)
            s = jnp.where(pos < length, s, neg)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            w = p / jnp.sum(p, axis=-1, keepdims=True)
            # einsum "bhqk,bkhd->bqhd" lowers with v as the LHS:
            # dot_general(v, w, (([1],[3]), ([0,2],[0,1]))) -> [b,h,d,q]
            o = jax.lax.dot_general(
                v4, w, (((1,), (3,)), ((0, 2), (0, 1))),
                preferred_element_type=jnp.float32,
            )  # [1, h, d, 1]
            o_ref[0] = jnp.transpose(o, (0, 3, 1, 2)).reshape(hq, d).astype(o_ref.dtype)
        else:
            for hh in range(hq):
                q2 = q_ref[0, hh].astype(jnp.float32).reshape(1, d)
                k2 = k_scr[:, head(hh // groups)]  # [span, d]
                s = jax.lax.dot_general(
                    q2, k2, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale  # [1, span]
                pos = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
                s = jnp.where(pos < length, s, neg)
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                w = p / jnp.sum(p, axis=-1, keepdims=True)
                o = jax.lax.dot_general(
                    w, v_scr[:, head(hh // groups)], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [1, d]
                o_ref[0, hh] = o.reshape(d).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,  # [b, n_heads, head_dim] — ONE decode query per slot row
    k_pool: jax.Array,  # [num_blocks, block_tokens, kv_heads * head_dim]
    v_pool: jax.Array,
    block_tables: jax.Array,  # [b, blocks_per_slot] int32 pool block ids
    lengths: jax.Array,  # [b] int32 valid kv positions (frontier cursor + 1)
    *,
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
    Table entries at or past the pool size (the engine's released-slot
    sentinel) are clamped to a real block id — every position they could
    contribute is past the frontier and masked. GQA pools read kv head
    ``h // (n_heads // kv_heads)`` directly; K/V are never repeated in HBM.

    VMEM cost per slot-row cell is `paged_decode_vmem_bytes` (two fp32
    ``[span, kv_heads * head_dim]`` buffers: 10.5 MB at gpt2-large's 1024
    positions) — the attended K/V span lives in fp32 scratch so the flush
    runs a single global-max softmax, bit-identical to the XLA gather oracle under the interpreter
    (`docs/serving.md` "Fused paged decode"). A span that cannot fit raises
    (`check_paged_decode_fits`); an online-softmax variant is what lifts the
    limit. Returns ``[b, n_heads, head_dim]`` in ``q.dtype``. On CPU
    (tests/CI) runs under the Pallas interpreter.

    An int8 pool (`kv_cache_dtype=int8` paged serving) passes its fp32 absmax
    planes as ``k_scale_pool``/``v_scale_pool`` (``[num_blocks, block_tokens,
    kv_heads]``, addressed through the same block table); each block is
    dequantized in VMEM scratch at staging time, so the quantized pool is
    never materialized at full precision."""
    b, hq, d = q.shape
    if k_pool.ndim != 3 or v_pool.shape != k_pool.shape:
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
    groups = hq // kvh
    bps = block_tables.shape[1]
    span = bps * block_tokens
    if interpret is None:
        interpret = not _on_tpu()
    vmem_bytes = check_paged_decode_fits(span, kvh, d)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # released slots park their whole table at the sentinel id num_blocks;
    # clamp to a real block (fully frontier-masked) so the index map never
    # reads out of range
    tables = jnp.minimum(block_tables.astype(jnp.int32), num_blocks - 1)
    lengths = lengths.astype(jnp.int32)

    in_specs = [
        pl.BlockSpec((1, hq, d), lambda b_, j, t, l: (b_, 0, 0)),
        pl.BlockSpec(
            (1, block_tokens, width),
            lambda b_, j, t, l: (t[b_, j], 0, 0),
        ),
        pl.BlockSpec(
            (1, block_tokens, width),
            lambda b_, j, t, l: (t[b_, j], 0, 0),
        ),
    ]
    inputs = [tables, lengths, q, k_pool, v_pool]
    if quant:
        # the scale planes page in through the same block-table index map as
        # their payload blocks, one [block_tokens, kv_heads] plane per cell
        in_specs += [
            pl.BlockSpec(
                (1, block_tokens, kvh),
                lambda b_, j, t, l: (t[b_, j], 0, 0),
            ),
            pl.BlockSpec(
                (1, block_tokens, kvh),
                lambda b_, j, t, l: (t[b_, j], 0, 0),
            ),
        ]
        inputs += [k_scale_pool.astype(jnp.float32),
                   v_scale_pool.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, bps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, d), lambda b_, j, t, l: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((span, width), jnp.float32),
            pltpu.VMEM((span, width), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, block_tokens=block_tokens, span=span, scale=scale,
        groups=groups, exact=bool(interpret),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
    )(*inputs)
