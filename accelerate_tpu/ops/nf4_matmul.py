"""Fused NF4 dequant-matmul Pallas kernel (staged decode lever).

Role: the reference's headline benchmark is big-model inference, and its 4-bit
rows run bitsandbytes' fused CUDA dequant-GEMV. Here every nf4 decode path
that exists — `QuantizedModule.apply`, the serving engine's
``weight_quant="nf4"`` — dequantizes inside jit and lets XLA fuse
(`utils/quantization.py`); NONE of them calls this kernel. It needs the packed
payload as a concrete array (the plane repack runs on the host), and inside a
jitted decode loop the payload is a tracer, so `nf4_matmul` there IS the XLA
dequant path. The kernel runs only when called with a concrete tensor
(`tools/bench_nf4_kernel.py`, `chip_smoke.py`). It is the escalation if a chip
measurement (`BENCH_INF_QUANT=nf4` vs fp16) shows dequant dominating decode:
it reads the PACKED payload (4 bits/weight) straight from HBM and dequantizes
in VMEM, so a memory-bound matvec moves ~4x fewer bytes than a bf16 weight
read. Wiring it into the decode loop means making the packed planes jit
arguments.

Kernel design (TPU-first):
- Plane packing: byte (k, j) holds element (k, j) in the high nibble and
  (k, j + N/2) in the low nibble — dequant needs only shift/mask/compare ops
  (no nibble interleave, no gather: the 16-entry NF4 codebook is compiled in
  as a select chain), and each grid cell emits two output tiles (left/right
  plane) with two MXU dots.
- Blockwise absmax scales (the QLoRA layout, 64 elements along a row) arrive
  pre-split per plane as [2, K, (N/2)/64]; a tile's scale columns expand over
  the lanes with an iota select — no repeat/reshape inside the kernel.
- Grid (N/2 / bn, K / bk) with accumulation over the K dim
  (`o_ref += dot(...)`); bn defaults to the full 128-lane width (two 64-wide
  scale blocks per tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..utils.quantization import NF4_CODE, QuantizedTensor


def _on_tpu() -> bool:
    from ..utils.environment import on_tpu_platform

    return on_tpu_platform()


def _kernel(x_ref, packed_ref, scales_ref, o_ref, *, code, bn):
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    p = packed_ref[...].astype(jnp.int32)
    hi, lo = (p >> 4) & 0xF, p & 0xF
    n_scale = bn // 64

    def dequant(idx, s_cols):
        vals = jnp.full(idx.shape, code[0], jnp.float32)
        for c in range(1, 16):
            vals = jnp.where(idx == c, code[c], vals)
        if n_scale == 1:
            return vals * s_cols  # [bk, 1] broadcasts over the lanes
        # expand [bk, n_scale] scale columns over the 64-lane blocks with an
        # iota select — no reshape/repeat (layout-sensitive on Mosaic)
        col = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1) // 64
        s_full = jnp.broadcast_to(s_cols[:, :1], idx.shape)
        for b in range(1, n_scale):
            s_full = jnp.where(col == b, s_cols[:, b : b + 1], s_full)
        return vals * s_full

    wl = dequant(hi, scales_ref[0, 0])
    wr = dequant(lo, scales_ref[1, 0])
    x = x_ref[...].astype(jnp.float32)
    o_ref[0, ...] += jnp.dot(x, wl, preferred_element_type=jnp.float32)
    o_ref[1, ...] += jnp.dot(x, wr, preferred_element_type=jnp.float32)


def _block_size(qt: QuantizedTensor) -> int:
    """The quantization block length this tensor was packed with (elements per
    scale), derived from the scale count."""
    total = 1
    for dim in qt.shape:
        total *= dim
    n_blocks = int(qt.scales.shape[0])
    return -(-total // n_blocks) if n_blocks else 0


def kernel_supported(qt: QuantizedTensor) -> bool:
    """True when the fused kernel can take this tensor: nf4, 2D, 64-element
    scale blocks, N tiling two 64-wide planes, and a CONCRETE payload (inside
    jit the payload is a tracer — the host-side repack is impossible, so
    traced calls use the XLA dequant path)."""
    return (
        qt.bits == 4
        and qt.quant_type == "nf4"
        and len(qt.shape) == 2
        and qt.shape[1] % 128 == 0
        and _block_size(qt) == 64
        and not isinstance(qt.data, jax.core.Tracer)
    )


def plane_pack(qt: QuantizedTensor) -> tuple[jax.Array, jax.Array]:
    """Host-side repack of a QuantizedTensor's interleaved payload into the
    kernel's plane layout: (packed [K, N/2] uint8, scales [2, K, (N/2)/64]),
    as DEVICE arrays — cached on the tensor so the upload happens once at
    load, not per matmul."""
    cached = qt._plane_pack
    if cached is not None:
        return cached
    if qt.bits != 4 or qt.quant_type != "nf4":
        raise ValueError(f"plane_pack needs an nf4 tensor, got {qt.bits}-bit {qt.quant_type}")
    if len(qt.shape) != 2:
        raise ValueError(f"plane_pack needs a 2D weight, got shape {qt.shape}")
    K, N = qt.shape
    if N % 128:
        raise ValueError(f"N ({N}) must be a multiple of 128 (two 64-wide scale planes)")
    if _block_size(qt) != 64:
        raise ValueError(
            f"plane_pack needs 64-element scale blocks, got {_block_size(qt)}"
        )
    data = np.asarray(jax.device_get(qt.data))
    hi, lo = (data >> 4) & 0xF, data & 0xF
    idx = np.stack([hi, lo], axis=-1).reshape(-1)[: K * N].reshape(K, N)
    scales = np.asarray(jax.device_get(qt.scales)).reshape(K, N // 64)
    P = N // 2
    packed = ((idx[:, :P] << 4) | idx[:, P:]).astype(np.uint8)
    scales2 = np.stack([scales[:, : P // 64], scales[:, P // 64:]]).astype(np.float32)
    qt._plane_pack = (jnp.asarray(packed), jnp.asarray(scales2))
    return qt._plane_pack


def nf4_matmul(
    x: jax.Array,
    qt: QuantizedTensor,
    block_k: int = 256,
    block_n: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ dequantize(qt)`` with the packed payload read directly by the
    kernel. ``x`` is [..., K]; the quantized weight is [K, N]. Any tensor or
    shape the kernel cannot take (non-nf4, odd block size, un-tileable dims,
    traced payload) falls back to the XLA dequant path — same numerics."""
    K, N = qt.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    bk = min(block_k, K)
    while K % bk:
        bk //= 2
    P = N // 2
    # largest multiple of 64 <= block_n that tiles the plane; 0 = no tiling
    bn = next(
        (c for c in range(min(block_n, P) - min(block_n, P) % 64, 63, -64) if P % c == 0),
        0,
    )
    if not kernel_supported(qt) or bk < 8 or bn < 64:
        from ..utils.quantization import dequantize

        return (x2 @ dequantize(qt, x.dtype)).reshape(*lead, N)
    if interpret is None:
        interpret = not _on_tpu()
    M = x2.shape[0]
    packed, scales2 = plane_pack(qt)
    # one [K, bn/64] scale matrix per column tile: Mosaic wants a block's last
    # dim to be a multiple of 128 or the whole axis, and bn/64 columns out of
    # P/64 are neither
    scales_tiled = scales2.reshape(2, K, P // bn, bn // 64).transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        functools.partial(_kernel, code=[float(c) for c in NF4_CODE], bn=bn),
        grid=(P // bn, K // bk),
        in_specs=[
            pl.BlockSpec((M, bk), lambda j, k: (0, k)),
            pl.BlockSpec((bk, bn), lambda j, k: (k, j)),
            pl.BlockSpec((2, 1, bk, bn // 64), lambda j, k: (0, j, k, 0)),
        ],
        out_specs=pl.BlockSpec((2, M, bn), lambda j, k: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((2, M, P), jnp.float32),
        interpret=interpret,
    )(x2, packed, scales_tiled)
    return jnp.concatenate([out[0], out[1]], axis=-1).astype(x.dtype).reshape(*lead, N)
