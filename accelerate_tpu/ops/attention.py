"""Attention ops.

The compute core the reference delegates to external engines (Megatron fused
kernels, TransformerEngine) is implemented here natively for TPU:

  - ``dot_product_attention``: XLA path — einsum QK^T -> masked softmax -> PV.
    XLA fuses the elementwise chain into the matmuls; with bf16 inputs both
    matmuls tile straight onto the MXU. Good to ~4k sequence.
  - a Pallas flash/splash kernel lives in `ops/flash_attention.py` (blockwise,
    O(seq) memory) and is selected automatically for long sequences on TPU.
  - ring attention for sequence-parallel meshes lives in
    `parallel/ring_attention.py` (ppermute KV rotation over ICI).

All functions take [batch, seq, heads, head_dim] ("BSHD") layouts — the layout
that keeps the head dim contiguous in lane registers on TPU.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def causal_mask(q_len: int, kv_len: int, dtype=jnp.float32, offset: int = 0) -> jax.Array:
    """Additive causal mask [q_len, kv_len]; query i attends to keys <= i+offset."""
    q_idx = jnp.arange(q_len)[:, None]
    k_idx = jnp.arange(kv_len)[None, :]
    allowed = k_idx <= (q_idx + offset)
    return jnp.where(allowed, 0.0, jnp.finfo(dtype).min).astype(dtype)


def dot_product_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, H, D]
    v: jax.Array,  # [B, Sk, H, D]
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,  # boolean [B, 1|H, Sq, Sk] or [Sq, Sk], True=keep
    causal: bool = False,
    window: int | None = None,  # sliding window: query i sees keys in (i-W, i]
    scale: float | None = None,
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
    dtype=None,
) -> jax.Array:
    """Plain XLA attention. Softmax accumulates in fp32 regardless of input dtype
    (bf16 logits lose too much range), output returns to the input dtype."""
    orig_dtype = q.dtype
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        logits = logits + causal_mask(q.shape[1], k.shape[1])[None, None, :, :]
    if window is not None:
        if not causal:
            raise ValueError(
                "window requires causal=True (one rule across xla and flash paths; "
                "a low-side-only band would silently attend future keys)"
            )
        q_idx = jnp.arange(q.shape[1])[:, None]
        k_idx = jnp.arange(k.shape[1])[None, :]
        in_band = k_idx > q_idx - window
        logits = jnp.where(in_band[None, None], logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None, :, :]
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, weights.shape)
        weights = jnp.where(keep, weights / (1.0 - dropout_rate), 0.0)
    weights = weights.astype(orig_dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _mesh_shard_map(flash_fn, q, k):
    """Under a live multi-device mesh, run the Pallas kernel per shard
    (`parallel.mesh.pallas_shard_axes`). Attention is row- and head-local, so
    the batch splits over the mesh's batch axes and heads over ``tensor``
    when both head counts divide it (contiguous sharding then keeps whole GQA
    groups per shard). Returns None when there is nothing to wrap."""
    from ..parallel.mesh import pallas_shard_axes

    live = pallas_shard_axes(q.shape[0])
    if live is None:
        return None
    from jax import shard_map

    mesh, rows = live
    tp = mesh.shape.get("tensor", 1)
    heads = "tensor" if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0 else None
    spec = P(rows, None, heads, None)
    return shard_map(
        flash_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: jax.Array | None = None,
    window: int | None = None,
    implementation: str = "auto",
    block_q: int | None = None,
    block_kv: int | None = None,
) -> jax.Array:
    """Dispatching entry point: 'xla' | 'flash' | 'auto'.

    'auto' picks the Pallas flash kernel on TPU for sequences where the
    O(S^2) logits buffer dominates HBM traffic, else the fused XLA path.
    ``window`` is Mistral-class sliding-window attention: on the flash path it
    runs on the band grid (compute scales with the window, not seq^2).
    """
    if k.shape[2] != q.shape[2] and (k.shape[2] == 0 or q.shape[2] % k.shape[2]):
        raise ValueError(
            f"q heads ({q.shape[2]}) must be a multiple of kv heads ({k.shape[2]})"
        )
    if mask is not None and implementation != "xla":
        # the flash kernel has no arbitrary-mask support; computing over the
        # masked positions would be silently wrong, so masked calls take the
        # XLA path regardless of the requested implementation
        implementation = "xla"
    if implementation == "auto":
        from ..utils.environment import on_tpu_platform

        on_tpu = on_tpu_platform()
        implementation = "flash" if (on_tpu and q.shape[1] >= 1024 and q.shape[1] == k.shape[1]) else "xla"
        if window is not None and implementation == "flash":
            # the band grid needs a block divisor of seq; un-tileable lengths
            # (e.g. prime) would raise in the kernel — auto routes them to xla
            from .flash_attention import band_block_default

            if band_block_default(q.shape[1]) is None:
                implementation = "xla"
        elif implementation == "flash":
            # so does the rectangular grid: a length its blocks do not divide
            # (1536 under the 1024 default) would raise in the kernel
            from .flash_attention import rect_blocks

            bq, bkv = rect_blocks(q.shape[1], k.shape[1], block_q, block_kv)
            if q.shape[1] % bq or k.shape[1] % bkv:
                implementation = "xla"
    if implementation == "flash":
        from .flash_attention import flash_attention

        # GQA K/V pass through unrepeated — the band grid reads kv head
        # h // groups directly; the rectangular path repeats internally
        flash = partial(
            flash_attention, causal=causal, window=window, block_q=block_q, block_kv=block_kv
        )
        wrapped = _mesh_shard_map(flash, q, k)
        if wrapped is not None:
            return wrapped(q, k, v)
        return flash(q, k, v)
    if k.shape[2] != q.shape[2]:
        groups = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    return dot_product_attention(q, k, v, causal=causal, mask=mask, window=window)
