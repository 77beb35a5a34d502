"""K-EXAONE (`LGAI-EXAONE/K-EXAONE-236B-A23B` `config.json`, `model_type:
exaone_moe`): a decoder whose layers attend in two ways, three over a sliding
window of ``sliding_window`` positions to one over the whole context
(``layer_types``, the pattern ``LLLG``), a leading dense layer and then many
routed experts chosen by a sigmoid router under a selection bias beside one
shared expert. Untied head. The multi-token prediction module is not here.

Block ``l`` (EXAONE 4.0's layer: the norms sit on the sublayers' OUTPUTS, and
the sublayers take the raw residual stream)::

    a = W_o Attn_l(q, k, v)              q = N_hd(W_q h), k = N_hd(W_k h), v = W_v h
    h <- h + N_attn(a)
    h <- h + N_ffn(FFN_l(h))

with ``N`` an RMSNorm (``rms_norm_eps``, weights start at 1) and ``N_hd`` one
over a head's ``head_dim`` (QK-norm); a final RMSNorm, the head. ``Attn_l`` is
grouped-query attention (``num_attention_heads`` queries on
``num_key_value_heads`` keys and values, scale ``head_dim^-1/2``, float32
softmax). On a sliding layer q and k are rotated (rotate-half over all of
``head_dim``, ``rope_theta``) and a query at ``i`` sees the keys in ``(i - W,
i]``; a full layer carries no position at all (NoPE) and sees every earlier
key. ``FFN_l`` is a SwiGLU MLP of ``intermediate_size`` where
``mlp_layer_types[l]`` is ``dense`` and else Kimi K2's expert layer
(`kimi_k2.SigmoidMoE`: ``s = sigmoid(W_g x)``, the ``num_experts_per_tok``
largest ``s + b`` chosen, weights ``routed_scaling_factor * s / sum s``, plus
the ungated shared expert; ``experts_held`` / ``first_expert`` make it one
expert-parallel chip's share).

Two kinds of key/value cache in one tree (`KExaoneConfig.cache_contract`): a
full layer keeps its keys and values in the paged pool; a sliding layer keeps
per slot a RING of its last ``W`` keys and values, ``[slots, W, kv_heads *
head_dim]`` each (the state leaves ``window_key``, ``window_value``), stored
after the norm and the rotation. Position ``p`` lives in ring row ``p % W``: a
decode step writes there and attends the ring's ``min(p + 1, W)`` rows, whose
order does not matter because nothing inside the window is masked; an admit
writes each prompt's last ``min(len, W)`` positions (``cache_write_len``). A
finished slot's ring is frozen by ``cache_write_mask``. A ring has no token
range a block table could address: the engine refuses the prefix cache, the
KV tier and speculation for this model.

A prefill (an admit) runs the band flash kernel with ``window=W`` on the
sliding layers and causal flash attention on the full one. A decode step runs
the fused paged kernel on every layer: on the full layer over the pool, on a
sliding one over the rings read as a pool of one block of ``W`` rows a slot
(table ``[slot]``, length ``min(p + 1, W)``; in the benchmark cell's decode
step on a TPU v5e this took 0.4 ms a step less than two einsums over the
ring at equal bytes). Scopes: ``window_attn`` and ``global_attn`` hold each
kind's projections and attention; the full layer's decode kernel runs outside
them and keeps the flax scope's name (``%attn.N``), a ring's is
``%window_attn.N``. Counter ``window_rows``: ring rows attended a decode step,
summed over the sliding layers and the live slots.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.flash_attention import paged_decode_attention
from .kimi_k2 import STEP_COUNTERS as MOE_COUNTERS
from .kimi_k2 import DenseMLP, RMSNorm, SigmoidMoE, _dense
from .qwen3_next import partial_rope

SLIDING, FULL = "sliding_attention", "full_attention"
WINDOW_LEAVES = ("window_key", "window_value")
STEP_COUNTERS = MOE_COUNTERS + ("window_rows",)


@dataclass(frozen=True)
class KExaoneConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432  # the dense layer's MLP
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL) * 12
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 47
    sliding_window: int = 128
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1e6
    num_experts: int = 128  # the router's width, always as published
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    # one expert-parallel chip's share: experts [first_expert, first_expert +
    # experts_held) live here; None holds them all
    experts_held: int | None = None
    first_expert: int = 0
    n_positions: int = 4096  # the context served (published: 262,144)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    # the serving engine's cache switches, as on GPT2Config
    kv_cache_dtype: Any = None
    kv_cache_per_slot: bool = False
    kv_cache_paged: bool = False
    kv_num_blocks: int = 0
    kv_block_tokens: int = 16
    kv_paged_attention: str = "gather"
    kv_cache_sharding: Any = None

    def __post_init__(self):
        kinds = set(self.layer_types[: self.num_hidden_layers])
        if len(self.layer_types) < self.num_hidden_layers or not kinds <= {SLIDING, FULL}:
            raise ValueError(f"layer_types must name {SLIDING!r} or {FULL!r} for each of the "
                             f"{self.num_hidden_layers} layers, got {self.layer_types}")
        if self.kv_cache_dtype is not None:
            raise ValueError("kv_cache_dtype: the window ring and the pool are kept in the compute dtype")

    # `kimi_k2.SigmoidMoE` reads the experts under DeepSeek's names
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def n_shared_experts(self) -> int:
        return self.num_shared_experts

    def is_sliding(self, layer: int) -> bool:
        return self.layer_types[layer] == SLIDING

    def is_dense(self, layer: int) -> bool:
        return self.mlp_layer_types[layer] == "dense"

    def cache_contract(self):
        from .kv_cache import CacheContract

        return CacheContract(
            kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
            state_leaves=WINDOW_LEAVES, step_counters=STEP_COUNTERS)


def _window_attend(mod, cfg: KExaoneConfig, q, k, v, positions, decode, fresh_prefill,
                   cache_write_mask, cache_write_len):
    """A sliding layer's attention through its ring (module docstring)."""
    b, s, hkv, d = k.shape
    w = cfg.sliding_window
    is_init = decode and mod.has_variable("cache", WINDOW_LEAVES[0])
    ring = [mod.variable("cache", name, jnp.zeros, (b, w, hkv * d), cfg.dtype)
            for name in WINDOW_LEAVES] if decode else []
    if not is_init or fresh_prefill:
        out = attention(q, k, v, causal=True, window=w, implementation=cfg.attention_impl)
        if is_init:
            # ring row t keeps the last position p < len with p % w == t
            lens = jnp.full((b,), s, jnp.int32) if cache_write_len is None else cache_write_len
            last = lens[:, None] - 1 - (lens[:, None] - 1 - jnp.arange(w)[None, :]) % w  # [b, w]
            for var, new in zip(ring, (k, v)):
                rows = jnp.take_along_axis(new.reshape(b, s, hkv * d), jnp.maximum(last, 0)[..., None], 1)
                var.value = jnp.where((last >= 0)[..., None], rows, 0).astype(cfg.dtype)
        return out
    if s != 1:
        raise NotImplementedError(
            "a multi-token segment on top of a window ring (prefix reuse, speculative verify) "
            "is not supported: only prefill from an empty cache and one-token decode")
    pos = positions[:, 0]
    live = jnp.ones((b,), bool) if cache_write_mask is None else cache_write_mask.astype(bool)
    row = jnp.where(live, pos % w, w)  # a frozen slot's write lands past the ring: dropped
    for var, new in zip(ring, (k, v)):
        var.value = var.value.at[jnp.arange(b), row].set(new[:, 0].reshape(b, hkv * d), mode="drop")
    length = jnp.minimum(pos + 1, w)
    mod.sow("counters", "window_rows", jnp.sum(jnp.where(live, length, 0)).astype(jnp.int32),
            reduce_fn=lambda a, c: a + c, init_fn=lambda: jnp.zeros((), jnp.int32))
    # the rings as a pool of one block a slot: slot i's table is [i]
    slots = jnp.arange(b, dtype=jnp.int32)[:, None]
    return paged_decode_attention(q[:, 0], ring[0].value, ring[1].value, slots, length)[:, None]


def _global_attend(mod, cfg: KExaoneConfig, q, k, v, decode, fresh_prefill, cache_write_mask,
                   block_tables, cache_write_len):
    """A full layer's attention: the segment over itself, or through the
    paged pool (the fused kernel for a one-token step of the fused engine)."""
    s = q.shape[1]

    def prefill():
        with jax.named_scope("global_attn"):
            return attention(q, k, v, causal=True, implementation=cfg.attention_impl)

    if not decode:
        return prefill()
    if cfg.kv_cache_paged and cfg.kv_paged_attention == "fused" and s == 1 \
            and cache_write_len is None:
        from .gpt2 import _fused_paged_attention
        from .kv_cache import paged_decode_write

        k_pool, v_pool, idx, is_init, _ = paged_decode_write(
            mod, k, v, cfg.kv_num_blocks, cfg.kv_block_tokens, block_tables,
            write_mask=cache_write_mask, sharding=cfg.kv_cache_sharding)
        if not is_init:
            return prefill()
        return _fused_paged_attention(q[:, 0], k_pool, v_pool, block_tables, idx + 1, None,
                                      cfg.kv_cache_sharding)[:, None]
    if cfg.kv_cache_paged:
        from .kv_cache import paged_decode_update

        k_all, v_all, idx, is_init = paged_decode_update(
            mod, k, v, cfg.kv_num_blocks, cfg.kv_block_tokens, block_tables,
            write_mask=cache_write_mask, write_len=cache_write_len, sharding=cfg.kv_cache_sharding)
    else:
        from .kv_cache import decode_cache_update

        k_all, v_all, idx, is_init = decode_cache_update(
            mod, k, v, cfg.n_positions, per_slot=cfg.kv_cache_per_slot, write_mask=cache_write_mask,
            write_len=cache_write_len, sharding=cfg.kv_cache_sharding)
    if not is_init or fresh_prefill:
        return prefill()  # nothing earlier to read: the segment attends itself
    q_pos = jnp.reshape(idx, (-1, 1, 1)) + jnp.arange(s)[None, :, None]
    kv_pos = jnp.arange(k_all.shape[1])[None, None, :]
    return attention(q, k_all, v_all, causal=False, mask=(kv_pos <= q_pos)[:, None],
                     implementation="xla")


class KExaoneAttention(nn.Module):
    config: KExaoneConfig
    sliding: bool

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        b, s, _ = x.shape
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        scope = "window_attn" if self.sliding else "global_attn"
        with jax.named_scope(scope):
            q = _dense(cfg, hq * d, "q_proj")(x).reshape(b, s, hq, d)
            k = _dense(cfg, hkv * d, "k_proj")(x).reshape(b, s, hkv, d)
            v = _dense(cfg, hkv * d, "v_proj")(x).reshape(b, s, hkv, d)
            q = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="k_norm")(k)
        if self.sliding:
            with jax.named_scope(scope):
                q = partial_rope(q, positions, cfg.rope_theta, d)
                k = partial_rope(k, positions, cfg.rope_theta, d)
                out = _window_attend(self, cfg, q, k, v, positions, decode, fresh_prefill,
                                     cache_write_mask, cache_write_len)
        else:  # NoPE
            out = _global_attend(self, cfg, q, k, v, decode, fresh_prefill, cache_write_mask,
                                 block_tables, cache_write_len)
        with jax.named_scope(scope):
            return _dense(cfg, cfg.hidden_size, "o_proj")(out.reshape(b, s, hq * d))


class KExaoneBlock(nn.Module):
    config: KExaoneConfig
    sliding: bool
    dense: bool

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        a = KExaoneAttention(cfg, self.sliding, name="attn")(
            x, positions, decode, fresh_prefill, cache_write_mask, block_tables, cache_write_len)
        x = x + RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="post_attention_norm")(a)
        ffn = DenseMLP(cfg, name="mlp") if self.dense else SigmoidMoE(cfg, name="moe")
        return x + RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="post_feedforward_norm")(ffn(x))


class KExaoneForCausalLM(nn.Module):
    """Decoder-only LM. Returns logits [batch, seq, vocab] in float32."""

    config: KExaoneConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True, decode: bool = False,
                 position_offset: jax.Array | int = 0, return_hidden: bool = False,
                 cache_write_mask: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 cache_write_len: jax.Array | None = None) -> jax.Array:
        cfg = self.config
        b, s = input_ids.shape
        # a static offset of 0 starts a sequence: nothing is cached before it
        fresh_prefill = decode and isinstance(position_offset, int) and position_offset == 0 and s > 1
        embed = self.param("embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        offset = jnp.asarray(position_offset, jnp.int32)
        positions = jnp.broadcast_to(offset.reshape(-1, 1), (b, 1)) + jnp.arange(s)[None, :]
        x = embed.astype(cfg.dtype)[input_ids]
        for i in range(cfg.num_hidden_layers):
            x = KExaoneBlock(cfg, cfg.is_sliding(i), cfg.is_dense(i), name=f"layer_{i}")(
                x, positions, decode, fresh_prefill, cache_write_mask, block_tables, cache_write_len)
        x = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="final_norm")(x)
        if return_hidden:
            return x
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype)
        return jnp.matmul(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)

