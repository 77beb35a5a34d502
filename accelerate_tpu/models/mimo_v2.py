"""MiMo-V2 (`XiaomiMiMo/MiMo-V2.5` `config.json`, `model_type: mimo_v2`; the
MiMo-V2-Flash 309B-A15B family): a decoder whose layers attend in two ways,
five over a sliding window of ``sliding_window`` positions to one over the
whole context (``hybrid_layer_pattern``, 1 = sliding), with head counts and
cache sizes that differ by kind; a leading dense layer, then layers of 256
routed experts chosen by a sigmoid router under a selection bias, with no
shared expert. Untied head. The multi-token prediction layers and the vision
and audio towers are not here.

Block ``l`` of kind ``t`` (S sliding, G full), pre-norm::

    x = N_1(h)
    q = RoPE_r(W_q x), k = RoPE_r(W_k x), v = c_v W_v x
    h <- h + W_o Attn_t(q, k, v)
    h <- h + FFN_l(N_2(h))

with ``N`` an RMSNorm (``layernorm_epsilon``, weights start at 1), a final
RMSNorm and the head. Both kinds have ``num_attention_heads`` query heads and
keys of ``head_dim`` (192) beside values of ``v_head_dim`` (128); scores are
``q . k / sqrt(head_dim)`` and values are scaled by ``attention_value_scale``
(``c_v``). Rotary positions (rotate-half) cover the first
``head_dim * partial_rotary_factor`` dims of each head (64), at
``swa_rope_theta`` on S and ``rope_theta`` on G. A sliding layer has
``swa_num_key_value_heads`` (8) key/value heads, a query at ``i`` sees the
keys in ``(i - W, i]``, and each query head ``h`` has a learnt sink logit
``b_h`` (``add_swa_attention_sink_bias``)::

    p_ij = exp(s_ij) / (exp(b_h) + sum_j exp(s_ij))

a column of the softmax whose value is zero. A full layer has
``num_key_value_heads`` (4), sees every earlier key under a plain softmax and
has no sink. ``FFN_l`` is a SwiGLU MLP of ``intermediate_size`` where
``moe_layer_freq[l]`` is 0 and else Kimi K2's expert layer
(`kimi_k2.SigmoidMoE`: ``s = sigmoid(W_g x)``, the ``num_experts_per_tok``
largest ``s + b`` chosen, weights ``s / sum s`` (scale 1), no shared expert;
``experts_held`` / ``first_expert`` make it one expert-parallel chip's share).

The cache (`MiMoV2Config.cache_contract`): the full layers keep keys and
values in the engine's paged pool, 4 heads of 192 beside 4 of 128 (the value
leaf at its own width, `CacheContract.value_head_dim`); the sliding layers
keep per slot a ring of their last ``W`` keys and values at 8 heads each, the
state leaves ``window_key`` ``[slots, W, 8 * 192]`` and ``window_value``
``[slots, W, 8 * 128]``, through K-EXAONE's ring logic
(`k_exaone._window_attend`), with the sinks handed to the band flash kernel in
an admit and to the fused paged kernel in a decode step. Scopes and event
names as on K-EXAONE: ``window_attn`` and ``global_attn`` hold each kind's
projections and attention; a ring's decode kernel is ``%window_attn.N``, the
full layers' is the flax scope's ``%attn.N``, the band kernel of an admit
runs under ``window_attn``. Counter ``window_rows`` as on K-EXAONE. The
engine refuses the prefix cache, the KV tier, speculation and a mesh for
this model (the rings are per-slot state).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from .k_exaone import STEP_COUNTERS, WINDOW_LEAVES, _global_attend, _window_attend
from .kimi_k2 import DenseMLP, RMSNorm, SigmoidMoE, _dense
from .qwen3_next import partial_rope

SLIDING, FULL = 1, 0  # `hybrid_layer_pattern`'s entries
PATTERN = (FULL,) + (SLIDING,) * 4 + (FULL,) + ((SLIDING,) * 5 + (FULL,)) * 7


@dataclass(frozen=True)
class MiMoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384  # the dense layer's MLP
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    hybrid_layer_pattern: tuple = PATTERN
    moe_layer_freq: tuple = (0,) + (1,) * 47
    sliding_window: int = 128
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    n_routed_experts: int = 256  # the router's width, always as published
    num_experts_per_tok: int = 8
    n_shared_experts: int | None = None
    routed_scaling_factor: float = 1.0  # published null: the weights are s / sum s
    layernorm_epsilon: float = 1e-5
    # one expert-parallel chip's share: experts [first_expert, first_expert +
    # experts_held) live here; None holds them all
    experts_held: int | None = None
    first_expert: int = 0
    n_positions: int = 4096  # the context served (published: 1,048,576)
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
        kinds = set(self.hybrid_layer_pattern[: self.num_hidden_layers])
        if len(self.hybrid_layer_pattern) < self.num_hidden_layers or not kinds <= {SLIDING, FULL}:
            raise ValueError(f"hybrid_layer_pattern must give 0 (full) or 1 (sliding) for each of the "
                             f"{self.num_hidden_layers} layers, got {self.hybrid_layer_pattern}")
        if self.kv_cache_dtype is not None:
            raise ValueError("kv_cache_dtype: the window ring and the pool are kept in the compute dtype")
        if self.add_full_attention_sink_bias:
            raise ValueError("add_full_attention_sink_bias: sinks on the full layers' pool are not written")
        if self.swa_head_dim != self.head_dim or self.swa_num_attention_heads != self.num_attention_heads:
            raise ValueError("the sliding and full layers share the query heads and the key size here")

    def is_sliding(self, layer: int) -> bool:
        return self.hybrid_layer_pattern[layer] == SLIDING

    def is_dense(self, layer: int) -> bool:
        return not self.moe_layer_freq[layer]

    @property
    def rotary_dim(self) -> int:
        """The rotated dims of a head: 64 of 192 at the published 0.334."""
        return int(self.head_dim * self.partial_rotary_factor)

    def heads(self, sliding: bool) -> tuple[int, int, int, int]:
        """(query heads, key/value heads, key size, value size) of a kind."""
        if sliding:
            return (self.swa_num_attention_heads, self.swa_num_key_value_heads, self.swa_head_dim,
                    self.swa_v_head_dim)
        return self.num_attention_heads, self.num_key_value_heads, self.head_dim, self.v_head_dim

    def cache_contract(self):
        """The paged pool is the full layers': 4 heads, keys 192, values 128."""
        from .kv_cache import CacheContract

        return CacheContract(
            kv_heads=self.num_key_value_heads, head_dim=self.head_dim, value_head_dim=self.v_head_dim,
            state_leaves=WINDOW_LEAVES, step_counters=STEP_COUNTERS)


class MiMoV2Attention(nn.Module):
    config: MiMoV2Config
    sliding: bool

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        b, s, _ = x.shape
        hq, hkv, d, dv = cfg.heads(self.sliding)
        scope = "window_attn" if self.sliding else "global_attn"
        theta = cfg.swa_rope_theta if self.sliding else cfg.rope_theta
        with jax.named_scope(scope):
            q = _dense(cfg, hq * d, "q_proj")(x).reshape(b, s, hq, d)
            k = _dense(cfg, hkv * d, "k_proj")(x).reshape(b, s, hkv, d)
            v = _dense(cfg, hkv * dv, "v_proj")(x).reshape(b, s, hkv, dv)
            v = v * jnp.asarray(cfg.attention_value_scale, v.dtype)
            q = partial_rope(q, positions, theta, cfg.rotary_dim)
            k = partial_rope(k, positions, theta, cfg.rotary_dim)
        if self.sliding:
            sinks = (self.param("attention_sink_bias", nn.initializers.zeros, (hq,), jnp.float32)
                     if cfg.add_swa_attention_sink_bias else None)
            with jax.named_scope(scope):
                out = _window_attend(self, cfg, q, k, v, positions, decode, fresh_prefill,
                                     cache_write_mask, cache_write_len, sinks=sinks)
        else:
            out = _global_attend(self, cfg, q, k, v, decode, fresh_prefill, cache_write_mask,
                                 block_tables, cache_write_len)
        with jax.named_scope(scope):
            return _dense(cfg, cfg.hidden_size, "o_proj")(out.reshape(b, s, hq * dv))


class MiMoV2Block(nn.Module):
    config: MiMoV2Config
    sliding: bool
    dense: bool

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        h = RMSNorm(cfg.layernorm_epsilon, cfg.param_dtype, name="input_norm")(x)
        x = x + MiMoV2Attention(cfg, self.sliding, name="attn")(
            h, positions, decode, fresh_prefill, cache_write_mask, block_tables, cache_write_len)
        h = RMSNorm(cfg.layernorm_epsilon, cfg.param_dtype, name="post_norm")(x)
        ffn = DenseMLP(cfg, name="mlp") if self.dense else SigmoidMoE(cfg, name="moe")
        return x + ffn(h)


class MiMoV2ForCausalLM(nn.Module):
    """Decoder-only LM. Returns logits [batch, seq, vocab] in float32."""

    config: MiMoV2Config

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
            x = MiMoV2Block(cfg, cfg.is_sliding(i), cfg.is_dense(i), name=f"layer_{i}")(
                x, positions, decode, fresh_prefill, cache_write_mask, block_tables, cache_write_len)
        x = RMSNorm(cfg.layernorm_epsilon, cfg.param_dtype, name="final_norm")(x)
        if return_hidden:
            return x
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype)
        return jnp.matmul(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)
