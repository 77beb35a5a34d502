"""Qwen3-Next (`model_type: qwen3_next`): a hybrid decoder. Three gated-DeltaNet
linear-attention layers, then one gated softmax-attention layer (zero-centred
RMSNorm, per-head q/k norm, rotary on a fraction of each head, a sigmoid gate
on the heads' outputs); every layer's MLP is many small routed experts plus a
shared expert behind a sigmoid gate. Untied head. The multi-token prediction
module is not here.

What it asks of the framework that the other decoders do not:

  - two kinds of decode state in one cache tree. The softmax layers keep keys
    and values (the per-slot / paged layouts of `models/kv_cache.py`, the fused
    paged kernel at 8 query heads a key/value head); the DeltaNet layers keep,
    per slot, the last ``conv_width - 1`` inputs of their convolution
    (``conv_state``) and a float32 matrix a value head (``delta_state``).
    `Qwen3NextConfig.cache_contract` tells the serving engine which is which.
  - an expert layer that is one chip's share under expert parallelism
    (`ops/moe.held_experts_mlp`): ``num_experts`` is the router's width,
    ``experts_held`` how many of them (from ``first_expert``) this chip has.
  - a sliced vocabulary is just a smaller ``vocab_size``.

Decode-mode calls (``decode=True``) follow `GPT2LMHead`'s arguments. A segment
longer than one token with a static ``position_offset == 0`` is a prefill from
an empty cache: it attends itself only (flash for long buckets) and writes the
cache; ``cache_write_len`` ([b]) then gives each row's true length inside the
padded segment, so that pad tokens leave the recurrent state untouched and the
state written is the one after the row's last real token.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention
from ..ops.gated_delta import (
    causal_conv_prefill,
    causal_conv_step,
    gated_delta_prefill,
    gated_delta_step,
    mask_pad,
)
from ..ops.moe import held_experts_mlp, route_top_k, shared_expert_mlp
from ..parallel.sharding import ShardingRules

STATE_LEAVES = ("conv_state", "delta_state")
STEP_COUNTERS = ("moe_picks_held", "moe_experts_touched")


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    linear_num_key_heads: int = 16
    linear_key_head_dim: int = 128
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_experts: int = 512  # the router's width, always as published
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    # one expert-parallel chip's share: experts [first_expert, first_expert +
    # experts_held) live here; None holds them all
    experts_held: int | None = None
    first_expert: int = 0
    n_positions: int = 4096  # the context served (published: 262,144)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    delta_chunk: int = 64
    # the serving engine's cache switches, as on GPT2Config
    kv_cache_dtype: Any = None
    kv_cache_per_slot: bool = False
    kv_cache_paged: bool = False
    kv_num_blocks: int = 0
    kv_block_tokens: int = 16
    kv_paged_attention: str = "gather"
    kv_cache_sharding: Any = None

    @classmethod
    def tiny(cls, **kw) -> "Qwen3NextConfig":
        """Test-sized: every mechanism, one period of layers."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, linear_num_key_heads=2, linear_key_head_dim=16,
            linear_num_value_heads=4, linear_value_head_dim=16, num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=32, shared_expert_intermediate_size=32,
            n_positions=128, delta_chunk=8, dtype=jnp.float32, param_dtype=jnp.float32), **kw})

    def is_full_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    def cache_contract(self):
        from .kv_cache import CacheContract

        return CacheContract(
            kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
            state_leaves=STATE_LEAVES, step_counters=STEP_COUNTERS,
            param_rules=qwen3_next_sharding_rules)


class ZeroCentredRMSNorm(nn.Module):
    """``x / rms(x) * (1 + w)``, statistics in float32; ``w`` starts at 0."""

    eps: float = 1e-6
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _dense(cfg: Qwen3NextConfig, features: int, name: str) -> nn.Module:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)


def partial_rope(x: jax.Array, positions: jax.Array, theta: float, rot: int) -> jax.Array:
    """Rotate-half rotary on the first ``rot`` dims of each head of
    ``x [b, s, h, d]`` at ``positions [b, s]``; the rest passes through."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv  # [b, s, rot/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., : rot // 2]], -1)
    return jnp.concatenate([(xr * cos + half * sin).astype(x.dtype), x[..., rot:]], -1)


class GatedAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        b, s, _ = x.shape
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        qg = _dense(cfg, hq * 2 * d, "q_proj")(x).reshape(b, s, hq, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = _dense(cfg, hkv * d, "k_proj")(x).reshape(b, s, hkv, d)
        v = _dense(cfg, hkv * d, "v_proj")(x).reshape(b, s, hkv, d)
        q = ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="q_norm")(q)
        k = ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="k_norm")(k)
        rot = int(d * cfg.partial_rotary_factor)
        q = partial_rope(q, positions, cfg.rope_theta, rot)
        k = partial_rope(k, positions, cfg.rope_theta, rot)

        if not decode:
            out = attention(q, k, v, causal=True, implementation=cfg.attention_impl)
        elif cfg.kv_cache_paged and cfg.kv_paged_attention == "fused" and s == 1 \
                and cache_write_len is None:
            from .gpt2 import _fused_paged_attention
            from .kv_cache import paged_decode_write

            k_pool, v_pool, idx, is_init, scale_pools = paged_decode_write(
                self, k, v, cfg.kv_num_blocks, cfg.kv_block_tokens, block_tables,
                kv_cache_dtype=cfg.kv_cache_dtype, write_mask=cache_write_mask,
                sharding=cfg.kv_cache_sharding)
            if is_init:
                out = _fused_paged_attention(q[:, 0], k_pool, v_pool, block_tables, idx + 1,
                                             scale_pools, cfg.kv_cache_sharding)[:, None]
            else:
                out = attention(q, k_pool, v_pool, causal=True, implementation="xla")
        else:
            if cfg.kv_cache_paged:
                from .kv_cache import paged_decode_update

                k_all, v_all, idx, is_init = paged_decode_update(
                    self, k, v, cfg.kv_num_blocks, cfg.kv_block_tokens, block_tables,
                    kv_cache_dtype=cfg.kv_cache_dtype, write_mask=cache_write_mask,
                    write_len=cache_write_len, sharding=cfg.kv_cache_sharding)
            else:
                from .kv_cache import decode_cache_update

                k_all, v_all, idx, is_init = decode_cache_update(
                    self, k, v, cfg.n_positions, kv_cache_dtype=cfg.kv_cache_dtype,
                    per_slot=cfg.kv_cache_per_slot, write_mask=cache_write_mask,
                    write_len=cache_write_len, sharding=cfg.kv_cache_sharding)
            if not is_init or fresh_prefill:
                # nothing earlier to read: the segment attends itself
                out = attention(q, k, v, causal=True, implementation=cfg.attention_impl)
            else:
                q_pos = jnp.reshape(idx, (-1, 1, 1)) + jnp.arange(s)[None, :, None]
                kv_pos = jnp.arange(k_all.shape[1])[None, None, :]
                out = attention(q, k_all, v_all, causal=False, mask=(kv_pos <= q_pos)[:, None],
                                implementation="xla")
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
        return _dense(cfg, cfg.hidden_size, "o_proj")(out.reshape(b, s, hq * d))


class GatedDeltaNet(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, decode=False, fresh_prefill=False, cache_write_mask=None,
                 cache_write_len=None):
        cfg = self.config
        b, s, _ = x.shape
        hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        width, n_qkv = cfg.linear_conv_kernel_dim, 2 * hk * dk + hv * dv
        qkvz = _dense(cfg, n_qkv + hv * dv, "in_proj_qkvz")(x)
        ba = _dense(cfg, 2 * hv, "in_proj_ba")(x).astype(jnp.float32)
        qkv, z = qkvz[..., :n_qkv], qkvz[..., n_qkv:].reshape(b, s, hv, dv)
        conv_w = self.param("conv_w", nn.initializers.normal(0.02), (width, n_qkv), cfg.param_dtype)
        a_log = self.param("A_log", nn.initializers.zeros, (hv,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(ba[..., hv:] + dt_bias.astype(jnp.float32))

        if decode:
            is_init = self.has_variable("cache", "delta_state")
            conv_state = self.variable("cache", "conv_state", jnp.zeros, (b, width - 1, n_qkv), cfg.dtype)
            delta_state = self.variable("cache", "delta_state", jnp.zeros, (b, hv, dk, dv), jnp.float32)
        else:
            is_init = False

        def heads(mixed):  # conv output [.., n_qkv] -> q, k, v per value head, float32
            mixed = jax.nn.silu(mixed.astype(jnp.float32))
            lead = mixed.shape[:-1]
            q = mixed[..., : hk * dk].reshape(lead + (hk, dk))
            k = mixed[..., hk * dk: 2 * hk * dk].reshape(lead + (hk, dk))
            v = mixed[..., 2 * hk * dk:].reshape(lead + (hv, dv))
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            return jnp.repeat(q, hv // hk, -2), jnp.repeat(k, hv // hk, -2), v

        if is_init and s == 1 and not fresh_prefill:
            mixed, window = causal_conv_step(conv_state.value, qkv[:, 0], conv_w)
            q, k, v = heads(mixed)
            if cache_write_mask is not None:  # a finished slot's state does not move: no decay, no write
                g, beta = mask_pad(g, beta, cache_write_mask.astype(jnp.int32))
                window = jnp.where(cache_write_mask.astype(bool)[:, None, None], window, conv_state.value)
            new_state, o = gated_delta_step(delta_state.value, q, k, v, g[:, 0], beta[:, 0])
            conv_state.value, delta_state.value = window, new_state
            o = o[:, None]
        else:
            if is_init and not fresh_prefill:
                raise NotImplementedError(
                    "a multi-token segment on top of recurrent state (prefix reuse, speculative "
                    "verify) is not supported: only prefill from an empty cache and one-token decode")
            mixed, window = causal_conv_prefill(qkv, conv_w, cache_write_len)
            q, k, v = heads(mixed)
            g, beta = mask_pad(g, beta, cache_write_len)
            o, new_state = gated_delta_prefill(q, k, v, g, beta, chunk=cfg.delta_chunk)
            if is_init:
                conv_state.value, delta_state.value = window.astype(cfg.dtype), new_state

        # per-head RMSNorm with a plain weight, gated by silu(z)
        w = self.param("norm", nn.initializers.ones, (dv,), cfg.param_dtype)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
        o = o * w.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return _dense(cfg, cfg.hidden_size, "out_proj")(o.reshape(b, s, hv * dv).astype(cfg.dtype))


class SparseMoE(nn.Module):
    """The routed experts this chip holds, plus the shared expert."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, e = x.shape
        held = cfg.num_experts if cfg.experts_held is None else cfg.experts_held
        f, fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (e, cfg.num_experts), jnp.float32)
        w_gate_up = self.param("w_gate_up", init, (held, e, 2 * f), cfg.param_dtype)
        w_down = self.param("w_down", init, (held, f, e), cfg.param_dtype)
        s_gate = self.param("shared_gate", init, (e,), cfg.param_dtype)
        s_gate_up = self.param("shared_gate_up", init, (e, 2 * fs), cfg.param_dtype)
        s_down = self.param("shared_down", init, (fs, e), cfg.param_dtype)
        xt = x.reshape(b * s, e)
        weights, idx = route_top_k(xt, router, cfg.num_experts_per_tok)
        out, picks, touched = held_experts_mlp(xt, weights, idx, w_gate_up, w_down, cfg.first_expert,
                                               cfg.num_experts)
        out = out + shared_expert_mlp(xt, s_gate, s_gate_up, s_down)
        for name, value in zip(STEP_COUNTERS, (picks, touched)):
            self.sow("counters", name, value, reduce_fn=lambda a, c: a + c,
                     init_fn=lambda: jnp.zeros((), jnp.int32))
        return out.reshape(b, s, e).astype(x.dtype)


class Qwen3NextBlock(nn.Module):
    config: Qwen3NextConfig
    full_attention: bool

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        h = ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="input_norm")(x)
        if self.full_attention:
            h = GatedAttention(cfg, name="attn")(h, positions, decode, fresh_prefill,
                                                 cache_write_mask, block_tables, cache_write_len)
        else:
            h = GatedDeltaNet(cfg, name="delta")(h, decode, fresh_prefill, cache_write_mask,
                                                 cache_write_len)
        x = x + h
        h = ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="post_norm")(x)
        return x + SparseMoE(cfg, name="moe")(h)


class Qwen3NextForCausalLM(nn.Module):
    """Decoder-only LM. Returns logits [batch, seq, vocab] in float32."""

    config: Qwen3NextConfig

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
            x = Qwen3NextBlock(cfg, cfg.is_full_attention(i), name=f"layer_{i}")(
                x, positions, decode, fresh_prefill, cache_write_mask, block_tables, cache_write_len)
        x = ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="final_norm")(x)
        if return_hidden:
            return x
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype)
        return jnp.matmul(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)

    def init_params(self, rng: jax.Array, batch: int = 1, seq: int = 8) -> Any:
        return self.init(rng, jnp.zeros((batch, seq), jnp.int32))["params"]


def qwen3_next_sharding_rules() -> ShardingRules:
    """Expert parallelism as sharding annotations: the expert-stacked weights
    split their leading dim over ``tensor``. The serving engine does not serve
    this model on a mesh yet (per-slot recurrent state has no mesh layout and
    the experts' exchange is not written); the rules are for `prepare`."""
    return ShardingRules(rules=[
        (r".*moe/w_gate_up", P("tensor", None, None)),
        (r".*moe/w_down", P("tensor", None, None)),
    ])
