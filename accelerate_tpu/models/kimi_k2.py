"""Kimi K2 (`model_type: kimi_k2`; the DeepSeek-V3 family's layer): a decoder
with multi-head latent attention (MLA), a leading dense layer and, from layer
``first_k_dense_replace`` on, many routed experts chosen by a sigmoid router
under a selection bias, beside one ungated shared expert. Untied head. No
vision tower and no multi-token prediction module here.

Block ``l``: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``
(``rms_norm_eps`` 1e-5, norm weights start at 1), a final RMSNorm and the head.
``FFN_l`` is a SwiGLU MLP of width ``intermediate_size``
(``down(silu(gate x) * up x)``) for ``l < first_k_dense_replace`` and the
expert layer after.

Attention (``H`` heads; no biases)::

    q = W_qb RMSNorm(W_qa x)                per head [q_nope (128) | q_pe (64)]
    [c (512) | k_pe (64)] = W_kva x         c~ = RMSNorm(c); k_pe one vector a
                                            token, shared by all heads
    [k_nope_h (128) | v_h (128)] = W_kvb,h c~
    q_pe, k_pe <- RoPE(., position)         rotate-half over the 64 dims, YaRN
    score_h(t, s) = scale * (q_nope_h(t) . k_nope_h(s) + q_pe_h(t) . k_pe(s))
    out = W_o concat_h(sum_s softmax_s(score_h(t, .)) v_h(s))      causal, float32 softmax

Absorbed form (algebraically the same; what a decode step runs): with
``W_kvb,h = [W_uk,h ; W_uv,h]``,

    q_lat_h = W_uk,h^T q_nope_h (512)
    score_h(t, s) = scale * ([q_lat_h | q_pe_h] . [c~(s) | k_pe(s)])
    o_lat_h = sum_s p_h(t, s) c~(s) (512)       v_out_h = W_uv,h o_lat_h (128)

so the cache holds ONE row a token a layer, ``[c~(s) | k_pe(s)]`` after the
norm and after the rotation (576 lanes, stored padded with zeros to 640: a
whole number of 128-lane tiles), shared by all heads; the value is its first
512 lanes. `KimiK2Config.cache_contract` says so (``value_dim``) and
`ops.flash_attention.paged_decode_attention` reads it in place: 64 query heads
against one row in one MXU product a chunk.

YaRN (`yarn_inv_freq`, `yarn_mscale`) over ``d = qk_rope_head_dim``: ``f_i =
theta^(-2i/d)``; ``corr(r) = d ln(L0 / (2 pi r)) / (2 ln theta)``; ``low =
floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))`` clamped to ``[0, d -
1]``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = f_i /
factor * ramp_i + f_i * (1 - ramp_i)``. ``m(s, a) = 0.1 a ln s + 1``; cos and
sin are multiplied by ``m(factor, mscale) / m(factor, mscale_all_dim)`` and
``scale = (128 + 64)^(-1/2) * m(factor, mscale_all_dim)^2``.

Expert layer (`ops/moe.py`): ``s = sigmoid(W_g x)`` in float32 over all
``n_routed_experts``; the ``num_experts_per_tok`` with the largest ``s + b``
are chosen (``b`` the ``e_score_correction_bias``, used to choose, never to
weigh; ``n_group = topk_group = 1``: no group limit); ``w_e =
routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)``; ``FFN(x) = sum_e
w_e E_e(x) + E_shared(x)``, each ``E`` a SwiGLU MLP of width
``moe_intermediate_size``. ``experts_held`` / ``first_expert`` make the layer
one expert-parallel chip's share (`held_experts_mlp`).

Decode-mode calls follow `GPT2LMHead`'s arguments. A segment longer than one
token with a static ``position_offset == 0`` is a prefill from an empty cache:
it runs the plain form over itself and writes the latent rows. Anything on top
of cached rows (a decode step, a speculative verify segment, a suffix prefill
behind the prefix cache) runs the absorbed form through the cache: the fused
kernel for a one-token step of the paged fused engine, else an einsum over the
gathered rows under the frontier mask.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention
from ..ops.moe import held_experts_mlp, route_sigmoid_top_k, shared_expert_mlp
from ..parallel.sharding import ShardingRules

STEP_COUNTERS = ("moe_picks_held", "moe_experts_touched")
LANE_TILE = 128


@dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432  # the dense layers' MLP
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1  # layers [0, this) are dense
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 384  # the router's width, always as published
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
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

    @classmethod
    def tiny(cls, **kw) -> "KimiK2Config":
        """Test-sized: every mechanism: a dense layer, two expert layers, a
        router wider than the experts held when ``experts_held`` is given."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16, n_routed_experts=16,
            num_experts_per_tok=4, n_positions=128, rope_original_max_position=32,
            rope_factor=4.0, dtype=jnp.float32, param_dtype=jnp.float32), **kw})

    @property
    def latent_width(self) -> int:
        """Lanes of a token's latent row that hold something."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_lanes(self) -> int:
        """Lanes a latent row is stored with: padded with zeros to whole lane
        tiles, so the pool's blocks, the kernel's copies and the MXU operands
        are tile-aligned (576 -> 640; `docs/serving.md` "Fused paged decode")."""
        return -(-self.latent_width // LANE_TILE) * LANE_TILE

    @property
    def softmax_scale(self) -> float:
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return qk ** -0.5 * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def cache_contract(self):
        from .kv_cache import CacheContract

        return CacheContract(
            kv_heads=1, head_dim=self.latent_row_lanes, value_dim=self.kv_lora_rank,
            step_counters=STEP_COUNTERS, param_rules=kimi_k2_sharding_rules)


# ------------------------------------------------------------------- rotary
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: KimiK2Config) -> jax.Array:
    """The rotary inverse frequencies ``[d / 2]`` under YaRN (module docstring)."""
    d, theta = cfg.qk_rope_head_dim, cfg.rope_theta

    def corr(rotations):
        return d * math.log(cfg.rope_original_max_position / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_beta_slow)), d - 1)
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1)
    return f / cfg.rope_factor * ramp + f * (1 - ramp)


def yarn_rope(x: jax.Array, positions: jax.Array, cfg: KimiK2Config) -> jax.Array:
    """Rotate-half rotary over all of ``x [b, s, h, d]`` at ``positions [b, s]``."""
    ang = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)  # [b, s, d/2]
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None, :] * m
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None, :] * m
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


class RMSNorm(nn.Module):
    """``x / rms(x) * w``, statistics in float32; ``w`` starts at 1."""

    eps: float = 1e-5
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        w = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * w.astype(jnp.float32)).astype(x.dtype)


def _dense(cfg: KimiK2Config, features: int, name: str) -> nn.Module:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)


def absorbed_attention(q_abs: jax.Array, rows: jax.Array, mask: jax.Array, scale: float,
                       value_dim: int) -> jax.Array:
    """The absorbed form over gathered latent rows: ``q_abs [b, s, h, L]``
    against ``rows [b, T, L]`` (one row a token, shared by the heads) under
    ``mask [b, 1, s, T]``; float32 softmax; ``[b, s, h, value_dim]``."""
    logits = jnp.einsum("bshl,btl->bhst", q_abs, rows, preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    weights = jax.nn.softmax(logits, axis=-1).astype(q_abs.dtype)
    return jnp.einsum("bhst,btv->bshv", weights, rows[..., :value_dim])


def latent_attend(mod: nn.Module, cfg, q_nope, q_pe, c, k_pe, kv_b, scale: float, decode=False,
                  fresh_prefill=False, cache_write_mask=None, block_tables=None,
                  cache_write_len=None) -> jax.Array:
    """Latent attention from its projected parts to the heads' outputs ``[b, s,
    h, dv]``, through ``mod``'s cache in decode mode (module docstring: the
    plain form for a segment that starts a sequence, the absorbed form on top
    of cached rows). ``q_nope [b, s, h, nope]``, ``q_pe [b, s, h, rope]`` and
    ``k_pe [b, s, 1, rope]`` rotated, ``c [b, s, rank]`` normed, ``kv_b [rank,
    h, nope + dv]``. ``cfg`` gives ``latent_row_lanes``, ``attention_impl``,
    ``n_positions`` and the engine's cache switches: any model's config whose
    contract declares the latent leaf."""
    b, s, h, nope = q_nope.shape
    rope, rank, lanes = q_pe.shape[-1], c.shape[-1], cfg.latent_row_lanes
    dv = kv_b.shape[-1] - nope

    def plain():
        """Multi-head attention of the segment over itself (query/key 192,
        value 128). `attention` takes one head size and scales by its
        inverse root: the value is padded with zeros to the keys' width,
        and the scale's other factors ride on the query."""
        with jax.named_scope("mla_prefill"):
            kv = jnp.einsum("bsc,chd->bshd", c, kv_b)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
            fold = scale * (nope + rope) ** 0.5
            qs = jnp.concatenate([q_nope, q_pe], -1) * jnp.asarray(fold, q_nope.dtype)
            v = jnp.pad(kv[..., nope:], ((0, 0), (0, 0), (0, 0), (0, nope + rope - dv)))
            # blocks of 512 divide both serving buckets the benchmark uses;
            # `auto` then takes the flash kernel on the chip from 1,024 up
            blocks = dict(block_q=512, block_kv=512) if s % 512 == 0 else {}
            return attention(qs, k, v, causal=True, implementation=cfg.attention_impl,
                             **blocks)[..., :dv]

    def absorbed(attend):
        """``attend(q_abs [b, s, h, lanes]) -> o_lat [b, s, h, rank]``, between
        the two absorbing products. ``attend`` runs outside their scope: the
        fused kernel keeps the name of the flax scope, ``%attn.N``, as the
        other models' does."""
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bshn,chn->bshc", q_nope, kv_b[..., :nope])
            q_abs = jnp.concatenate(
                [q_lat, q_pe, jnp.zeros((b, s, h, lanes - rank - rope), q_nope.dtype)], -1)
        o_lat = attend(q_abs)
        with jax.named_scope("mla_absorb"):
            return jnp.einsum("bshc,chv->bshv", o_lat, kv_b[..., nope:])

    # the row the cache keeps: after the norm and after the rotation
    row = jnp.concatenate([c[:, :, None, :], k_pe,
                           jnp.zeros((b, s, 1, lanes - rank - rope), c.dtype)], -1)
    if not decode:
        return plain()
    if cfg.kv_cache_paged and cfg.kv_paged_attention == "fused" and s == 1 \
            and cache_write_len is None:
        from ..ops.flash_attention import paged_decode_attention
        from .kv_cache import paged_decode_write

        pool, _, idx, is_init, _ = paged_decode_write(
            mod, row, None, cfg.kv_num_blocks, cfg.kv_block_tokens, block_tables,
            kv_cache_dtype=cfg.kv_cache_dtype, write_mask=cache_write_mask,
            sharding=cfg.kv_cache_sharding)
        if not is_init:
            return plain()
        return absorbed(lambda q_abs: paged_decode_attention(
            q_abs[:, 0], pool, None, block_tables, idx + 1, value_dim=rank,
            scale=scale)[:, None])
    if cfg.kv_cache_paged:
        from .kv_cache import paged_decode_update

        rows, _, idx, is_init = paged_decode_update(
            mod, row, None, cfg.kv_num_blocks, cfg.kv_block_tokens, block_tables,
            kv_cache_dtype=cfg.kv_cache_dtype, write_mask=cache_write_mask,
            write_len=cache_write_len, sharding=cfg.kv_cache_sharding)
    else:
        from .kv_cache import decode_cache_update

        rows, _, idx, is_init = decode_cache_update(
            mod, row, None, cfg.n_positions, kv_cache_dtype=cfg.kv_cache_dtype,
            per_slot=cfg.kv_cache_per_slot, write_mask=cache_write_mask,
            write_len=cache_write_len, sharding=cfg.kv_cache_sharding)
    if not is_init or fresh_prefill:
        # nothing earlier to read: the segment attends itself
        return plain()
    q_pos = jnp.reshape(idx, (-1, 1, 1)) + jnp.arange(s)[None, :, None]
    mask = (jnp.arange(rows.shape[1])[None, None, :] <= q_pos)[:, None]
    return absorbed(lambda q_abs: absorbed_attention(q_abs, rows[:, :, 0], mask, scale, rank))


class LatentAttention(nn.Module):
    config: KimiK2Config

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        b, s, _ = x.shape
        h, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        rank = cfg.kv_lora_rank
        q = _dense(cfg, cfg.q_lora_rank, "q_a_proj")(x)
        q = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="q_a_norm")(q)
        q = _dense(cfg, h * (nope + rope), "q_b_proj")(q).reshape(b, s, h, nope + rope)
        q_nope, q_pe = q[..., :nope], yarn_rope(q[..., nope:], positions, cfg)
        ckv = _dense(cfg, rank + rope, "kv_a_proj")(x)
        c = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="kv_a_norm")(ckv[..., :rank])
        k_pe = yarn_rope(ckv[..., None, rank:], positions, cfg)  # [b, s, 1, rope]
        kv_b = self.param("kv_b_proj", nn.initializers.normal(0.02),
                          (rank, h, nope + dv), cfg.param_dtype).astype(cfg.dtype)
        out = latent_attend(self, cfg, q_nope, q_pe, c, k_pe, kv_b, cfg.softmax_scale, decode,
                            fresh_prefill, cache_write_mask, block_tables, cache_write_len)
        return _dense(cfg, cfg.hidden_size, "o_proj")(out.reshape(b, s, h * dv))


PREFILL_TOKEN_CHUNKS = (1536, 1024)  # tokens a turn of an FFN over a long segment


def by_token_chunks(ffn, *xs: jax.Array):
    """``ffn(*xs) -> (out [T, hidden], *int32 counts)`` over an admit program's
    tokens a chunk at a time (`lax.map` over the leading axis of every
    ``xs``): what it wraps is compute bound, so a chunk's one more read of
    the weights costs little, and the float32 intermediates of 6,144 tokens
    (the dense layer's ``[T, 36864]``) are GBs at once. It wraps the dense
    MLP and the shared expert; the routed experts take the whole segment and
    bound their own intermediates by windows (`held_experts_mlp`), because at
    tens of rows an expert a chunk the products wait on the weights' read. A
    decode step's few rows, and any count the chunks do not divide, go whole.
    Counts add up over the chunks."""
    n_tokens = xs[0].shape[0]
    chunk = next((c for c in PREFILL_TOKEN_CHUNKS if n_tokens > c and n_tokens % c == 0), None)
    if chunk is None:
        return ffn(*xs)
    out, *counts = jax.lax.map(lambda part: ffn(*part),
                               tuple(a.reshape(n_tokens // chunk, chunk, -1) for a in xs))
    return (out.reshape(n_tokens, -1), *(c.sum() for c in counts))


class DenseMLP(nn.Module):
    """``down(silu(gate x) * up x)`` of width ``intermediate_size``."""

    config: KimiK2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        e, f = cfg.hidden_size, cfg.intermediate_size
        init = nn.initializers.normal(0.02)
        gate_up = self.param("gate_up", init, (e, 2 * f), cfg.param_dtype)
        down = self.param("down", init, (f, e), cfg.param_dtype)
        def ffn(xt):
            with jax.named_scope("dense_mlp"):
                return (shared_expert_mlp(xt, None, gate_up, down).astype(x.dtype),)

        return by_token_chunks(ffn, x.reshape(-1, e))[0].reshape(x.shape)


class SigmoidMoE(nn.Module):
    """The routed experts this chip holds, plus the ungated shared expert
    where ``n_shared_experts`` is above 0 (0 or None: none, and no weights
    for it)."""

    config: KimiK2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, e = x.shape
        held = cfg.n_routed_experts if cfg.experts_held is None else cfg.experts_held
        f, fs = cfg.moe_intermediate_size, cfg.moe_intermediate_size * (cfg.n_shared_experts or 0)
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (e, cfg.n_routed_experts), jnp.float32)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (cfg.n_routed_experts,), jnp.float32)
        w_gate_up = self.param("w_gate_up", init, (held, e, 2 * f), cfg.param_dtype)
        w_down = self.param("w_down", init, (held, f, e), cfg.param_dtype)
        if fs:
            s_gate_up = self.param("shared_gate_up", init, (e, 2 * fs), cfg.param_dtype)
            s_down = self.param("shared_down", init, (fs, e), cfg.param_dtype)

        xt = x.reshape(b * s, e)
        weights, idx = route_sigmoid_top_k(xt, router, bias, cfg.num_experts_per_tok,
                                           cfg.routed_scaling_factor)
        routed, *counts = held_experts_mlp(xt, weights, idx, w_gate_up, w_down, cfg.first_expert,
                                           cfg.n_routed_experts)
        if fs:
            out, = by_token_chunks(lambda xc, rc: (
                (rc + shared_expert_mlp(xc, None, s_gate_up, s_down)).astype(x.dtype),), xt, routed)
        else:
            out = routed.astype(x.dtype)
        for name, value in zip(STEP_COUNTERS, counts):
            self.sow("counters", name, value, reduce_fn=lambda a, c: a + c,
                     init_fn=lambda: jnp.zeros((), jnp.int32))
        return out.reshape(b, s, e)


class KimiK2Block(nn.Module):
    config: KimiK2Config
    dense: bool

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="input_norm")(x)
        x = x + LatentAttention(cfg, name="attn")(h, positions, decode, fresh_prefill,
                                                  cache_write_mask, block_tables, cache_write_len)
        h = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="post_norm")(x)
        ffn = DenseMLP(cfg, name="mlp") if self.dense else SigmoidMoE(cfg, name="moe")
        return x + ffn(h)


class KimiK2ForCausalLM(nn.Module):
    """Decoder-only LM. Returns logits [batch, seq, vocab] in float32."""

    config: KimiK2Config

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
            x = KimiK2Block(cfg, cfg.is_dense(i), name=f"layer_{i}")(
                x, positions, decode, fresh_prefill, cache_write_mask, block_tables, cache_write_len)
        x = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="final_norm")(x)
        if return_hidden:
            return x
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype)
        return jnp.matmul(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)

    def init_params(self, rng: jax.Array, batch: int = 1, seq: int = 8) -> Any:
        return self.init(rng, jnp.zeros((batch, seq), jnp.int32))["params"]


def kimi_k2_sharding_rules() -> ShardingRules:
    """Expert parallelism as sharding annotations: the expert-stacked weights
    split their leading dim over ``tensor``. The serving engine does not serve
    this model on a mesh (a latent row is shared by all heads, so the pool
    cannot split on heads, and the experts' exchange is not written); the
    rules are for `prepare`."""
    return ShardingRules(rules=[
        (r".*moe/w_gate_up", P("tensor", None, None)),
        (r".*moe/w_down", P("tensor", None, None)),
    ])
