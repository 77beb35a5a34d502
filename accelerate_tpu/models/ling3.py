"""Ling 3.0 flash (`inclusionAI/Ling-3.0-flash-VL` `config.json`, the language
model; the catalog's row holds no key of the vision tower beyond four token
ids, and none of the multi-token prediction module its description mentions:
both are left out): a hybrid decoder. Layer ``l`` mixes by latent attention
(MLA) where ``(l + 1) % layer_group_size == 0`` and by Kimi Delta Attention
(KDA, Kimi Linear, arXiv:2510.26692) otherwise: five KDA layers to one MLA
layer. Layers ``[0, first_k_dense_replace)`` carry a dense SwiGLU MLP, the
rest many small routed experts under DeepSeek-V3's group-limited sigmoid
router beside one ungated shared expert. Untied head.

Block ``l``: ``h = x + Mixer_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``
(``rms_norm_eps`` 1e-6, norm weights start at 1), a final RMSNorm, the head.

KDA mixer (``H`` heads, ``d_k = d_v = head_dim``; float32 state ``S [d_k,
d_v]`` a head; no position enters)::

    q = L2(SiLU(conv(W_q x))) * d_k^-1/2    k = L2(SiLU(conv(W_k x)))    v = SiLU(conv(W_v x))
    a = W_f x + dt_bias  [H, d_k]           g = kda_lower_bound * sigmoid(exp(A_log_h) * a)
    beta = sigmoid(W_b x)  [H]              g in (-5, 0): one log decay a key CHANNEL
    S <- Diag(exp(g)) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q
    Mixer(x) = W_o [ RMSNorm_{d_v}(o) * sigmoid(W_z x) ]

``conv`` is the causal depthwise convolution of ``short_conv_kernel_size``
taps, L2 is per head. It is `ops/gated_delta.py`'s rule with ``exp(g)`` a
vector over the rows of ``S`` where Qwen3-Next has one scalar a head
(`gated_delta_step`, `gated_delta_prefill` with ``g [.., h, d_k]``). ``W_q, W_k, W_v, W_z`` are one matrix
here (``in_proj_qkvz``), ``W_f, W_b`` another whose product stays float32
(``in_proj_fb``): the gate's arithmetic is float32 from the accumulator on.

MLA mixer: `models/kimi_k2.py`'s (`latent_attend`: the plain form for a
segment that starts a sequence, the absorbed form against ONE cache row a
token ``[c~ (512) | k_pe (64) | zeros (64)]`` otherwise, the fused paged kernel
for a decode step) with an uncompressed query (``q_lora_rank`` null: ``q = W_q
x``), plain rotary of ``rope_theta`` on the 64 rotary dims, scale ``(128 +
64)^-1/2`` and a head-wise output gate: ``out = W_o concat_h(sigmoid(w_h . x)
attn_h)``.

Router (`ops/moe.route_sigmoid_top_k`): ``s = sigmoid(W_g x)`` in float32 over
all ``num_experts``; the experts lie in ``n_group`` groups; a group's score is
the sum of its two largest ``s + b`` (``b`` the expert bias); the
``topk_group`` best groups are kept; the ``num_experts_per_tok`` largest ``s +
b`` inside them are chosen; ``w_e = routed_scaling_factor * s_e / (sum of the
chosen s + 1e-20)``; ``FFN(x) = sum_e w_e E_e(x) + E_shared(x)``, every ``E`` a
SwiGLU MLP. ``experts_held`` / ``first_expert`` make the layer one
expert-parallel chip's share: a chip that holds whole groups gets several of a
token's picks or none (`moe_rows_routed_here` counts the tokens with any).

Readings of the published keys that are conventions of the family and not
statements of the config (the benchmark's configuration file lists them under
``assumed``): ``layer_group_size`` as above (the Ling/Ring linear family's
rule); ``kda_safe_gate`` with ``kda_lower_bound`` -5 as the bounded gate above
(the flash-linear-attention library's lower-bound gate; the unbounded form is
``-exp(A_log) softplus(a)``); ``no_kda_lora`` as full-rank ``W_f`` and ``W_z``;
``use_qk_norm`` as KDA's own L2 on q and k and nothing more on the MLA layers
than the latent's RMSNorm (a norm over a head's concatenated 192-dim key could
not be absorbed into a shared row); ``use_mla_nope`` false with
``partial_rotary_factor`` 0.5 x ``head_dim`` 128 = ``rotary_dim`` 64 as "the MLA
layers rotate their 64 dims, the KDA layers carry no position";
``group_norm_size`` 1 as the per-head norm of ``o``;
``gated_attention_proj_granularity_type`` ``head_wise`` as the gate above. A
non-zero ``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``
entry of a layer held here is refused (the published lists read 0 for layers
0-34 and 0-33; the clamp's form is not in the config). ``use_nGPT``,
``scale_router_input``, ``value_norm``, ``up_proj_norm`` are false in the
config and have no code here.

Two kinds of decode state in one cache tree (`Ling3Config.cache_contract`):
per slot, a KDA layer's ``conv_state`` (the last ``width - 1`` inputs of the q,
k and v convolutions side by side) and ``kda_state`` (``S``, float32); in the
paged pool, an MLA layer's one latent leaf. Decode-mode calls follow
`GPT2LMHead`'s arguments; ``cache_write_len`` gives each row's true length
inside a padded admit bucket, so that pad tokens leave the state untouched.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.gated_delta import (
    causal_conv_prefill,
    causal_conv_step,
    gated_delta_prefill,
    gated_delta_step,
    mask_pad,
)
from ..ops.moe import held_experts_mlp, route_sigmoid_top_k, shared_expert_mlp
from ..parallel.sharding import ShardingRules
from .kimi_k2 import LANE_TILE, DenseMLP, RMSNorm, _dense, by_token_chunks, latent_attend
from .qwen3_next import partial_rope

STATE_LEAVES = ("conv_state", "kda_state")
STEP_COUNTERS = ("moe_picks_held", "moe_experts_touched", "moe_rows_routed_here")


@dataclass(frozen=True)
class Ling3Config:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144  # the dense layers' MLP
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2  # layers [0, this) are dense
    layer_group_size: int = 6  # the last layer of each group is MLA, the others KDA
    num_attention_heads: int = 32  # of both mixers
    head_dim: int = 128  # KDA's d_k = d_v
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    num_experts: int = 512  # the router's width, always as published
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    expert_swiglu_limit_list: tuple = ()  # per layer; only zeros are supported
    share_expert_swiglu_limit_list: tuple = ()
    # one expert-parallel chip's share: experts [first_expert, first_expert +
    # experts_held) live here; None holds them all
    experts_held: int | None = None
    first_expert: int = 0
    n_positions: int = 4096  # the context served (published: 131,072)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    kda_chunk: int = 64
    # the serving engine's cache switches, as on GPT2Config
    kv_cache_dtype: Any = None
    kv_cache_per_slot: bool = False
    kv_cache_paged: bool = False
    kv_num_blocks: int = 0
    kv_block_tokens: int = 16
    kv_paged_attention: str = "gather"
    kv_cache_sharding: Any = None

    def __post_init__(self):
        for name in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
            limited = [i for i, v in enumerate(getattr(self, name)[: self.num_hidden_layers]) if v]
            if limited:
                raise NotImplementedError(
                    f"{name} is non-zero for layers {limited}: the config does not say how the "
                    "limit enters the SwiGLU, and this model does not guess")
        if self.num_experts % self.n_group:
            raise ValueError(f"n_group {self.n_group} does not divide num_experts {self.num_experts}")

    @classmethod
    def tiny(cls, **kw) -> "Ling3Config":
        """Test-sized: every mechanism: one period (five KDA layers, one MLA
        layer), two dense layers and four expert layers, four routing groups
        of which two are kept."""
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=32, num_hidden_layers=6, num_attention_heads=4,
            head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
            num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2, n_positions=128,
            kda_chunk=32, dtype=jnp.float32, param_dtype=jnp.float32), **kw})

    @property
    def latent_width(self) -> int:
        """Lanes of a token's latent row that hold something."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_lanes(self) -> int:
        """Lanes a latent row is stored with: whole lane tiles (576 -> 640)."""
        return -(-self.latent_width // LANE_TILE) * LANE_TILE

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def is_latent(self, layer: int) -> bool:
        return (layer + 1) % self.layer_group_size == 0

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def cache_contract(self):
        from .kv_cache import CacheContract

        return CacheContract(
            kv_heads=1, head_dim=self.latent_row_lanes, value_dim=self.kv_lora_rank,
            state_leaves=STATE_LEAVES, step_counters=STEP_COUNTERS,
            param_rules=ling3_sharding_rules)


class KimiDeltaAttention(nn.Module):
    config: Ling3Config

    @nn.compact
    def __call__(self, x, decode=False, fresh_prefill=False, cache_write_mask=None,
                 cache_write_len=None):
        cfg = self.config
        b, s, e = x.shape
        h, d, width = cfg.num_attention_heads, cfg.head_dim, cfg.short_conv_kernel_size
        n = h * d
        qkvz = _dense(cfg, 4 * n, "in_proj_qkvz")(x)
        w_fb = self.param("in_proj_fb", nn.initializers.normal(0.02), (e, n + h), cfg.param_dtype)
        fb = jnp.matmul(x, w_fb.astype(cfg.dtype), preferred_element_type=jnp.float32)
        qkv, z = qkvz[..., : 3 * n], qkvz[..., 3 * n:].reshape(b, s, h, d)
        conv_w = self.param("conv_w", nn.initializers.normal(0.02), (width, 3 * n), cfg.param_dtype)
        a_log = self.param("A_log", nn.initializers.zeros, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (n,), jnp.float32)
        a = (fb[..., :n] + dt_bias.astype(jnp.float32)).reshape(b, s, h, d)
        g = cfg.kda_lower_bound * jax.nn.sigmoid(jnp.exp(a_log.astype(jnp.float32))[:, None] * a)
        beta = jax.nn.sigmoid(fb[..., n:])

        if decode:
            is_init = self.has_variable("cache", "kda_state")
            conv_state = self.variable("cache", "conv_state", jnp.zeros, (b, width - 1, 3 * n), cfg.dtype)
            kda_state = self.variable("cache", "kda_state", jnp.zeros, (b, h, d, d), jnp.float32)
        else:
            is_init = False

        def heads(mixed):  # conv output [.., 3n] -> q, k, v [.., h, d], float32
            mixed = jax.nn.silu(mixed.astype(jnp.float32))
            q, k, v = (mixed[..., i * n: (i + 1) * n].reshape(mixed.shape[:-1] + (h, d)) for i in range(3))
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            return q, k, v

        if is_init and s == 1 and not fresh_prefill:
            mixed, window = causal_conv_step(conv_state.value, qkv[:, 0], conv_w)
            q, k, v = heads(mixed)
            if cache_write_mask is not None:  # a finished slot's state does not move: no decay, no write
                g, beta = mask_pad(g, beta, cache_write_mask.astype(jnp.int32))
                window = jnp.where(cache_write_mask.astype(bool)[:, None, None], window, conv_state.value)
            new_state, o = gated_delta_step(kda_state.value, q, k, v, g[:, 0], beta[:, 0])
            conv_state.value, kda_state.value = window, new_state
            o = o[:, None]
        else:
            if is_init and not fresh_prefill:
                raise NotImplementedError(
                    "a multi-token segment on top of recurrent state (prefix reuse, speculative "
                    "verify) is not supported: only prefill from an empty cache and one-token decode")
            mixed, window = causal_conv_prefill(qkv, conv_w, cache_write_len, window_first=True)
            q, k, v = heads(mixed)
            g, beta = mask_pad(g, beta, cache_write_len)
            o, new_state = gated_delta_prefill(q, k, v, g, beta, chunk=cfg.kda_chunk)
            if is_init:
                conv_state.value, kda_state.value = window.astype(cfg.dtype), new_state

        # per-head RMSNorm with a plain weight, gated by sigmoid(z)
        w = self.param("norm", nn.initializers.ones, (d,), cfg.param_dtype)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
        o = o * w.astype(jnp.float32) * jax.nn.sigmoid(z.astype(jnp.float32))
        return _dense(cfg, cfg.hidden_size, "out_proj")(o.reshape(b, s, n).astype(cfg.dtype))


class GatedLatentAttention(nn.Module):
    config: Ling3Config

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        b, s, _ = x.shape
        h, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        rank = cfg.kv_lora_rank
        q = _dense(cfg, h * (nope + rope), "q_proj")(x).reshape(b, s, h, nope + rope)
        q_nope, q_pe = q[..., :nope], partial_rope(q[..., nope:], positions, cfg.rope_theta, rope)
        ckv = _dense(cfg, rank + rope, "kv_a_proj")(x)
        c = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="kv_a_norm")(ckv[..., :rank])
        k_pe = partial_rope(ckv[..., None, rank:], positions, cfg.rope_theta, rope)  # [b, s, 1, rope]
        kv_b = self.param("kv_b_proj", nn.initializers.normal(0.02),
                          (rank, h, nope + dv), cfg.param_dtype).astype(cfg.dtype)
        gate = jax.nn.sigmoid(_dense(cfg, h, "g_proj")(x).astype(jnp.float32))  # one a head
        out = latent_attend(self, cfg, q_nope, q_pe, c, k_pe, kv_b, cfg.softmax_scale, decode,
                            fresh_prefill, cache_write_mask, block_tables, cache_write_len)
        out = out * gate[..., None].astype(out.dtype)
        return _dense(cfg, cfg.hidden_size, "o_proj")(out.reshape(b, s, h * dv))


class GroupLimitedMoE(nn.Module):
    """The routed experts this chip holds, chosen under the group limit, plus
    the ungated shared expert."""

    config: Ling3Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, e = x.shape
        held = cfg.num_experts if cfg.experts_held is None else cfg.experts_held
        f, fs = cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (e, cfg.num_experts), jnp.float32)
        bias = self.param("expert_bias", nn.initializers.zeros, (cfg.num_experts,), jnp.float32)
        w_gate_up = self.param("w_gate_up", init, (held, e, 2 * f), cfg.param_dtype)
        w_down = self.param("w_down", init, (held, f, e), cfg.param_dtype)
        s_gate_up = self.param("shared_gate_up", init, (e, 2 * fs), cfg.param_dtype)
        s_down = self.param("shared_down", init, (fs, e), cfg.param_dtype)

        xt = x.reshape(b * s, e)
        weights, idx = route_sigmoid_top_k(
            xt, router, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            cfg.n_group, cfg.topk_group)
        routed, picks, touched = held_experts_mlp(xt, weights, idx, w_gate_up, w_down,
                                                  cfg.first_expert, cfg.num_experts)
        here = (idx >= cfg.first_expert) & (idx < cfg.first_expert + held)
        out, = by_token_chunks(lambda xc, rc: (
            (rc + shared_expert_mlp(xc, None, s_gate_up, s_down)).astype(x.dtype),), xt, routed)
        counts = (picks, touched, jnp.sum(here.any(-1)).astype(jnp.int32))
        for name, value in zip(STEP_COUNTERS, counts):
            self.sow("counters", name, value, reduce_fn=lambda a, c: a + c,
                     init_fn=lambda: jnp.zeros((), jnp.int32))
        return out.reshape(b, s, e)


class Ling3Block(nn.Module):
    config: Ling3Config
    latent: bool
    dense: bool

    @nn.compact
    def __call__(self, x, positions, decode=False, fresh_prefill=False, cache_write_mask=None,
                 block_tables=None, cache_write_len=None):
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="input_norm")(x)
        if self.latent:
            h = GatedLatentAttention(cfg, name="attn")(h, positions, decode, fresh_prefill,
                                                       cache_write_mask, block_tables, cache_write_len)
        else:
            h = KimiDeltaAttention(cfg, name="kda")(h, decode, fresh_prefill, cache_write_mask,
                                                    cache_write_len)
        x = x + h
        h = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="post_norm")(x)
        ffn = DenseMLP(cfg, name="mlp") if self.dense else GroupLimitedMoE(cfg, name="moe")
        return x + ffn(h)


class Ling3ForCausalLM(nn.Module):
    """Decoder-only LM. Returns logits [batch, seq, vocab] in float32."""

    config: Ling3Config

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
            x = Ling3Block(cfg, cfg.is_latent(i), cfg.is_dense(i), name=f"layer_{i}")(
                x, positions, decode, fresh_prefill, cache_write_mask, block_tables, cache_write_len)
        x = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="final_norm")(x)
        if return_hidden:
            return x
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype)
        return jnp.matmul(x, head.astype(cfg.dtype), preferred_element_type=jnp.float32)

    def init_params(self, rng: jax.Array, batch: int = 1, seq: int = 8) -> Any:
        return self.init(rng, jnp.zeros((batch, seq), jnp.int32))["params"]


def ling3_sharding_rules() -> ShardingRules:
    """Expert parallelism as sharding annotations and nothing more: the
    expert-stacked weights split their leading dim over ``tensor``. The
    serving engine does not serve this model on a mesh (per-slot recurrent
    state has no mesh layout, a latent row cannot split by head, and the
    experts' exchange is not written); the rules are for `prepare`."""
    return ShardingRules(rules=[
        (r".*moe/w_gate_up", P("tensor", None, None)),
        (r".*moe/w_down", P("tensor", None, None)),
    ])
