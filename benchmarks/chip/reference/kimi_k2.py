"""Kimi K2 (`model_type: kimi_k2`) in plain `jax.numpy` and float32: latent
attention in the PLAIN (un-absorbed) form for every position, a leading dense
layer, then layers of sigmoid-routed experts beside an ungated shared expert.
The forward pass only; no cache, no kernel, no absorbed form: keys and values
are built for every head from the latent, the experts are looped over in
Python. It imports nothing from the program. Matmuls run at `highest`
precision: on a TPU a float32 product is otherwise computed in bf16 passes.

The equations (config.json of moonshotai/Kimi-K2.7-Code; the family's
published modeling code, DeepSeek-V3's layer):

  N(x)  = x / rms(x) * w                                  eps 1e-5, w starts at 1
  block : h = x + Attn(N1(x));  y = h + FFN(N2(h));  a final N, the untied head
  FFN of layer l < first_k_dense_replace: down(silu(gate x) * up x), width 18,432
  attention, H heads:
    q = W_qb N(W_qa x)              per head [q_nope (128) | q_pe (64)]
    [c | k_pe] = W_kva x            c~ = N(c) (512); k_pe (64) one vector a token
    [k_nope_h | v_h] = W_kvb,h c~   (128 | 128)
    q_pe, k_pe <- RoPE              rotate-half over 64 dims, YaRN frequencies
    score_h(t, s) = scale * (q_nope_h(t) . k_nope_h(s) + q_pe_h(t) . k_pe(s)),
    causal, softmax;  out = W_o concat_h(sum_s p_h(t, s) v_h(s))
  YaRN over d = 64: f_i = theta^(-2i/d); corr(r) = d ln(L0 / (2 pi r)) / (2 ln
    theta); low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)) in [0, d -
    1]; ramp_i = clip((i - low) / (high - low), 0, 1); inv_freq_i = f_i / factor
    * ramp_i + f_i (1 - ramp_i); m(s, a) = 0.1 a ln s + 1; cos, sin times
    m(factor, mscale) / m(factor, mscale_all_dim); scale = 192^(-1/2) m(factor,
    mscale_all_dim)^2
  experts: s = sigmoid(W_g x) over all routed experts; the k with the largest
    s + b chosen (b chooses, never weighs); w_e = routed_scaling_factor * s_e /
    (sum of the chosen s + 1e-20); FFN(x) = sum_e w_e E_e(x) + E_shared(x).

Departures, each also in the configuration's file: no vision tower, no
multi-token prediction module; rotary pairs are halves, not interleaved (the
release permutes before rotating: with random weights a relabelling); an
expert layer may be given a *share*: `held = (first, count)` names the routed
experts whose weights it was handed, the router stays as wide as published,
and what the absent experts would add is left out. The vocabulary may be a
slice.

Parameters of one layer (a dict):
  norm1 norm2 [H]; wqa [H, Lq]; qa_norm [Lq]; wqb [Lq, heads * 192]; wkva [H, 576];
  kva_norm [512]; wkvb [512, heads * 256] (a head's 128 key dims, then its 128
  value dims); wo [heads * 128, H]
  dense:  wg wu [H, F]; wd [F, H]
  expert: router [H, E]; bias [E]; wg wu [E_held, H, f]; wd [E_held, f, H];
          s_wg s_wu [H, fs]; s_wd [fs, H]
Top level: embed [V, H]; final_norm [H]; head [H, V].

`low` turns a layer into a lower-precision control. "int8": every product
with a weight matrix (projections, router, experts, head) takes both operands
through vector-wise absmax int8; "fp8": through per-tensor scaled float8 e4m3:
the precisions next below the bfloat16 compute the configuration states.
"latent_fp8" rounds what a cache would hold, c~ and the rotated k_pe, through
float8 e4m3: the precision next below the bfloat16 latent pool."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# the weight product in a control's precision and its roundings: the sibling
# reference's, plain `jax.numpy` like everything here
from .qwen3_next import HIGHEST, _fp8, linear, silu

HEADS_AT_ONCE = 8  # float32 scores of 8 heads over 4,608 x 4,608 positions are 0.7 GB


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def is_dense(i: int, cfg: dict) -> bool:
    return i < int(cfg["first_k_dense_replace"])


# ------------------------------------------------------------------- rotary
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict):
    d, theta, rope = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"]), cfg["rope_scaling"]

    def corr(rotations):
        return d * math.log(rope["original_max_position_embeddings"] / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), d - 1)
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1)
    return f / rope["factor"] * ramp + f * (1 - ramp)


def softmax_scale(cfg: dict) -> float:
    rope = cfg["rope_scaling"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return qk ** -0.5 * yarn_mscale(rope["factor"], rope["mscale_all_dim"]) ** 2


def rotary(x, cfg: dict):
    """Rotate-half over every dim of each head; x is [B, T, heads, d], token t
    at position t."""
    rope = cfg["rope_scaling"]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]
    m = yarn_mscale(rope["factor"], rope["mscale"]) / yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :] * m
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :] * m
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


# ---------------------------------------------------------------- attention
def latent_attention(p, x, cfg, low=None):
    """The plain form over the whole sequence, a few heads at a time."""
    b, t, _ = x.shape
    heads, eps = cfg["num_attention_heads"], float(cfg["rms_norm_eps"])
    nope, rope, dv, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                            cfg["kv_lora_rank"])
    q = linear(norm(linear(x, p["wqa"], low), p["qa_norm"], eps), p["wqb"], low)
    q = q.reshape(b, t, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], rotary(q[..., nope:], cfg)
    ckv = linear(x, p["wkva"], low)
    c = norm(ckv[..., :rank], p["kva_norm"], eps)
    k_pe = rotary(ckv[..., None, rank:], cfg)  # [B, T, 1, rope]: shared by the heads
    if low == "latent_fp8":
        c, k_pe = _fp8(c), _fp8(k_pe)
    kv = linear(c, p["wkvb"], low).reshape(b, t, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))
    outs = []
    for at in range(0, heads, HEADS_AT_ONCE):
        hs = slice(at, at + HEADS_AT_ONCE)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, :, hs], k_nope[:, :, hs], precision=HIGHEST)
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe[:, :, hs], k_pe[:, :, 0], precision=HIGHEST))
        weights = jax.nn.softmax(jnp.where(causal, scores * softmax_scale(cfg), -jnp.inf), -1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", weights, v[:, :, hs], precision=HIGHEST))
    out = jnp.concatenate(outs, 2)
    return linear(out.reshape(b, t, heads * dv), p["wo"], low)


# ------------------------------------------------------------------ the FFNs
def swiglu(x, wg, wu, wd, low=None):
    return linear(silu(linear(x, wg, low)) * linear(x, wu, low), wd, low)


def route(p, x, cfg, low=None):
    """(weights [..., k], ids [..., k]) of the sigmoid router under its bias."""
    scores = jax.nn.sigmoid(linear(x, p["router"], low))
    _, idx = jax.lax.top_k(scores + p["bias"], int(cfg["num_experts_per_tok"]))
    top = jnp.take_along_axis(scores, idx, -1)
    return float(cfg["routed_scaling_factor"]) * top / (top.sum(-1, keepdims=True) + 1e-20), idx


def moe(p, x, cfg, held=None, shared=True, low=None):
    """The routed experts `held = (first, count)` hold, out of the
    `p["router"].shape[1]` the router scores, plus the shared expert. A pick
    that falls on an absent expert adds nothing."""
    first, count = held if held is not None else (0, p["router"].shape[1])
    top, idx = route(p, x, cfg, low)
    out = jnp.zeros_like(x)
    for e in range(count):
        weight = jnp.sum(jnp.where(idx == first + e, top, 0.0), -1, keepdims=True)  # 0 unless chosen
        out = out + weight * swiglu(x, p["wg"][e], p["wu"][e], p["wd"][e], low)
    if shared:
        out = out + swiglu(x, p["s_wg"], p["s_wu"], p["s_wd"], low)
    return out


# ---------------------------------------------------------------------- model
def mix(p, x, cfg, low=None):
    """The block's first half: h = x + Attn(N1(x))."""
    return x + latent_attention(p, norm(x, p["norm1"], float(cfg["rms_norm_eps"])), cfg, low=low)


def ffn(p, h, cfg, dense: bool, held=None, low=None):
    """What the block's second half adds to h: FFN(N2(h)). Per token, so a
    caller may hand it any set of tokens [..., H]."""
    x = norm(h, p["norm2"], float(cfg["rms_norm_eps"]))
    return swiglu(x, p["wg"], p["wu"], p["wd"], low) if dense else moe(p, x, cfg, held=held, low=low)


def layer(p, x, cfg, dense: bool, held=None, low=None):
    h = mix(p, x, cfg, low=low)
    return h + ffn(p, h, cfg, dense, held=held, low=low)


def embed(top, ids):
    return top["embed"][ids]


def head_logits(top, x, positions, cfg, low=None):
    """Logits [B, n, V] at `positions` [B, n] of the final hidden states x."""
    rows = jnp.take_along_axis(x, positions[..., None], axis=1)
    return linear(norm(rows, top["final_norm"], float(cfg["rms_norm_eps"])), top["head"], low)


def forward(params, ids, cfg, held=None, low=None):
    """Logits [B, T, V] of the whole model; `params = {"top": ..., "layers": [...]}`.
    For the unit tests: at published widths the driver walks layer by layer."""
    x = embed(params["top"], ids)
    for i, p in enumerate(params["layers"]):
        x = layer(p, x, cfg, is_dense(i, cfg), held=held, low=low)
    return linear(norm(x, params["top"]["final_norm"], float(cfg["rms_norm_eps"])),
                  params["top"]["head"], low)
