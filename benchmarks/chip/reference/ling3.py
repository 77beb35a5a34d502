"""Ling 3.0 flash (`inclusionAI/Ling-3.0-flash-VL`, the language model) in
plain `jax.numpy` and float32: five Kimi-Delta-Attention layers to one
latent-attention layer, two leading dense layers, then layers of experts
chosen by a group-limited sigmoid router beside an ungated shared expert. The
forward pass only; no cache, no kernel, no chunks and no WY form: the delta
rule is the token-by-token recurrence under one `lax.scan`, latent attention
the PLAIN (un-absorbed) form at every position, the experts are looped over.
It imports nothing from the program. Matmuls run at `highest` precision: on a
TPU a float32 product is otherwise computed in bf16 passes.

The equations (config.json of inclusionAI/Ling-3.0-flash-VL; Kimi Linear,
arXiv:2510.26692, for the KDA layer; DeepSeek-V3's layer for MLA and router):

  N(x)  = x / rms(x) * w                                  eps 1e-6, w starts at 1
  block l: h = x + Mixer_l(N1(x));  y = h + FFN_l(N2(h));  a final N, the untied head
  layer l mixes by latent attention where (l + 1) % layer_group_size == 0, by KDA
  otherwise; FFN_l is a SwiGLU MLP 6,144 wide for l < first_k_dense_replace.
  KDA, H heads, d_k = d_v = 128, float32 S [d_k, d_v] a head:
    q = L2(silu(conv(W_q x))) * d_k^-1/2;  k = L2(silu(conv(W_k x)));  v = silu(conv(W_v x))
    a = W_f x + dt_bias [H, d_k];  g = kda_lower_bound * sigmoid(exp(A_log_h) * a)  in (-5, 0)
    beta = sigmoid(W_b x) [H]
    S <- Diag(exp(g)) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q
    Mixer(x) = W_o [ N_{d_v}(o) * sigmoid(W_z x) ]
    conv: causal, depthwise, short_conv_kernel_size taps; L2 per head, eps 1e-6
  latent attention, H heads:
    q = W_q x                       per head [q_nope (128) | q_pe (64)]
    [c | k_pe] = W_kva x            c~ = N(c) (512); k_pe (64) one vector a token
    [k_nope_h | v_h] = W_kvb,h c~   (128 | 128)
    q_pe, k_pe <- RoPE              rotate-half over the 64 dims, theta 6e6, no scaling
    score_h(t, s) = 192^-1/2 (q_nope_h(t) . k_nope_h(s) + q_pe_h(t) . k_pe(s)), causal, softmax
    out = W_o concat_h(sigmoid(w_h . x) * sum_s p_h(t, s) v_h(s))       a gate a head
  experts: s = sigmoid(W_g x) over all routed experts, in n_group groups; a
    group's score is the sum of its two largest s + b; the topk_group best
    groups are kept; the k largest s + b inside them are chosen (b chooses,
    never weighs); w_e = routed_scaling_factor * s_e / (sum of the chosen s +
    1e-20); FFN(x) = sum_e w_e E_e(x) + E_shared(x).

Departures, each also in the configuration's file: no vision tower, no
multi-token prediction module; `[q | k | v | z]` and `[f | b]` are plain
concatenations of the projections' columns; rotary pairs are halves; an expert
layer may be given a *share*: `held = (first, count)` names the routed experts
whose weights it was handed, the router stays as wide as published, and what
the absent experts would add is left out. The vocabulary may be a slice.

Parameters of one layer (a dict; `kind` is "kda" or "latent"):
  norm1 norm2 [H]
  kda:    wqkvz [H, 4 n] (n = heads * 128); wfb [H, n + heads]; conv_w [K, 3 n];
          A_log [heads]; dt_bias [n]; out_norm [128]; wout [n, H]
  latent: wq [H, heads * 192]; wkva [H, 576]; kva_norm [512]; wkvb [512, heads *
          256] (a head's 128 key dims, then its 128 value dims); whg [H, heads];
          wo [heads * 128, H]
  dense:  wg wu [H, F]; wd [F, H]
  expert: router [H, E]; bias [E]; wg wu [E_held, H, f]; wd [E_held, f, H];
          s_wg s_wu [H, fs]; s_wd [fs, H]
Top level: embed [V, H]; final_norm [H]; head [H, V].

`low` turns a layer into a control that a sound program must be told from.
"int8": every product with a weight matrix (projections, router, experts,
head) takes both operands through vector-wise absmax int8, the precision next
below the stated bfloat16 compute. "decay_mean": the per-channel log decay g
replaced by its mean over a head's channels (the scalar-decay rule of the
sibling model in this one's place). "no_group_limit": the plain top-k of all
experts' s + b. "state_bf16": S kept in bfloat16 between tokens."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the weight product in a control's precision: the sibling reference's, plain
# `jax.numpy` like everything here
from .qwen3_next import HIGHEST, linear, silu

HEADS_AT_ONCE = 8  # float32 scores of 8 heads over 4,608 x 4,608 positions are 0.7 GB
L2_EPS = 1e-6


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_kind(i: int, cfg: dict) -> str:
    return "latent" if (i + 1) % int(cfg["layer_group_size"]) == 0 else "kda"


def is_dense(i: int, cfg: dict) -> bool:
    return i < int(cfg["first_k_dense_replace"])


# ---------------------------------------------------- Kimi Delta Attention
def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda_gate(p, fb, cfg, low=None):
    """(g [B, T, heads, d], beta [B, T, heads]) from the [f | b] projection."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    n = heads * d
    a = (fb[..., :n] + p["dt_bias"]).reshape(fb.shape[:-1] + (heads, d))
    g = float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * a)
    if low == "decay_mean":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    return g, jax.nn.sigmoid(fb[..., n:])


def kimi_delta_attention(p, x, cfg, low=None):
    b, t, _ = x.shape
    heads, d, width = cfg["num_attention_heads"], cfg["head_dim"], int(cfg["short_conv_kernel_size"])
    n = heads * d
    qkvz = linear(x, p["wqkvz"], low)
    g, beta = kda_gate(p, linear(x, p["wfb"], low), cfg, low)
    qkv, z = qkvz[..., : 3 * n], qkvz[..., 3 * n:].reshape(b, t, heads, d)
    # causal depthwise convolution, then SiLU: channel c at token t sums
    # conv_w[j, c] * qkv[t - (width - 1) + j, c]
    padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
    qkv = silu(sum(padded[:, j: j + t] * p["conv_w"][j] for j in range(width)))
    q, k, v = (qkv[..., i * n: (i + 1) * n].reshape(b, t, heads, d) for i in range(3))
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    keep = (lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)) if low == "state_bf16" else (lambda s: s)

    def token(S, xs):  # S [B, heads, d_k, d_v]
        q_t, k_t, v_t, g_t, beta_t = xs
        S = S * jnp.exp(g_t)[..., None]  # a decay a key channel: the rows of S
        delta = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=HIGHEST))
        S = keep(S + k_t[..., :, None] * delta[..., None, :])
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=HIGHEST)

    S0 = jnp.zeros((b, heads, d, d), jnp.float32)
    _, o = jax.lax.scan(token, S0, tuple(jnp.moveaxis(y, 1, 0) for y in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)  # [B, T, heads, d]
    o = norm(o, p["out_norm"], float(cfg["rms_norm_eps"])) * jax.nn.sigmoid(z)
    return linear(o.reshape(b, t, n), p["wout"], low)


# ---------------------------------------------------------- latent attention
def rotary(x, theta: float):
    """Rotate-half over every dim of each head; x is [B, T, heads, d], token t
    at position t."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    return x * cos + jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1) * sin


def latent_attention(p, x, cfg, low=None):
    """The plain form over the whole sequence, a few heads at a time."""
    b, t, _ = x.shape
    heads, eps, theta = cfg["num_attention_heads"], float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    nope, rope, dv, rank = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                            cfg["kv_lora_rank"])
    q = linear(x, p["wq"], low).reshape(b, t, heads, nope + rope)
    q_nope, q_pe = q[..., :nope], rotary(q[..., nope:], theta)
    ckv = linear(x, p["wkva"], low)
    c = norm(ckv[..., :rank], p["kva_norm"], eps)
    k_pe = rotary(ckv[..., None, rank:], theta)  # [B, T, 1, rope]: shared by the heads
    kv = linear(c, p["wkvb"], low).reshape(b, t, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    gate = jax.nn.sigmoid(linear(x, p["whg"], low))  # [B, T, heads]
    causal = jnp.tril(jnp.ones((t, t), bool))
    outs = []
    for at in range(0, heads, HEADS_AT_ONCE):
        hs = slice(at, at + HEADS_AT_ONCE)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, :, hs], k_nope[:, :, hs], precision=HIGHEST)
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe[:, :, hs], k_pe[:, :, 0], precision=HIGHEST))
        weights = jax.nn.softmax(jnp.where(causal, scores * (nope + rope) ** -0.5, -jnp.inf), -1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", weights, v[:, :, hs], precision=HIGHEST))
    out = jnp.concatenate(outs, 2) * gate[..., None]
    return linear(out.reshape(b, t, heads * dv), p["wo"], low)


# ------------------------------------------------------------------ the FFNs
def swiglu(x, wg, wu, wd, low=None):
    return linear(silu(linear(x, wg, low)) * linear(x, wu, low), wd, low)


def route(p, x, cfg, low=None):
    """(weights [..., k], ids [..., k]) of the sigmoid router: the k largest
    s + b inside the `topk_group` groups whose two largest s + b sum highest."""
    scores = jax.nn.sigmoid(linear(x, p["router"], low))
    choose = scores + p["bias"]
    groups, kept = int(cfg["n_group"]), int(cfg["topk_group"])
    if groups > 1 and low != "no_group_limit":
        grouped = choose.reshape(choose.shape[:-1] + (groups, -1))
        group_score = jnp.sort(grouped, -1)[..., -2:].sum(-1)
        floor = jnp.sort(group_score, -1)[..., -kept][..., None]  # the kept-th best group's score
        choose = jnp.where((group_score >= floor)[..., None], grouped, -jnp.inf).reshape(choose.shape)
    _, idx = jax.lax.top_k(choose, int(cfg["num_experts_per_tok"]))
    top = jnp.take_along_axis(scores, idx, -1)
    return float(cfg["routed_scaling_factor"]) * top / (top.sum(-1, keepdims=True) + 1e-20), idx


def moe(p, x, cfg, held=None, shared=True, low=None):
    """The routed experts `held = (first, count)` hold, out of the
    `p["router"].shape[1]` the router scores, plus the shared expert. A pick
    that falls on an absent expert adds nothing."""
    first, count = held if held is not None else (0, p["router"].shape[1])
    top, idx = route(p, x, cfg, low)

    def expert(acc, xs):
        e, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1, keepdims=True)  # 0 unless chosen
        return acc + weight * swiglu(x, wg, wu, wd, low), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), (first + jnp.arange(count), p["wg"], p["wu"], p["wd"]))
    if shared:
        out = out + swiglu(x, p["s_wg"], p["s_wu"], p["s_wd"], low)
    return out


# ---------------------------------------------------------------------- model
def mix(p, x, cfg, kind: str, low=None):
    """The block's first half: h = x + Mixer(N1(x))."""
    h = norm(x, p["norm1"], float(cfg["rms_norm_eps"]))
    return x + (latent_attention(p, h, cfg, low) if kind == "latent" else kimi_delta_attention(p, h, cfg, low))


def ffn(p, h, cfg, dense: bool, held=None, low=None):
    """What the block's second half adds to h: FFN(N2(h)). Per token, so a
    caller may hand it any set of tokens [..., H]."""
    x = norm(h, p["norm2"], float(cfg["rms_norm_eps"]))
    return swiglu(x, p["wg"], p["wu"], p["wd"], low) if dense else moe(p, x, cfg, held=held, low=low)


def layer(p, x, cfg, kind: str, dense: bool, held=None, low=None):
    h = mix(p, x, cfg, kind, low=low)
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
        x = layer(p, x, cfg, layer_kind(i, cfg), is_dense(i, cfg), held=held, low=low)
    return linear(norm(x, params["top"]["final_norm"], float(cfg["rms_norm_eps"])),
                  params["top"]["head"], low)
