"""Qwen3-Next (`model_type: qwen3_next`) in plain `jax.numpy` and float32:
three gated-DeltaNet linear-attention layers, then one gated softmax-attention
layer, each followed by a routed-expert MLP with a shared expert. The forward
pass only; no cache, no kernel, no chunking: the delta rule is a token-by-token
`lax.scan`, the experts are looped over. It imports nothing from the program.
Matmuls run at `highest` precision: on a TPU a float32 product is otherwise
computed in bf16 passes.

The equations (config.json of Qwen/Qwen3-Next-80B-A3B-Instruct; the layer
code of transformers' `modeling_qwen3_next.py`):

  N(x)  = x / rms(x) * (1 + w)                     zero-centred weight, eps 1e-6
  block : h = x + Mixer(N1(x));  y = h + MoE(N2(h))
  layer i mixes by attention where (i + 1) % full_attention_interval == 0,
  by DeltaNet otherwise. A final N, then the untied head.

Departures, each also in the configuration's file: the multi-token prediction
module is left out; `[q | k | v | z]` and `[b | a]` are plain concatenations
of the fused projections' columns (the release interleaves them per key head:
with random weights a relabelling); an expert layer may be given a *share*:
`held = (first, count)` names the routed experts whose weights it was handed,
the router stays as wide as published, and what the absent experts would add
is left out. The vocabulary may be a slice: the embedding's and the head's
rows are what they are given.

Parameters of one layer (a dict; `kind` is "linear" or "full"):
  norm1 norm2 [H]; router [H, E]; wg wu [E_held, H, F]; wd [E_held, F, H];
  s_gate [H]; s_wg s_wu [H, Fs]; s_wd [Fs, H]
  full:   wq [H, Hq * 2 * D]; wk wv [H, Hkv * D]; q_norm k_norm [D]; wo [Hq * D, H]
  linear: wqkvz [H, 2 * Hk * Dk + 2 * Hv * Dv]; wba [H, 2 * Hv]; conv_w [K, C]
          with C = 2 * Hk * Dk + Hv * Dv; A_log dt_bias [Hv]; out_norm [Dv];
          wout [Hv * Dv, H]
Top level: embed [V, H]; final_norm [H]; head [H, V].

`low` turns a layer into a lower-precision control. "int8": every product
with a weight matrix (projections, router, experts, head) takes both operands
through vector-wise absmax int8, the kindest int8 scheme in use (LLM.int8);
"fp8": through per-tensor scaled float8 e4m3: the precisions next below the
bfloat16 compute the configuration states. "state_bf16" keeps the DeltaNet
matrix S in bfloat16 between tokens, "router_bf16" computes the router's logits
and softmax in bfloat16: the two places the configuration states float32."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _fp8(x):
    """Through float8 e4m3 and back, the tensor scaled to the format's range."""
    scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def linear(x, w, low=None):
    """x [..., K] times a weight matrix w [K, N], in the control's precision."""
    if low == "int8":
        return _mm(_int8(x, -1), _int8(w, 0))
    if low == "fp8":
        return _mm(_fp8(x), _fp8(w))
    return _mm(x, w)


def norm(x, w):
    """Zero-centred RMSNorm over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * (1.0 + w)


def silu(x):
    return x * jax.nn.sigmoid(x)


def layer_kind(i: int, cfg: dict) -> str:
    return "full" if (i + 1) % int(cfg["full_attention_interval"]) == 0 else "linear"


# ------------------------------------------------------------ gated attention
def rotary(x, theta: float, rot: int):
    """Rotate-half on the first `rot` dims of each head; x is [B, T, heads, D]."""
    t = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., : rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], -1)


def gated_attention(p, x, cfg, low=None):
    b, t, _ = x.shape
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    qg = linear(x, p["wq"], low).reshape(b, t, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = linear(x, p["wk"], low).reshape(b, t, hkv, d)
    v = linear(x, p["wv"], low).reshape(b, t, hkv, d)
    q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
    rot = int(d * cfg["partial_rotary_factor"])
    q, k = rotary(q, float(cfg["rope_theta"]), rot), rotary(k, float(cfg["rope_theta"]), rot)
    k, v = jnp.repeat(k, hq // hkv, 2), jnp.repeat(v, hq // hkv, 2)  # head j reads j // groups
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(float(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v, precision=HIGHEST)
    out = out * jax.nn.sigmoid(gate)
    return linear(out.reshape(b, t, hq * d), p["wo"], low)


# ------------------------------------------------------------- gated DeltaNet
def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + EPS)


def gated_deltanet(p, x, cfg, low=None):
    b, t, _ = x.shape
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    width = int(cfg["linear_conv_kernel_dim"])
    qkvz = linear(x, p["wqkvz"], low)
    ba = linear(x, p["wba"], low)
    n_qkv = 2 * hk * dk + hv * dv
    qkv, z = qkvz[..., :n_qkv], qkvz[..., n_qkv:].reshape(b, t, hv, dv)
    beta, a = jax.nn.sigmoid(ba[..., :hv]), ba[..., hv:]
    # causal depthwise convolution of width `width`, then SiLU: channel c at
    # token t sums conv_w[j, c] * qkv[t - (width - 1) + j, c]
    padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
    qkv = silu(sum(padded[:, j: j + t] * p["conv_w"][j] for j in range(width)))
    q = qkv[..., : hk * dk].reshape(b, t, hk, dk)
    k = qkv[..., hk * dk: 2 * hk * dk].reshape(b, t, hk, dk)
    v = qkv[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])  # [B, T, Hv], log decay
    q, k = l2norm(q) * dk ** -0.5, l2norm(k)
    q, k = jnp.repeat(q, hv // hk, 2), jnp.repeat(k, hv // hk, 2)  # value head j reads j // 2
    keep = (lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)) if low == "state_bf16" else (lambda s: s)

    def token(S, xs):  # S [B, Hv, Dk, Dv]
        q_t, k_t, v_t, g_t, beta_t = xs
        S = S * jnp.exp(g_t)[..., None, None]
        d = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=HIGHEST))
        S = keep(S + k_t[..., :, None] * d[..., None, :])
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=HIGHEST)

    S0 = jnp.zeros((b, hv, dk, dv), jnp.float32)
    _, o = jax.lax.scan(token, S0, tuple(jnp.moveaxis(y, 1, 0) for y in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)  # [B, T, Hv, Dv]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + EPS) * p["out_norm"] * silu(z)
    return linear(o.reshape(b, t, hv * dv), p["wout"], low)


# ------------------------------------------------------------------------ MoE
def moe(p, x, cfg, held=None, shared=True, low=None):
    """The routed experts `held = (first, count)` hold, out of the
    `p["router"].shape[1]` the router scores, plus the shared expert. The
    top-k is over all of them and renormalised over the k; a pick that falls
    on an absent expert adds nothing."""
    n_routed = p["router"].shape[1]
    first, count = held if held is not None else (0, n_routed)
    k = int(cfg["num_experts_per_tok"])
    if low == "router_bf16":
        logits = jnp.matmul(x.astype(jnp.bfloat16), p["router"].astype(jnp.bfloat16))
        probs = jax.nn.softmax(logits, -1).astype(jnp.float32)
    else:
        probs = jax.nn.softmax(linear(x, p["router"], low), -1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / top.sum(-1, keepdims=True)

    def expert(acc, xs):
        e, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(idx == e, top, 0.0), -1, keepdims=True)  # 0 unless chosen
        return acc + weight * linear(silu(linear(x, wg, low)) * linear(x, wu, low), wd, low), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (first + jnp.arange(count), p["wg"], p["wu"], p["wd"]))
    if shared:
        gate = jax.nn.sigmoid(jnp.sum(x * p["s_gate"], -1, keepdims=True))
        out = out + gate * linear(silu(linear(x, p["s_wg"], low)) * linear(x, p["s_wu"], low), p["s_wd"], low)
    return out


# ---------------------------------------------------------------------- model
def mix(p, x, cfg, kind: str, low=None):
    """The block's first half: h = x + Mixer(N1(x))."""
    mixer = gated_attention(p, norm(x, p["norm1"]), cfg, low=low) if kind == "full" else \
        gated_deltanet(p, norm(x, p["norm1"]), cfg, low=low)
    return x + mixer


def experts(p, h, cfg, held=None, low=None):
    """What the block's second half adds to h: MoE(N2(h)). Per token, so a
    caller may hand it any set of tokens [..., H]."""
    return moe(p, norm(h, p["norm2"]), cfg, held=held, low=low)


def layer(p, x, cfg, kind: str, held=None, low=None):
    h = mix(p, x, cfg, kind, low=low)
    return h + experts(p, h, cfg, held=held, low=low)


def embed(top, ids):
    return top["embed"][ids]


def head_logits(top, x, positions, low=None):
    """Logits [B, n, V] at `positions` [B, n] of the final hidden states x."""
    rows = jnp.take_along_axis(x, positions[..., None], axis=1)
    return linear(norm(rows, top["final_norm"]), top["head"], low)


def forward(params, ids, cfg, held=None, low=None):
    """Logits [B, T, V] of the whole model; `params = {"top": ..., "layers": [...]}`.
    For the unit tests: at published widths the driver walks layer by layer."""
    x = embed(params["top"], ids)
    for i, p in enumerate(params["layers"]):
        x = layer(p, x, cfg, layer_kind(i, cfg), held=held, low=low)
    return linear(norm(x, params["top"]["final_norm"]), params["top"]["head"], low)
