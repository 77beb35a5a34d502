"""MiMo-V2 (`XiaomiMiMo/MiMo-V2.5`, `model_type: mimo_v2`; the MiMo-V2-Flash
309B-A15B family) in plain `jax.numpy` and float32: five sliding-window layers
with attention sinks to one full-attention layer, head counts that differ by
kind, keys 192 wide beside values 128 wide, a leading dense layer, then layers
of sigmoid-routed experts with no shared expert. The forward pass only; no
cache, no kernel, no ring, no batching of requests: attention is computed for
every query against every key it may see under explicit causal and window
masks, a block of queries at a time, with the sink as its own term of the
softmax's sum; the experts are looped over. It imports nothing from the
program. Matmuls run at `highest` precision: on a TPU a float32 product is
otherwise computed in bf16 passes.

The equations (config.json of XiaomiMiMo/MiMo-V2.5; the layer is written
down from the configuration's keys, as the configuration's `assumed` says):

  N(x)  = x / rms(x) * w                                  eps 1e-5, w starts at 1
  block l of kind t (S: hybrid_layer_pattern[l] == 1, G: == 0), pre-norm:
    x = N_1(h)
    q = RoPE(W_q x), k = RoPE(W_k x), v = c_v W_v x      c_v = attention_value_scale
          RoPE: rotate-half over the first 64 of each head's 192 dims, theta
          swa_rope_theta (1e4) on S, rope_theta (1e7) on G
    s_ij = q_h(i) . k_g(j) / sqrt(192)          g = h // (64 / kv heads): 8 on S, 4 on G
    on S: j in (i - 128, i], p_ij = exp(s_ij) / (exp(b_h) + sum_j exp(s_ij))
    on G: j <= i,            p_ij = exp(s_ij) / sum_j exp(s_ij)
    h <- h + W_o concat_h(sum_j p_ij v_g(j))    each v 128 wide
    h <- h + FFN_l(N_2(h))
  a final N, the untied head.
  FFN of layer 0 (moe_layer_freq 0): down(silu(gate x) * up x), width 16,384.
  experts: s = sigmoid(W_g x) over all routed experts; the 8 with the largest
    s + b chosen (b chooses, never weighs); w_e = s_e / (sum of the chosen s +
    1e-20) (routed_scaling_factor null: 1); FFN(x) = sum_e w_e E_e(x), each E
    a SwiGLU MLP of width 2,048, no shared expert (`reference/kimi_k2.py`'s
    `moe` with `shared=False`).

Departures, each also in the configuration's file: no multi-token prediction
layers, no vision or audio tower; an expert layer may be given a *share*:
`held = (first, count)` names the routed experts whose weights it was handed,
the router stays as wide as published, and what the absent experts would add
is left out. The vocabulary may be a slice.

Parameters of one layer (a dict):
  norm1 norm2 [H]; wq [H, 64 * 192]; wk [H, hkv * 192]; wv [H, hkv * 128];
  wo [64 * 128, H]; on S also sinks [64]
  dense:  wg wu [H, F]; wd [F, H]
  expert: router [H, E]; bias [E]; wg wu [E_held, H, f]; wd [E_held, f, H]
Top level: embed [V, H]; final_norm [H]; head [H, V].

`low` turns a layer into a control that a sound program must be told from.
"int8": every product with a weight matrix (projections, router, experts,
head) takes both operands through vector-wise absmax int8, the precision next
below the stated bfloat16 compute. "no_window": the sliding layers attend the
whole causal context (the window removed). "no_sinks": the sliding layers'
softmax without its sink term."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the expert layer, the norm and the embedding: the Kimi K2 reference's
# (embed: `drivers/serve_mimo_v2.py` takes it here)
from .kimi_k2 import embed, moe, norm, swiglu  # noqa: F401
from .qwen3_next import HIGHEST, linear, rotary

QUERY_BLOCK = 128  # float32 scores of 64 heads, 128 queries x 16,384 keys: 0.5 GB


def is_sliding(i: int, cfg: dict) -> bool:
    return int(cfg["hybrid_layer_pattern"][i]) == 1


def is_dense(i: int, cfg: dict) -> bool:
    return int(cfg["moe_layer_freq"][i]) == 0


def heads(cfg: dict, sliding: bool) -> tuple[int, int, int, int]:
    """(query heads, key/value heads, key size, value size) of a kind."""
    if sliding:
        return (cfg["swa_num_attention_heads"], cfg["swa_num_key_value_heads"], cfg["swa_head_dim"],
                cfg["swa_v_head_dim"])
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["v_head_dim"]


def rotary_dim(cfg: dict) -> int:
    return int(cfg["head_dim"] * float(cfg["partial_rotary_factor"]))


def attend(q, k, v, window=None, sinks=None):
    """Causal grouped-query attention of q [B, T, hq, d] over k [B, T, hkv, d]
    and v [B, T, hkv, dv], query block by query block, under the masks s <= t
    and, with a window, t - window < s. With `sinks` [hq] each query's
    softmax divides by exp(sink) besides the sum of its keys' exp(score)."""
    b, t, hq, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    n = min(QUERY_BLOCK, t)
    whole = -(-t // n) * n  # keys past t lie after every real query: masked below
    q, k, v = (jnp.pad(x, ((0, 0), (0, whole - t), (0, 0), (0, 0))) for x in (q, k, v))
    span = whole if window is None else n + window - 1
    if window is not None:  # keys before position 0 are padding, masked below
        k, v = (jnp.pad(x, ((0, 0), (window - 1, 0), (0, 0), (0, 0))) for x in (k, v))
    sink = None if sinks is None else sinks.reshape(1, hkv, hq // hkv, 1, 1)

    def block(at):
        qb = jax.lax.dynamic_slice_in_dim(q, at, n, 1).reshape(b, n, hkv, hq // hkv, d)
        first = 0 if window is None else at  # padded index of key position at - (window - 1)
        kb, vb = (jax.lax.dynamic_slice_in_dim(x, first, span, 1) for x in (k, v))
        q_pos = at + jnp.arange(n)[:, None]
        k_pos = (first if window is None else at - (window - 1)) + jnp.arange(span)[None, :]
        keep = (k_pos <= q_pos) & (k_pos >= 0)
        if window is not None:
            keep &= k_pos > q_pos - window
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb, precision=HIGHEST) * d ** -0.5
        scores = jnp.where(keep, scores, -jnp.inf)
        top = scores.max(-1, keepdims=True)
        if sink is not None:
            top = jnp.maximum(top, sink)
        e = jnp.exp(scores - top)
        total = e.sum(-1, keepdims=True) + (0.0 if sink is None else jnp.exp(sink - top))
        out = jnp.einsum("bhgqk,bkhd->bqhgd", e / total, vb, precision=HIGHEST)
        return out.reshape(b, n, hq, dv)

    out = jax.lax.map(block, jnp.arange(0, whole, n))  # [whole / n, B, n, hq, dv]
    return jnp.moveaxis(out, 0, 1).reshape(b, whole, hq, dv)[:, :t]


def attention(p, x, cfg, sliding: bool, low=None):
    """W_o Attn(q, k, v) of the normed stream x (the block's docstring above)."""
    b, t, _ = x.shape
    hq, hkv, d, dv = heads(cfg, sliding)
    theta = float(cfg["swa_rope_theta"] if sliding else cfg["rope_theta"])
    q = rotary(linear(x, p["wq"], low).reshape(b, t, hq, d), theta, rotary_dim(cfg))
    k = rotary(linear(x, p["wk"], low).reshape(b, t, hkv, d), theta, rotary_dim(cfg))
    v = linear(x, p["wv"], low).reshape(b, t, hkv, dv) * float(cfg["attention_value_scale"])
    window = int(cfg["sliding_window"]) if sliding and low != "no_window" else None
    sinks = p["sinks"] if sliding and cfg["add_swa_attention_sink_bias"] and low != "no_sinks" else None
    return linear(attend(q, k, v, window, sinks).reshape(b, t, hq * dv), p["wo"], low)


def mix(p, x, cfg, sliding: bool, low=None):
    """The block's first half: h = x + Attn(N_1(x))."""
    return x + attention(p, norm(x, p["norm1"], float(cfg["layernorm_epsilon"])), cfg, sliding, low)


def ffn(p, h, cfg, dense: bool, held=None, low=None):
    """What the block's second half adds to h: FFN(N_2(h)). Per token, so a
    caller may hand it any set of tokens [..., H]."""
    x = norm(h, p["norm2"], float(cfg["layernorm_epsilon"]))
    if dense:
        return swiglu(x, p["wg"], p["wu"], p["wd"], low)
    return moe(p, x, dict(cfg, routed_scaling_factor=1.0), held=held, shared=False, low=low)


def layer(p, x, cfg, sliding: bool, dense: bool, held=None, low=None):
    h = mix(p, x, cfg, sliding, low=low)
    return h + ffn(p, h, cfg, dense, held=held, low=low)


def head_logits(top, x, positions, cfg, low=None):
    """Logits [B, n, V] at `positions` [B, n] of the final hidden states x."""
    rows = jnp.take_along_axis(x, positions[..., None], axis=1)
    return linear(norm(rows, top["final_norm"], float(cfg["layernorm_epsilon"])), top["head"], low)


def forward(params, ids, cfg, held=None, low=None):
    """Logits [B, T, V] of the whole model; `params = {"top": ..., "layers": [...]}`.
    For the unit tests: at published widths `drivers/serve_mimo_v2.py` walks layer by layer."""
    x = embed(params["top"], ids)
    for i, p in enumerate(params["layers"]):
        x = layer(p, x, cfg, is_sliding(i, cfg), is_dense(i, cfg), held=held, low=low)
    return linear(norm(x, params["top"]["final_norm"], float(cfg["layernorm_epsilon"])),
                  params["top"]["head"], low)
