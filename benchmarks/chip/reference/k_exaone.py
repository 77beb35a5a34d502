"""K-EXAONE (`LGAI-EXAONE/K-EXAONE-236B-A23B`, `model_type: exaone_moe`) in
plain `jax.numpy` and float32: three sliding-window attention layers to one
full-attention layer, a leading dense layer, then layers of sigmoid-routed
experts beside an ungated shared expert. The forward pass only; no cache, no
kernel, no ring: attention is computed for every query against every key it
may see under explicit causal and window masks, a block of queries at a time
(a 12,288-position full layer then fits a chip), the experts are looped over.
It imports nothing from the program. Matmuls run at `highest` precision: on a
TPU a float32 product is otherwise computed in bf16 passes.

The equations (config.json of LGAI-EXAONE/K-EXAONE-236B-A23B; the layer is
EXAONE 4.0's, transformers' `models/exaone4/modeling_exaone4.py`, since
`exaone_moe` itself is not in the installed library):

  N(x)  = x / rms(x) * w                                  eps 1e-5, w starts at 1
  block l (no pre-norm: the norms sit on the sublayers' outputs):
    q = N_q(W_q h), k = N_k(W_k h), v = W_v h      N_q, N_k over each head's 128 dims
    q, k <- RoPE(q), RoPE(k)                        sliding layers only; rotate-half
                                                    over all 128 dims, theta 1e6
    a = W_o concat_h(sum_s softmax_s(q_h(t) . k_g(s) / sqrt(128)) v_g(s))
          g = h // 8 (64 query heads, 8 key/value heads); s <= t, and on a
          sliding layer t - 128 < s (the window)
    h <- h + N_attn(a);  h <- h + N_ffn(FFN_l(h))
  a final N, the untied head.
  FFN of layer 0: down(silu(gate x) * up x), width 18,432.
  experts: s = sigmoid(W_g x) over all routed experts; the 8 with the largest
    s + b chosen (b chooses, never weighs); w_e = 2.5 s_e / (sum of the chosen
    s + 1e-20); FFN(x) = sum_e w_e E_e(x) + E_shared(x), each E a SwiGLU MLP
    of width 2,048 (Kimi K2's expert layer, `reference/kimi_k2.py`'s `moe`).

Departures, each also in the configuration's file: no multi-token prediction
module; rotary pairs are halves; an expert layer may be given a *share*:
`held = (first, count)` names the routed experts whose weights it was handed,
the router stays as wide as published, and what the absent experts would add
is left out. The vocabulary may be a slice.

Parameters of one layer (a dict):
  norm_attn norm_ffn [H]; wq [H, 64 d]; wk wv [H, 8 d]; wo [64 d, H]; q_norm k_norm [d]
  dense:  wg wu [H, F]; wd [F, H]
  expert: router [H, E]; bias [E]; wg wu [E_held, H, f]; wd [E_held, f, H];
          s_wg s_wu [H, fs]; s_wd [fs, H]
Top level: embed [V, H]; final_norm [H]; head [H, V].

`low` turns a layer into a control that a sound program must be told from.
"int8": every product with a weight matrix (projections, router, experts,
head) takes both operands through vector-wise absmax int8, the precision next
below the stated bfloat16 compute. "no_window": the sliding layers attend the
whole causal context (the window removed). "rope_global": the full layers
rotate q and k as the sliding ones do (RoPE where the model has none)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the expert layer, the norm, the embedding and the head: the Kimi K2
# reference's, plain `jax.numpy` like everything here (embed and head_logits:
# `drivers/serve_k_exaone.py` takes them here)
from .kimi_k2 import embed, head_logits, moe, norm, swiglu  # noqa: F401
from .ling3 import rotary
from .qwen3_next import HIGHEST, linear

QUERY_BLOCK = 256  # float32 scores of 64 heads, 256 queries x 12,288 keys: 0.8 GB


def is_sliding(i: int, cfg: dict) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def is_dense(i: int, cfg: dict) -> bool:
    return cfg["mlp_layer_types"][i] == "dense"


def attend(q, k, v, window=None):
    """Causal grouped-query attention of q [B, T, hq, d] over k, v [B, T, hkv,
    d], query block by query block: a block sees every key (the full layers)
    or the `window - 1` keys before it and itself (the sliding layers), under
    the masks s <= t and, with a window, t - window < s."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    n = min(QUERY_BLOCK, t)
    whole = -(-t // n) * n  # keys past t lie after every real query: masked below
    q, k, v = (jnp.pad(x, ((0, 0), (0, whole - t), (0, 0), (0, 0))) for x in (q, k, v))
    span = whole if window is None else n + window - 1
    if window is not None:  # keys before position 0 are padding, masked below
        k, v = (jnp.pad(x, ((0, 0), (window - 1, 0), (0, 0), (0, 0))) for x in (k, v))

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
        weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", weights, vb, precision=HIGHEST).reshape(b, n, hq, d)

    out = jax.lax.map(block, jnp.arange(0, whole, n))  # [whole / n, B, n, hq, d]
    return jnp.moveaxis(out, 0, 1).reshape(b, whole, hq, d)[:, :t]


def attention(p, x, cfg, sliding: bool, low=None):
    b, t, _ = x.shape
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_parameters"]["rope_theta"])
    q = norm(linear(x, p["wq"], low).reshape(b, t, hq, d), p["q_norm"], eps)
    k = norm(linear(x, p["wk"], low).reshape(b, t, hkv, d), p["k_norm"], eps)
    v = linear(x, p["wv"], low).reshape(b, t, hkv, d)
    if sliding or low == "rope_global":
        q, k = rotary(q, theta), rotary(k, theta)
    window = int(cfg["sliding_window"]) if sliding and low != "no_window" else None
    return linear(attend(q, k, v, window).reshape(b, t, hq * d), p["wo"], low)


def mix(p, x, cfg, sliding: bool, low=None):
    """The block's first half: h = x + N_attn(Attn(x))."""
    return x + norm(attention(p, x, cfg, sliding, low), p["norm_attn"], float(cfg["rms_norm_eps"]))


def ffn(p, h, cfg, dense: bool, held=None, low=None):
    """What the block's second half adds to h: N_ffn(FFN(h)). Per token, so a
    caller may hand it any set of tokens [..., H]."""
    y = swiglu(h, p["wg"], p["wu"], p["wd"], low) if dense else moe(p, h, cfg, held=held, low=low)
    return norm(y, p["norm_ffn"], float(cfg["rms_norm_eps"]))


def layer(p, x, cfg, sliding: bool, dense: bool, held=None, low=None):
    h = mix(p, x, cfg, sliding, low=low)
    return h + ffn(p, h, cfg, dense, held=held, low=low)


def forward(params, ids, cfg, held=None, low=None):
    """Logits [B, T, V] of the whole model; `params = {"top": ..., "layers": [...]}`.
    For the unit tests: at published widths `drivers/serve_k_exaone.py` walks layer by layer."""
    x = embed(params["top"], ids)
    for i, p in enumerate(params["layers"]):
        x = layer(p, x, cfg, is_sliding(i, cfg), is_dense(i, cfg), held=held, low=low)
    return linear(norm(x, params["top"]["final_norm"], float(cfg["rms_norm_eps"])),
                  params["top"]["head"], low)
