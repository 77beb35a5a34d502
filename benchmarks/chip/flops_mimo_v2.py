"""Operations and bytes MiMo-V2 needs, from the configuration's shapes: the
chip's share as the configuration's file states it (`n_routed_experts` held
of the `published` router width, the sliced vocabulary, the layers present:
layer `l` attends over a sliding window where `hybrid_layer_pattern[l]` is 1
and over the whole context where it is 0, and is dense where
`moe_layer_freq[l]` is 0). The two kinds differ in their key/value heads (8 on
a sliding layer, 4 on a full one); both have 64 query heads, keys of 192 and
values of 128.

Matmul FLOPs are 2 per multiply-add. Weights are counted at the
configuration's `param_dtype`; the router, its selection bias and the sinks
at float32; keys and values (the full layers' pool, the sliding layers'
rings) in `compute_dtype`. Attention FLOPs are those of the keys a query may
see: the causal context on a full layer, at most `sliding_window` keys on a
sliding one; a query a key, 2 x heads x 192 for its score and 2 x heads x 128
for its value."""

from __future__ import annotations

from flops_k_exaone import window_keys
# an expert's size, the dense MLP's and the router's width, on the same keys as the Kimi K2 configuration's
from flops_kimi_k2 import DTYPE_BYTES, dense_mlp_params, expert_params, router_width
from reference.mimo_v2 import heads, is_dense, is_sliding


def layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def kinds(cfg: dict) -> list[bool]:
    """Whether each layer here is a sliding one."""
    return [is_sliding(i, cfg) for i in range(layers(cfg))]


def dense_layers(cfg: dict) -> int:
    return sum(is_dense(i, cfg) for i in range(layers(cfg)))


def expert_layers(cfg: dict) -> int:
    return layers(cfg) - dense_layers(cfg)


def token_kv(cfg: dict, sliding: bool) -> int:
    """Lanes of one token's key and value on one layer of a kind: heads x (192 + 128)."""
    _, hkv, d, dv = heads(cfg, sliding)
    return hkv * (d + dv)


def query_lanes(cfg: dict, sliding: bool) -> int:
    """A query in and an output out, one token, one layer: heads x (192 + 128)."""
    hq, _, d, dv = heads(cfg, sliding)
    return hq * (d + dv)


def sinks(cfg: dict, sliding: bool) -> int:
    return heads(cfg, True)[0] if sliding and cfg["add_swa_attention_sink_bias"] else 0


def attention_matmul_params(cfg: dict, sliding: bool) -> int:
    """W_q, W_k, W_v and W_o of one layer of a kind."""
    hq, hkv, d, dv = heads(cfg, sliding)
    return cfg["hidden_size"] * (hq * d + hkv * d + hkv * dv + hq * dv)


def router_params(cfg: dict) -> int:
    """The router's matrix and the selection bias, both float32."""
    return cfg["hidden_size"] * router_width(cfg) + router_width(cfg)


def layer_params(cfg: dict, i: int) -> int:
    sliding = is_sliding(i, cfg)
    common = attention_matmul_params(cfg, sliding) + sinks(cfg, sliding) + 2 * cfg["hidden_size"]
    if is_dense(i, cfg):
        return common + dense_mlp_params(cfg)
    return common + router_params(cfg) + int(cfg["n_routed_experts"]) * expert_params(cfg)


def total_params(cfg: dict) -> int:
    """All parameters this chip holds: layers with their held experts, the
    embedding's and the head's slice, the final norm."""
    held = sum(layer_params(cfg, i) for i in range(layers(cfg)))
    return held + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def param_bytes(cfg: dict) -> int:
    w = DTYPE_BYTES[cfg["param_dtype"]]
    float32 = expert_layers(cfg) * router_params(cfg) + sum(sinks(cfg, s) for s in kinds(cfg))
    return total_params(cfg) * w + float32 * (4 - w)


def held_picks_per_token(cfg: dict) -> float:
    """Of a token's picks, the expected number on an expert held here under
    even routing."""
    return cfg["num_experts_per_tok"] * int(cfg["n_routed_experts"]) / router_width(cfg)


def attention_flops_per_key(cfg: dict, sliding: bool) -> float:
    """One query against one key, one layer of a kind: its scores and its values, every head."""
    return 2.0 * query_lanes(cfg, sliding)


def token_flops(cfg: dict) -> float:
    """Forward FLOPs of one token through the layers here, attention's reads
    of the context and the head left out: projections, the dense MLP, the
    router, the held share of the picks."""
    per_expert_layer = cfg["hidden_size"] * router_width(cfg) + held_picks_per_token(cfg) * expert_params(cfg)
    return 2.0 * (sum(attention_matmul_params(cfg, s) for s in kinds(cfg))
                  + dense_layers(cfg) * dense_mlp_params(cfg) + expert_layers(cfg) * per_expert_layer)


def serve_request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one request needs of this chip: every prompt token and
    every fed-back token through the layers, each query's attention over the
    keys it sees (the causal context on the full layers, the window on the
    sliding ones), the head once per token produced."""
    k = kinds(cfg)
    steps = max(new_tokens - 1, 0)
    fed = prompt_len + steps
    full_keys = fed * (fed + 1) / 2.0  # every fed token at positions 0 .. fed - 1
    sliding_keys = window_keys(cfg, 0, fed)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return (fed * token_flops(cfg) + k.count(False) * attention_flops_per_key(cfg, False) * full_keys
            + k.count(True) * attention_flops_per_key(cfg, True) * sliding_keys + new_tokens * head)


def decode_step_bytes(cfg: dict, rows: int, experts_touched: float, live_tokens: float,
                      window_rows: float) -> dict:
    """The least bytes one decode step of `rows` slots moves, by part:
    `experts_touched` distinct held experts an expert layer (the program's
    counter), every other weight once (the head whole, `rows` rows of the
    embedding), the full layers' live keys and values (`live_tokens` summed
    over the slots, a layer: the engine's count) read and `rows` new ones
    written, the rings' live rows (`window_rows`, summed over the sliding
    layers and the slots: the program's counter) read and `rows` new ones
    written on every sliding layer."""
    w, a = DTYPE_BYTES[cfg["param_dtype"]], DTYPE_BYTES[cfg["compute_dtype"]]
    h, k = cfg["hidden_size"], kinds(cfg)
    parts = {
        "experts": expert_layers(cfg) * experts_touched * expert_params(cfg) * w,
        "attention_weights": sum(attention_matmul_params(cfg, s) * w + sinks(cfg, s) * 4 for s in k),
        "dense_mlp": dense_layers(cfg) * dense_mlp_params(cfg) * w,
        "router": expert_layers(cfg) * router_params(cfg) * 4,
        "head_and_norms": (cfg["vocab_size"] * h + rows * h + h + 2 * h * layers(cfg)) * w,
        "full_kv": k.count(False) * token_kv(cfg, False) * a * (live_tokens + rows),
        "rings": token_kv(cfg, True) * a * (window_rows + k.count(True) * rows),
    }
    return dict(parts, total=sum(parts.values()))


def gqa_decode_cost(cfg: dict, live_tokens: float, rows: int) -> dict:
    """One call of the fused kernel on a full layer's pool: the live keys (4
    heads of 192) and values (4 of 128) read once, `rows` queries in and
    outputs out; scores and values over every live key."""
    a = DTYPE_BYTES[cfg["compute_dtype"]]
    return {"flops": attention_flops_per_key(cfg, False) * live_tokens,
            "bytes": (token_kv(cfg, False) * live_tokens + rows * query_lanes(cfg, False)) * a}


def window_decode_cost(cfg: dict, window_rows: float, rows: int) -> dict:
    """One decode step's attention over the rings of every sliding layer:
    `window_rows` live ring rows (8 heads x (192 + 128) lanes) read once,
    `rows` new ones written, `rows` queries in and outputs out a layer;
    scores and values over every live row."""
    a = DTYPE_BYTES[cfg["compute_dtype"]]
    per_layer = rows * (token_kv(cfg, True) + query_lanes(cfg, True)) * a
    return {"flops": attention_flops_per_key(cfg, True) * window_rows,
            "bytes": token_kv(cfg, True) * a * window_rows + kinds(cfg).count(True) * per_layer}


def window_prefill_cost(cfg: dict, tokens: int) -> dict:
    """One call of the band kernel with the sink on one sliding layer over an
    admit row of `tokens` positions (the bucket): every query's scores and
    values over the keys of its window, q, k, v read and the output written
    once."""
    a = DTYPE_BYTES[cfg["compute_dtype"]]
    return {"flops": attention_flops_per_key(cfg, True) * window_keys(cfg, 0, tokens),
            "bytes": tokens * (token_kv(cfg, True) + query_lanes(cfg, True)) * a}
