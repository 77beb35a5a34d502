"""Operations and bytes K-EXAONE needs, from the configuration's shapes: the
chip's share as the configuration's file states it (`num_experts` held of the
`published` router width, the sliced vocabulary, the layers present: layer `l`
attends over a sliding window where `layer_types[l]` says so and over the
whole context otherwise, and is dense where `mlp_layer_types[l]` says so).

Matmul FLOPs are 2 per multiply-add. Weights are counted at the
configuration's `param_dtype`, the router and its selection bias at float32,
keys and values (a full layer's pool, a sliding layer's ring) in
`compute_dtype`. Attention FLOPs are those of the keys a query may see: the
causal half of the context on a full layer, at most `sliding_window` keys on a
sliding one; 4 x heads x head_dim a query a key (scores and values)."""

from __future__ import annotations

# an expert's size and the dense MLP's, on the same keys as the Kimi K2 configuration's
from flops_kimi_k2 import DTYPE_BYTES, dense_mlp_params, expert_params  # noqa: F401  (the tests take them here)


def router_width(cfg: dict) -> int:
    """The router scores the published number of experts, whatever is held."""
    return int(cfg.get("published", {}).get("num_experts", cfg["num_experts"]))


def layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"])


def kinds(cfg: dict) -> list[str]:
    """"sliding" or "full", a layer."""
    return ["sliding" if t == "sliding_attention" else "full" for t in cfg["layer_types"][: layers(cfg)]]


def dense_layers(cfg: dict) -> int:
    return cfg["mlp_layer_types"][: layers(cfg)].count("dense")


def expert_layers(cfg: dict) -> int:
    return layers(cfg) - dense_layers(cfg)


def kv_width(cfg: dict) -> int:
    """Key/value heads times head size: one key (or one value) of a token."""
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def attention_matmul_params(cfg: dict) -> int:
    """W_q and W_o (hidden x 64 heads x 128), W_k and W_v (hidden x 8 x 128)."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return 2 * cfg["hidden_size"] * q + 2 * cfg["hidden_size"] * kv_width(cfg)


def attention_params(cfg: dict) -> int:
    return attention_matmul_params(cfg) + 2 * cfg["head_dim"]  # the QK-norms


def shared_params(cfg: dict) -> int:
    return int(cfg["num_shared_experts"]) * expert_params(cfg)


def router_params(cfg: dict) -> int:
    """The router's matrix and the selection bias, both float32."""
    return cfg["hidden_size"] * router_width(cfg) + router_width(cfg)


def layer_params(cfg: dict, i: int) -> int:
    common = attention_params(cfg) + 2 * cfg["hidden_size"]  # the two post-sublayer norms
    if cfg["mlp_layer_types"][i] == "dense":
        return common + dense_mlp_params(cfg)
    return common + router_params(cfg) + int(cfg["num_experts"]) * expert_params(cfg) + shared_params(cfg)


def total_params(cfg: dict) -> int:
    """All parameters this chip holds: layers with their held experts, the
    embedding's and the head's slice, the final norm."""
    held = sum(layer_params(cfg, i) for i in range(layers(cfg)))
    return held + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def param_bytes(cfg: dict) -> int:
    w = DTYPE_BYTES[cfg["param_dtype"]]
    return total_params(cfg) * w + expert_layers(cfg) * router_params(cfg) * (4 - w)


def held_picks_per_token(cfg: dict) -> float:
    """Of a token's picks, the expected number on an expert held here under
    even routing."""
    return cfg["num_experts_per_tok"] * int(cfg["num_experts"]) / router_width(cfg)


def attention_flops_per_key(cfg: dict) -> float:
    """One query against one key, one layer: its scores and its values, every head."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def token_flops(cfg: dict) -> float:
    """Forward FLOPs of one token through the layers here, attention's reads
    of the context and the head left out: projections, the dense MLP, the
    router, the shared expert, the held share of the picks."""
    per_expert_layer = (cfg["hidden_size"] * router_width(cfg)
                        + (int(cfg["num_shared_experts"]) + held_picks_per_token(cfg)) * expert_params(cfg))
    return 2.0 * (layers(cfg) * attention_matmul_params(cfg) + dense_layers(cfg) * dense_mlp_params(cfg)
                  + expert_layers(cfg) * per_expert_layer)


def window_keys(cfg: dict, first: int, count: int) -> float:
    """Keys the queries at positions [first, first + count) see on one
    sliding layer: min(p + 1, window) each."""
    w = int(cfg["sliding_window"])
    inside = max(0, min(first + count, w) - first)  # queries still short of a whole window
    start = first + 1
    return inside * (2 * start + inside - 1) / 2.0 + (count - inside) * w


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
    return (fed * token_flops(cfg) + attention_flops_per_key(cfg)
            * (k.count("full") * full_keys + k.count("sliding") * sliding_keys) + new_tokens * head)


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
    token_kv = 2 * kv_width(cfg) * a
    parts = {
        "experts": expert_layers(cfg) * experts_touched * expert_params(cfg) * w,
        "attention_weights": layers(cfg) * attention_params(cfg) * w,
        "dense_mlp": dense_layers(cfg) * dense_mlp_params(cfg) * w,
        "shared_and_router": expert_layers(cfg) * (shared_params(cfg) * w + router_params(cfg) * 4),
        "head_and_norms": (cfg["vocab_size"] * h + rows * h + h + 2 * h * layers(cfg)) * w,
        "full_kv": k.count("full") * token_kv * (live_tokens + rows),
        "rings": token_kv * (window_rows + k.count("sliding") * rows),
    }
    return dict(parts, total=sum(parts.values()))


def gqa_decode_cost(cfg: dict, live_tokens: float, rows: int) -> dict:
    """One call of the fused kernel on a full layer's pool: the live keys and
    values read once, `rows` queries in and outputs out; scores and values
    over every live key."""
    a = DTYPE_BYTES[cfg["compute_dtype"]]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    return {"flops": attention_flops_per_key(cfg) * live_tokens,
            "bytes": 2 * kv_width(cfg) * a * live_tokens + 2 * rows * q * a}


def window_decode_cost(cfg: dict, window_rows: float, rows: int) -> dict:
    """One decode step's attention over the rings of every sliding layer:
    `window_rows` live ring rows (keys and values) read once, `rows` new ones
    written, `rows` queries in and outputs out a layer; scores and values over
    every live row."""
    a = DTYPE_BYTES[cfg["compute_dtype"]]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    token_kv = 2 * kv_width(cfg) * a
    per_layer = rows * (token_kv + 2 * q * a)
    return {"flops": attention_flops_per_key(cfg) * window_rows,
            "bytes": token_kv * window_rows + kinds(cfg).count("sliding") * per_layer}
