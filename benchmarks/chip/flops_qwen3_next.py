"""Operations and bytes Qwen3-Next needs, from the configuration's shapes: the
chip's share as the configuration's file states it (`num_experts` held of the
`published` router width, the sliced vocabulary, the layers present).

Matmul FLOPs are 2 per multiply-add. Attention is counted causal. Weights are
counted at the configuration's `param_dtype`, the DeltaNet state at float32."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def layer_kind(i: int, cfg: dict) -> str:
    return "full" if (i + 1) % int(cfg["full_attention_interval"]) == 0 else "linear"


def kinds(cfg: dict) -> list[str]:
    return [layer_kind(i, cfg) for i in range(int(cfg["num_hidden_layers"]))]


def router_width(cfg: dict) -> int:
    """The router scores the published number of experts, whatever is held."""
    return int(cfg.get("published", {}).get("num_experts", cfg["num_experts"]))


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down projections."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params_outside_experts(cfg: dict, kind: str) -> int:
    """Every parameter of one layer but its routed experts: mixer, norms,
    router (at its published width), shared expert and its gate."""
    h = cfg["hidden_size"]
    shared = 3 * h * cfg["shared_expert_intermediate_size"] + h
    common = 2 * h + h * router_width(cfg) + shared
    if kind == "full":
        hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        return common + h * hq * 2 * d + 2 * h * hkv * d + hq * d * h + 2 * d
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    n_qkv = 2 * hk * dk + hv * dv
    return (common + h * (n_qkv + hv * dv) + h * 2 * hv + int(cfg["linear_conv_kernel_dim"]) * n_qkv
            + 2 * hv + dv + hv * dv * h)


def matmul_params_outside_experts(cfg: dict, kind: str) -> int:
    """The part of `layer_params_outside_experts` that sits in a matrix
    product for every token: projections, router, shared expert."""
    h = cfg["hidden_size"]
    outside = layer_params_outside_experts(cfg, kind) - 2 * h - h  # norms, the shared gate's vector
    if kind == "full":
        return outside - 2 * cfg["head_dim"]
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    return outside - int(cfg["linear_conv_kernel_dim"]) * (2 * hk * dk + hv * dv) - 2 * hv - dv


def total_params(cfg: dict) -> int:
    """All parameters this chip holds: layers with their held experts, the
    embedding's and the head's slice, the final norm."""
    held = int(cfg["num_experts"])
    layers = sum(layer_params_outside_experts(cfg, k) + held * expert_params(cfg) for k in kinds(cfg))
    return layers + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def held_picks_per_token(cfg: dict) -> float:
    """Of a token's `num_experts_per_tok` picks, the expected number that fall
    on an expert held here under even routing."""
    return cfg["num_experts_per_tok"] * int(cfg["num_experts"]) / router_width(cfg)


def delta_state_elements(cfg: dict) -> int:
    """The float32 matrix S of one slot in one linear layer."""
    return cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]


def conv_state_elements(cfg: dict) -> int:
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    return (int(cfg["linear_conv_kernel_dim"]) - 1) * (2 * hk * dk + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def token_flops(cfg: dict) -> float:
    """Forward FLOPs of one token through the layers here, attention's reads
    of the context and the head left out: projections, router, shared expert,
    the held share of its picks, and the delta rule's three products with S
    (S^T k, k d^T, S^T q) and its decay."""
    total = 0.0
    for kind in kinds(cfg):
        total += 2.0 * matmul_params_outside_experts(cfg, kind)
        total += 2.0 * held_picks_per_token(cfg) * expert_params(cfg)
        if kind == "linear":
            total += 7.0 * delta_state_elements(cfg)
    return total


def attention_flops_per_key(cfg: dict) -> float:
    """QK^T and PV of one query against one key, all full-attention layers."""
    full = sum(1 for k in kinds(cfg) if k == "full")
    return 4.0 * full * cfg["num_attention_heads"] * cfg["head_dim"]


def serve_request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one request needs of this chip: every prompt token and
    every fed-back token through the layers, the head once per token produced."""
    fed = prompt_len + max(new_tokens - 1, 0)
    context_sum = fed * (fed + 1) / 2.0
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return fed * token_flops(cfg) + attention_flops_per_key(cfg) * context_sum + new_tokens * head


def decode_step_bytes(cfg: dict, rows: int, experts_touched: float, live_tokens: float) -> dict:
    """The least bytes one decode step of `rows` slots moves, by part:
    `experts_touched` distinct held experts a layer (from the program's
    counter), every other weight once (the head whole, `rows` rows of the
    embedding), the recurrent state read and written, the live keys and values
    (`live_tokens` summed over the slots) read and `rows` new ones written."""
    w = DTYPE_BYTES[cfg["param_dtype"]]
    h, layers = cfg["hidden_size"], kinds(cfg)
    linear, full = layers.count("linear"), layers.count("full")
    experts = len(layers) * experts_touched * expert_params(cfg) * w
    other = sum(layer_params_outside_experts(cfg, k) for k in layers) * w \
        + (cfg["vocab_size"] * h + rows * h + h) * w
    state = 2.0 * rows * linear * (4 * delta_state_elements(cfg) + DTYPE_BYTES[cfg["compute_dtype"]] * conv_state_elements(cfg))
    kv_row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * DTYPE_BYTES[cfg["compute_dtype"]]
    kv = full * kv_row * (live_tokens + rows)
    return {"experts": experts, "other_weights": other, "state": state, "kv": kv,
            "total": experts + other + state + kv}


def expert_matmul_cost(cfg: dict, picks_held: float, experts_touched: float) -> dict:
    """The grouped products of one layer's routed experts for one call:
    `picks_held` rows through gate, up and down; `experts_touched` experts'
    weights read once; each pick's input row read and output row written."""
    w, a = DTYPE_BYTES[cfg["param_dtype"]], DTYPE_BYTES[cfg["compute_dtype"]]
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"flops": 2.0 * picks_held * expert_params(cfg),
            "bytes": experts_touched * expert_params(cfg) * w + picks_held * (h * a + h * 4 + 2 * f * (4 + a))}


def delta_step_cost(cfg: dict, rows: int) -> dict:
    """One token of `rows` slots through one linear layer's delta rule: S read
    and written in float32; a decay and three products with it."""
    return {"flops": 7.0 * rows * delta_state_elements(cfg),
            "bytes": 2.0 * rows * 4 * delta_state_elements(cfg)}
