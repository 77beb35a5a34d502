"""Operations and bytes Kimi K2 needs, from the configuration's shapes: the
chip's share as the configuration's file states it (`n_routed_experts` held of
the `published` router width, the sliced vocabulary, the layers present, of
which the first `first_k_dense_replace` are dense).

Matmul FLOPs are 2 per multiply-add. Weights are counted at the
configuration's `param_dtype`, the router and its selection bias at float32,
a latent row at the lanes that hold something (`kv_lora_rank +
qk_rope_head_dim`: 576; the pool stores 640) in `compute_dtype`. Prefill
attention is counted in the plain form (query/key 192, value 128 a head),
causal; a decode step's in the absorbed form (576 + 512 a head a live row)."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def router_width(cfg: dict) -> int:
    """The router scores the published number of experts, whatever is held."""
    return int(cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"]))


def dense_layers(cfg: dict) -> int:
    return min(int(cfg["first_k_dense_replace"]), int(cfg["num_hidden_layers"]))


def expert_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"]) - dense_layers(cfg)


def latent_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def expert_params(cfg: dict) -> int:
    """One routed expert (and one shared expert): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_matmul_params(cfg: dict) -> int:
    """q_a, q_b, kv_a, kv_b and o: what a token meets in either form (the
    absorbed form multiplies by kv_b's two halves, once each)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * qk + h * latent_width(cfg)
            + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + heads * cfg["v_head_dim"] * h)


def attention_params(cfg: dict) -> int:
    return attention_matmul_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]  # the two norms


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix and the selection bias, both float32."""
    return cfg["hidden_size"] * router_width(cfg) + router_width(cfg)


def layer_params(cfg: dict, dense: bool) -> int:
    common = attention_params(cfg) + 2 * cfg["hidden_size"]
    if dense:
        return common + dense_mlp_params(cfg)
    return (common + router_params(cfg)
            + (int(cfg["n_routed_experts"]) + int(cfg["n_shared_experts"])) * expert_params(cfg))


def total_params(cfg: dict) -> int:
    """All parameters this chip holds: layers with their held experts, the
    embedding's and the head's slice, the final norm."""
    layers = dense_layers(cfg) * layer_params(cfg, True) + expert_layers(cfg) * layer_params(cfg, False)
    return layers + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def param_bytes(cfg: dict) -> int:
    w = DTYPE_BYTES[cfg["param_dtype"]]
    return total_params(cfg) * w + expert_layers(cfg) * router_params(cfg) * (4 - w)


def held_picks_per_token(cfg: dict) -> float:
    """Of a token's picks, the expected number on an expert held here under
    even routing."""
    return cfg["num_experts_per_tok"] * int(cfg["n_routed_experts"]) / router_width(cfg)


def token_flops(cfg: dict) -> float:
    """Forward FLOPs of one token through the layers here, attention's reads
    of the context and the head left out."""
    per_expert_layer = (cfg["hidden_size"] * router_width(cfg)
                        + (int(cfg["n_shared_experts"]) + held_picks_per_token(cfg)) * expert_params(cfg))
    return 2.0 * (int(cfg["num_hidden_layers"]) * attention_matmul_params(cfg)
                  + dense_layers(cfg) * dense_mlp_params(cfg)
                  + expert_layers(cfg) * per_expert_layer)


def absorbed_flops_per_key(cfg: dict) -> float:
    """One decode query against one cached row, one layer: every head's score
    over the latent row's lanes and its output over the value lanes."""
    return 2.0 * cfg["num_attention_heads"] * (latent_width(cfg) + cfg["kv_lora_rank"])


def plain_flops_per_key(cfg: dict) -> float:
    """One prefill query against one key, one layer, plain form."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def serve_request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one request needs of this chip: every prompt token and
    every fed-back token through the layers, the prompt's causal attention in
    the plain form, each decode step's over its context in the absorbed form,
    the head once per token produced."""
    layers = int(cfg["num_hidden_layers"])
    steps = max(new_tokens - 1, 0)
    fed = prompt_len + steps
    prefill_keys = prompt_len * (prompt_len + 1) / 2.0
    decode_keys = steps * prompt_len + steps * (steps + 1) / 2.0
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return (fed * token_flops(cfg) + layers * (plain_flops_per_key(cfg) * prefill_keys
                                                + absorbed_flops_per_key(cfg) * decode_keys)
            + new_tokens * head)


def decode_step_bytes(cfg: dict, rows: int, experts_touched: float, live_tokens: float) -> dict:
    """The least bytes one decode step of `rows` slots moves, by part:
    `experts_touched` distinct held experts an expert layer (the program's
    counter), every other weight once (the head whole, `rows` rows of the
    embedding), the live latent rows (`live_tokens` summed over the slots)
    read and `rows` new ones written, every layer."""
    w, a = DTYPE_BYTES[cfg["param_dtype"]], DTYPE_BYTES[cfg["compute_dtype"]]
    h = cfg["hidden_size"]
    experts = expert_layers(cfg) * experts_touched * expert_params(cfg) * w
    attention = int(cfg["num_hidden_layers"]) * attention_params(cfg) * w
    dense = dense_layers(cfg) * dense_mlp_params(cfg) * w
    shared = expert_layers(cfg) * (int(cfg["n_shared_experts"]) * expert_params(cfg) * w + router_params(cfg) * 4)
    top = (cfg["vocab_size"] * h + rows * h + h + 2 * h * int(cfg["num_hidden_layers"])) * w
    latent = int(cfg["num_hidden_layers"]) * latent_width(cfg) * a * (live_tokens + rows)
    parts = {"experts": experts, "attention_weights": attention, "dense_mlp": dense,
             "shared_and_router": shared, "head_and_norms": top, "latent_rows": latent}
    return dict(parts, total=sum(parts.values()))


def expert_matmul_cost(cfg: dict, picks_held: float, experts_touched: float) -> dict:
    """The grouped products of one layer's routed experts for one call:
    `picks_held` rows through gate, up and down; `experts_touched` experts'
    weights read once; each pick's input row read and output row written."""
    w, a = DTYPE_BYTES[cfg["param_dtype"]], DTYPE_BYTES[cfg["compute_dtype"]]
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"flops": 2.0 * picks_held * expert_params(cfg),
            "bytes": experts_touched * expert_params(cfg) * w + picks_held * (h * a + h * 4 + 2 * f * (4 + a))}


def mla_decode_cost(cfg: dict, live_tokens: float, rows: int) -> dict:
    """One call of the fused kernel on a latent pool: the live rows read once
    (at the lanes that hold something), `rows` queries in and outputs out; the
    absorbed form's two products over every live row."""
    a = DTYPE_BYTES[cfg["compute_dtype"]]
    heads = cfg["num_attention_heads"]
    return {"flops": absorbed_flops_per_key(cfg) * live_tokens,
            "bytes": latent_width(cfg) * a * live_tokens
            + rows * heads * (latent_width(cfg) + cfg["kv_lora_rank"]) * a}
