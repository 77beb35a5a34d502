"""Operations and bytes Ling 3.0 flash needs, from the configuration's shapes:
the chip's share as the configuration's file states it (`num_experts` held of
the `published` router width, the sliced vocabulary, the layers present: layer
`l` is latent attention where `(l + 1) % layer_group_size == 0` and Kimi Delta
Attention otherwise, dense below `first_k_dense_replace`).

Matmul FLOPs are 2 per multiply-add. Weights are counted at the
configuration's `param_dtype`; the router, its bias, `A_log` and `dt_bias` at
float32, the KDA state at float32, a latent row at the lanes that hold
something (`kv_lora_rank + qk_rope_head_dim`: 576; the pool stores 640) and a
convolution window in `compute_dtype`. Prefill attention is counted in the
plain form (query/key 192, value 128 a head), causal; a decode step's in the
absorbed form (576 + 512 a head a live row)."""

from __future__ import annotations

# the same arithmetic on the same keys as the Kimi K2 configuration's: the
# latent layer's costs, the dense and expert layers' counts, an expert's size
from flops_kimi_k2 import (  # noqa: F401  (the readers and the tests take them here)
    DTYPE_BYTES,
    absorbed_flops_per_key,
    dense_layers,
    dense_mlp_params,
    expert_layers,
    expert_matmul_cost,
    expert_params,
    latent_width,
    mla_decode_cost,
    plain_flops_per_key,
)


def router_width(cfg: dict) -> int:
    """The router scores the published number of experts, whatever is held."""
    return int(cfg.get("published", {}).get("num_experts", cfg["num_experts"]))


def layer_kind(i: int, cfg: dict) -> str:
    return "latent" if (i + 1) % int(cfg["layer_group_size"]) == 0 else "kda"


def kinds(cfg: dict) -> list[str]:
    return [layer_kind(i, cfg) for i in range(int(cfg["num_hidden_layers"]))]


def kda_width(cfg: dict) -> int:
    """Heads times head size: the width of each of q, k, v, the gate and z."""
    return cfg["num_attention_heads"] * cfg["head_dim"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def kda_matmul_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_z, W_f and W_o (each hidden x heads x head size) and W_b."""
    return 6 * cfg["hidden_size"] * kda_width(cfg) + cfg["hidden_size"] * cfg["num_attention_heads"]


def kda_params(cfg: dict) -> int:
    """The products' matrices, the three convolutions' taps, `A_log` a head,
    `dt_bias` a channel and the output norm."""
    return (kda_matmul_params(cfg) + int(cfg["short_conv_kernel_size"]) * 3 * kda_width(cfg)
            + cfg["num_attention_heads"] + kda_width(cfg) + cfg["head_dim"])


def latent_matmul_params(cfg: dict) -> int:
    """The uncompressed query, kv_a, kv_b, the head-wise gate and o."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (h * heads * qk + h * latent_width(cfg)
            + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * heads + heads * cfg["v_head_dim"] * h)


def latent_params(cfg: dict) -> int:
    return latent_matmul_params(cfg) + cfg["kv_lora_rank"]  # the latent's norm


def mixer_params(cfg: dict, kind: str) -> int:
    return latent_params(cfg) if kind == "latent" else kda_params(cfg)


def router_params(cfg: dict) -> int:
    """The router's matrix and the expert bias, both float32."""
    return cfg["hidden_size"] * router_width(cfg) + router_width(cfg)


def layer_params(cfg: dict, i: int) -> int:
    common = mixer_params(cfg, layer_kind(i, cfg)) + 2 * cfg["hidden_size"]
    if i < dense_layers(cfg):
        return common + dense_mlp_params(cfg)
    return common + router_params(cfg) + int(cfg["num_experts"]) * expert_params(cfg) + shared_params(cfg)


def total_params(cfg: dict) -> int:
    """All parameters this chip holds: layers with their held experts, the
    embedding's and the head's slice, the final norm."""
    layers = sum(layer_params(cfg, i) for i in range(int(cfg["num_hidden_layers"])))
    return layers + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def float32_params(cfg: dict) -> int:
    """What stays float32 whatever `param_dtype` says: routers and biases,
    `A_log` and `dt_bias`."""
    return (expert_layers(cfg) * router_params(cfg)
            + kinds(cfg).count("kda") * (cfg["num_attention_heads"] + kda_width(cfg)))


def param_bytes(cfg: dict) -> int:
    w = DTYPE_BYTES[cfg["param_dtype"]]
    return total_params(cfg) * w + float32_params(cfg) * (4 - w)


def held_picks_per_token(cfg: dict) -> float:
    """Of a token's picks, the expected number on an expert held here under
    even routing."""
    return cfg["num_experts_per_tok"] * int(cfg["num_experts"]) / router_width(cfg)


def kda_state_elements(cfg: dict) -> int:
    """The float32 matrices S of one slot in one KDA layer."""
    return cfg["num_attention_heads"] * cfg["head_dim"] * cfg["head_dim"]


def conv_state_elements(cfg: dict) -> int:
    return (int(cfg["short_conv_kernel_size"]) - 1) * 3 * kda_width(cfg)


def token_flops(cfg: dict) -> float:
    """Forward FLOPs of one token through the layers here, attention's reads
    of the context and the head left out: projections, the dense MLPs, the
    router, the shared expert, the held share of the picks, and the delta
    rule's decay and three products with S."""
    total = 0.0
    for i, kind in enumerate(kinds(cfg)):
        if kind == "latent":
            total += 2.0 * latent_matmul_params(cfg)
        else:
            total += 2.0 * kda_matmul_params(cfg) + 7.0 * kda_state_elements(cfg)
        if i < dense_layers(cfg):
            total += 2.0 * dense_mlp_params(cfg)
        else:
            total += 2.0 * (cfg["hidden_size"] * router_width(cfg) + shared_params(cfg)
                            + held_picks_per_token(cfg) * expert_params(cfg))
    return total


def serve_request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs one request needs of this chip: every prompt token and
    every fed-back token through the layers, the prompt's causal attention in
    the plain form and each decode step's over its context in the absorbed
    form on the latent layers, the head once per token produced."""
    latent = kinds(cfg).count("latent")
    steps = max(new_tokens - 1, 0)
    fed = prompt_len + steps
    prefill_keys = prompt_len * (prompt_len + 1) / 2.0
    decode_keys = steps * prompt_len + steps * (steps + 1) / 2.0
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return (fed * token_flops(cfg) + latent * (plain_flops_per_key(cfg) * prefill_keys
                                                + absorbed_flops_per_key(cfg) * decode_keys)
            + new_tokens * head)


def decode_step_bytes(cfg: dict, rows: int, experts_touched: float, live_tokens: float) -> dict:
    """The least bytes one decode step of `rows` slots moves, by part:
    `experts_touched` distinct held experts an expert layer (the program's
    counter), every other weight once (the head whole, `rows` rows of the
    embedding), the KDA state and convolution windows read and written, the
    live latent rows (`live_tokens` summed over the slots) read and `rows` new
    ones written on every latent layer."""
    w, a = DTYPE_BYTES[cfg["param_dtype"]], DTYPE_BYTES[cfg["compute_dtype"]]
    h, layers = cfg["hidden_size"], kinds(cfg)
    kda, latent = layers.count("kda"), layers.count("latent")
    parts = {
        "experts": expert_layers(cfg) * experts_touched * expert_params(cfg) * w,
        "mixer_weights": sum(mixer_params(cfg, k) for k in layers) * w
        + kda * (cfg["num_attention_heads"] + kda_width(cfg)) * (4 - w),
        "dense_mlp": dense_layers(cfg) * dense_mlp_params(cfg) * w,
        "shared_and_router": expert_layers(cfg) * (shared_params(cfg) * w + router_params(cfg) * 4),
        "head_and_norms": (cfg["vocab_size"] * h + rows * h + h + 2 * h * len(layers)) * w,
        "state": 2.0 * rows * kda * (4 * kda_state_elements(cfg) + a * conv_state_elements(cfg)),
        "latent_rows": latent * latent_width(cfg) * a * (live_tokens + rows),
    }
    return dict(parts, total=sum(parts.values()))


def kda_step_cost(cfg: dict, rows: int) -> dict:
    """One token of `rows` slots through one KDA layer's delta rule: S read
    and written in float32; a decay a channel and three products with it."""
    return {"flops": 7.0 * rows * kda_state_elements(cfg),
            "bytes": 2.0 * rows * 4 * kda_state_elements(cfg)}
