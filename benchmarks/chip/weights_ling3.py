"""Random Ling 3.0 flash weights from a seed, one layer at a time.

A layer is a pure function of (seed, layer index), drawn in the reference's
layout (reference/ling3.py) in the configuration's `param_dtype`;
`layer_to_program` re-arranges the very same arrays into the tree
`accelerate_tpu.models.ling3.Ling3ForCausalLM` expects (gate and up side by
side, `kv_b` with its heads unfolded), so both sides of `correct` hold
identical numbers and neither takes anything the other made. At published
widths one expert layer's 128 held experts are 1.51 GB in bfloat16: the
program's tree is built layer by layer (one compiled draw for each kind of
layer), and the reference asks for one layer at a time and upcasts it.

Scales (the configuration's `assumed.weights`): every projection normal(0,
0.02), the experts' among them (a held pick enters with a weight of about 2.5 /
8); the router 0.0198 = 2560^-1/2, which spreads its 512 logits to a standard
deviation of about 1, and `expert_bias` normal(0, 0.01): the scores around the
eighth place inside the four kept groups lie some 0.006 apart, as in the Kimi
K2 cell, so the bias moves the last place or two of a token's choice and
seldom which groups are kept; embedding and the untied head 0.02 (logits of
standard deviation 1.0 over the vocabulary: near-ties common, greedy streams
keep moving); norm weights 1 +- 0.1; convolution taps 0.3. KDA's gate:
`A_log` normal(0, 0.3) a head and `dt_bias` normal(-5, 1.5) a channel, beside
`W_f x` of standard deviation about 1: `exp(A_log) (W_f x + dt_bias)` lies
between about -8.5 and -1.5 across channels, so a channel's decay a token,
`exp(-5 sigmoid(.))`, spreads from about 0.999 to 0.4 with its middle at 0.97
(a state that forgets in four tokens would hide every fault of the state),
and moves with the token. The router, its bias, `A_log` and `dt_bias` stay
float32 in every layout."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flops_ling3 import dense_layers, kda_width, latent_width, layer_kind, router_width
from weights import seed_key
from weights_kimi_k2 import top_to_program
from weights_qwen3_next import _draw, _frozen, upcast  # noqa: F401  (upcast: the driver takes it here)

# keys of the published config whose `true` would be mathematics this model does not have
OFF = ("use_nGPT", "scale_router_input", "value_norm", "up_proj_norm", "mtp_use_kda", "use_kda_lora",
       "use_mla_nope")


def held_experts(cfg: dict) -> tuple[int, int]:
    """(first, count) of the routed experts this chip holds."""
    return int(cfg.get("deployment", {}).get("first_expert", 0)), int(cfg["num_experts"])


def is_dense(i: int, cfg: dict) -> bool:
    return i < dense_layers(cfg)


def layer_specs(cfg: dict, kind: str, dense: bool) -> dict:
    """{leaf: (shape, mean, std, float32_always)} of one layer, reference layout."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    specs = {"norm1": ((h,), 1.0, 0.1, False), "norm2": ((h,), 1.0, 0.1, False)}
    if kind == "latent":
        rank, nope, rope, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                                cfg["v_head_dim"])
        specs.update({
            "wq": ((h, heads * (nope + rope)), 0.0, 0.02, False),
            "wkva": ((h, latent_width(cfg)), 0.0, 0.02, False), "kva_norm": ((rank,), 1.0, 0.1, False),
            "wkvb": ((rank, heads * (nope + dv)), 0.0, 0.02, False),
            "whg": ((h, heads), 0.0, 0.02, False), "wo": ((heads * dv, h), 0.0, 0.02, False),
        })
    else:
        n = kda_width(cfg)
        specs.update({
            "wqkvz": ((h, 4 * n), 0.0, 0.02, False), "wfb": ((h, n + heads), 0.0, 0.02, False),
            "conv_w": ((int(cfg["short_conv_kernel_size"]), 3 * n), 0.0, 0.3, False),
            "A_log": ((heads,), 0.0, 0.3, True), "dt_bias": ((n,), -5.0, 1.5, True),
            "out_norm": ((cfg["head_dim"],), 1.0, 0.1, False), "wout": ((n, h), 0.0, 0.02, False),
        })
    if dense:
        f = cfg["intermediate_size"]
        specs.update({"wg": ((h, f), 0.0, 0.02, False), "wu": ((h, f), 0.0, 0.02, False),
                      "wd": ((f, h), 0.0, 0.02, False)})
    else:
        held, f, fs = int(cfg["num_experts"]), cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
        specs.update({
            "router": ((h, router_width(cfg)), 0.0, h ** -0.5, True),
            "bias": ((router_width(cfg),), 0.0, 0.01, True),
            "wg": ((held, h, f), 0.0, 0.02, False), "wu": ((held, h, f), 0.0, 0.02, False),
            "wd": ((held, f, h), 0.0, 0.02, False),
            "s_wg": ((h, fs), 0.0, 0.02, False), "s_wu": ((h, fs), 0.0, 0.02, False),
            "s_wd": ((fs, h), 0.0, 0.02, False),
        })
    return specs


def top_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": ((v, h), 0.0, 0.02, False), "final_norm": ((h,), 1.0, 0.1, False),
            "head": ((h, v), 0.0, 0.02, False)}


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_cfg, held: int, router: int, kind: str, dense: bool, dtype_name: str, program: bool):
    cfg = dict(frozen_cfg, num_experts=held, published={"num_experts": router})
    specs = layer_specs(cfg, kind, dense)

    def draw(key):
        layer = _draw(key, specs, jnp.dtype(dtype_name))
        return layer_to_program(layer, cfg, kind, dense) if program else layer

    return jax.jit(draw)


def _layer(seed: int, cfg: dict, i: int, dtype, program: bool) -> dict:
    fn = _layer_fn(_frozen(cfg), int(cfg["num_experts"]), router_width(cfg), layer_kind(i, cfg),
                   is_dense(i, cfg), jnp.dtype(dtype).name, program)
    return fn(jax.random.fold_in(seed_key(seed), 1 + i))


def make_layer(seed: int, cfg: dict, i: int, dtype=jnp.bfloat16) -> dict:
    """Layer `i` in the reference's layout, in `dtype`."""
    return _layer(seed, cfg, i, dtype, program=False)


def make_top(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    specs = top_specs(cfg)
    return jax.jit(lambda k: _draw(k, specs, jnp.dtype(dtype)))(jax.random.fold_in(seed_key(seed), 0))


def layer_to_program(p: dict, cfg: dict, kind: str, dense: bool) -> dict:
    """A reference-layout layer as the program's `layer_i` subtree."""
    out = {"input_norm": {"scale": p["norm1"]}, "post_norm": {"scale": p["norm2"]}}
    if kind == "latent":
        out["attn"] = {"q_proj": {"kernel": p["wq"]}, "kv_a_proj": {"kernel": p["wkva"]},
                       "kv_a_norm": {"scale": p["kva_norm"]},
                       "kv_b_proj": p["wkvb"].reshape(p["wkvb"].shape[0], cfg["num_attention_heads"], -1),
                       "g_proj": {"kernel": p["whg"]}, "o_proj": {"kernel": p["wo"]}}
    else:
        out["kda"] = {"in_proj_qkvz": {"kernel": p["wqkvz"]}, "in_proj_fb": p["wfb"],
                      "conv_w": p["conv_w"], "A_log": p["A_log"], "dt_bias": p["dt_bias"],
                      "norm": p["out_norm"], "out_proj": {"kernel": p["wout"]}}
    if dense:
        out["mlp"] = {"gate_up": jnp.concatenate([p["wg"], p["wu"]], -1), "down": p["wd"]}
    else:
        out["moe"] = {"router": p["router"], "expert_bias": p["bias"],
                      "w_gate_up": jnp.concatenate([p["wg"], p["wu"]], -1), "w_down": p["wd"],
                      "shared_gate_up": jnp.concatenate([p["s_wg"], p["s_wu"]], -1),
                      "shared_down": p["s_wd"]}
    return out


def make_program(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of `Ling3ForCausalLM`, layer by layer."""
    tree = top_to_program(make_top(seed, cfg, dtype))
    for i in range(int(cfg["num_hidden_layers"])):
        tree[f"layer_{i}"] = _layer(seed, cfg, i, dtype, program=True)
    return tree


def make_reference(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """{"top", "layers"} in float32, the values those of `dtype`: for the
    unit tests; at published widths the driver walks layer by layer."""
    return {"top": upcast(make_top(seed, cfg, dtype)),
            "layers": [upcast(make_layer(seed, cfg, i, dtype))
                       for i in range(int(cfg["num_hidden_layers"]))]}


def model_config(cfg: dict, **extra):
    """The configuration file as the program's `Ling3Config`. A published
    switch this model has no code for must read false, and a compressed
    query (`q_lora_rank`) null."""
    from accelerate_tpu.models.ling3 import Ling3Config

    on = [k for k in OFF if cfg.get(k)]
    if on or cfg.get("q_lora_rank") is not None or cfg.get("score_function") != "sigmoid":
        raise ValueError(f"the configuration asks for what Ling3ForCausalLM has not: {on or 'q_lora_rank / score_function'}")
    first, held = held_experts(cfg)
    keys = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size", "num_hidden_layers", "first_k_dense_replace",
            "layer_group_size", "num_attention_heads", "head_dim", "short_conv_kernel_size",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor", "rms_norm_eps")
    return Ling3Config(
        **{k: cfg[k] for k in keys}, kda_lower_bound=float(cfg["kda_lower_bound"]),
        rope_theta=float(cfg["rope_theta"]), num_experts=router_width(cfg), experts_held=held,
        first_expert=first, expert_swiglu_limit_list=tuple(cfg["expert_swiglu_limit_list"]),
        share_expert_swiglu_limit_list=tuple(cfg["share_expert_swiglu_limit_list"]),
        n_positions=int(cfg["n_positions"]), dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]), **extra)
