"""Random MiMo-V2 weights from a seed, one layer at a time.

A layer is a pure function of (seed, layer index), drawn in the reference's
layout (reference/mimo_v2.py) in the configuration's `param_dtype`;
`layer_to_program` re-arranges the very same arrays into the tree
`accelerate_tpu.models.mimo_v2.MiMoV2ForCausalLM` expects (gate and up side
by side), so both sides of `correct` hold identical numbers and neither takes
anything the other made. At published widths one expert layer's 16 held
experts are 0.81 GB in bfloat16 and the dense layer's MLP 0.40 GB: the
program's tree is built layer by layer (one compiled draw for each kind of
layer), and the reference asks for one layer at a time and upcasts it.

Scales (the configuration's `assumed.weights`): every projection normal(0,
0.02), the experts' among them; the router 4096^-1/2, and
`e_score_correction_bias` the mid-quantiles of normal(0, 0.01) stratified by
chip (`weights_k_exaone.selection_bias`, as in the K-EXAONE cell: every chip
of the deployment's 16 holds one value of each sixteenth). The sinks,
float32, normal(log W, 1) a query head (log 128 at the window W of 128): a
normed stream's scores spread
about 1.6 at these scales, so over a full window of 128 keys the sink takes
about a fifth of a query's weight (more on the rows still short of a window),
and removing it moves what a layer adds by as much. Embedding 0.02 and the
untied head 0.0128 (logits of standard deviation about 1 over the vocabulary:
near-ties common, greedy streams keep moving); norm weights 1 +- 0.1."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from flops_mimo_v2 import router_width
from reference.mimo_v2 import heads, is_dense, is_sliding
from weights import seed_key
from weights_k_exaone import selection_bias
from weights_kimi_k2 import top_to_program
from weights_qwen3_next import _draw, _frozen, upcast  # noqa: F401  (upcast: drivers/serve_mimo_v2.py takes it here)

# keys of the published config whose other value would be mathematics this model does not have
AS_PUBLISHED = {"hidden_act": "silu", "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
                "topk_method": "noaux_tc", "norm_topk_prob": True, "tie_word_embeddings": False,
                "attention_bias": False, "add_full_attention_sink_bias": False}


def held_experts(cfg: dict) -> tuple[int, int]:
    """(first, count) of the routed experts this chip holds."""
    return int(cfg.get("deployment", {}).get("first_expert", 0)), int(cfg["n_routed_experts"])


def layer_specs(cfg: dict, sliding: bool, dense: bool) -> dict:
    """{leaf: (shape, mean, std, float32_always)} of one layer, reference layout."""
    h = cfg["hidden_size"]
    hq, hkv, d, dv = heads(cfg, sliding)
    specs = {
        "norm1": ((h,), 1.0, 0.1, False), "norm2": ((h,), 1.0, 0.1, False),
        "wq": ((h, hq * d), 0.0, 0.02, False), "wk": ((h, hkv * d), 0.0, 0.02, False),
        "wv": ((h, hkv * dv), 0.0, 0.02, False), "wo": ((hq * dv, h), 0.0, 0.02, False),
    }
    if sliding and cfg["add_swa_attention_sink_bias"]:
        specs["sinks"] = ((hq,), math.log(cfg["sliding_window"]), 1.0, True)
    if dense:
        f = cfg["intermediate_size"]
        specs.update({"wg": ((h, f), 0.0, 0.02, False), "wu": ((h, f), 0.0, 0.02, False),
                      "wd": ((f, h), 0.0, 0.02, False)})
    else:
        held, f = int(cfg["n_routed_experts"]), cfg["moe_intermediate_size"]
        specs.update({
            "router": ((h, router_width(cfg)), 0.0, h ** -0.5, True),
            "bias": ((router_width(cfg),), 0.0, 0.01, True),
            "wg": ((held, h, f), 0.0, 0.02, False), "wu": ((held, h, f), 0.0, 0.02, False),
            "wd": ((held, f, h), 0.0, 0.02, False),
        })
    return specs


def top_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": ((v, h), 0.0, 0.02, False), "final_norm": ((h,), 1.0, 0.1, False),
            "head": ((h, v), 0.0, 0.0128, False)}


def chip_block(cfg: dict) -> int:
    """The routed experts one chip of the deployment holds."""
    chips = int(cfg.get("deployment", {}).get("chips_sharing_a_layer", 1))
    if router_width(cfg) % chips:
        raise ValueError(f"a router {router_width(cfg)} wide does not split over {chips} chips")
    return router_width(cfg) // chips


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_cfg, held: int, router: int, block: int, sliding: bool, dense: bool,
              dtype_name: str, program: bool):
    cfg = dict(frozen_cfg, n_routed_experts=held, published={"n_routed_experts": router})
    specs = layer_specs(cfg, sliding, dense)

    def draw(key):
        layer = _draw(key, specs, jnp.dtype(dtype_name))
        if not dense:
            layer["bias"] = selection_bias(jax.random.fold_in(key, len(specs)), router, block, specs["bias"][2])
        return layer_to_program(layer, dense) if program else layer

    return jax.jit(draw)


def _layer(seed: int, cfg: dict, i: int, dtype, program: bool) -> dict:
    fn = _layer_fn(_frozen(cfg), int(cfg["n_routed_experts"]), router_width(cfg), chip_block(cfg),
                   is_sliding(i, cfg), is_dense(i, cfg), jnp.dtype(dtype).name, program)
    return fn(jax.random.fold_in(seed_key(seed), 1 + i))


def make_layer(seed: int, cfg: dict, i: int, dtype=jnp.bfloat16) -> dict:
    """Layer `i` in the reference's layout, in `dtype`."""
    return _layer(seed, cfg, i, dtype, program=False)


def make_top(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    specs = top_specs(cfg)
    return jax.jit(lambda k: _draw(k, specs, jnp.dtype(dtype)))(jax.random.fold_in(seed_key(seed), 0))


def layer_to_program(p: dict, dense: bool) -> dict:
    """A reference-layout layer as the program's `layer_i` subtree."""
    attn = {"q_proj": {"kernel": p["wq"]}, "k_proj": {"kernel": p["wk"]},
            "v_proj": {"kernel": p["wv"]}, "o_proj": {"kernel": p["wo"]}}
    if "sinks" in p:
        attn["attention_sink_bias"] = p["sinks"]
    out = {"attn": attn, "input_norm": {"scale": p["norm1"]}, "post_norm": {"scale": p["norm2"]}}
    if dense:
        out["mlp"] = {"gate_up": jnp.concatenate([p["wg"], p["wu"]], -1), "down": p["wd"]}
    else:
        out["moe"] = {"router": p["router"], "e_score_correction_bias": p["bias"],
                      "w_gate_up": jnp.concatenate([p["wg"], p["wu"]], -1), "w_down": p["wd"]}
    return out


def make_program(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of `MiMoV2ForCausalLM`, layer by layer."""
    tree = top_to_program(make_top(seed, cfg, dtype))
    for i in range(int(cfg["num_hidden_layers"])):
        tree[f"layer_{i}"] = _layer(seed, cfg, i, dtype, program=True)
    return tree


def make_reference(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """{"top", "layers"} in float32, the values those of `dtype`: for the
    unit tests; at published widths `drivers/serve_mimo_v2.py` walks layer by layer."""
    return {"top": upcast(make_top(seed, cfg, dtype)),
            "layers": [upcast(make_layer(seed, cfg, i, dtype))
                       for i in range(int(cfg["num_hidden_layers"]))]}


def model_config(cfg: dict, **extra):
    """The configuration file as the program's `MiMoV2Config`. A published
    key whose other value this model has no code for must read as published."""
    from accelerate_tpu.models.mimo_v2 import MiMoV2Config

    off = [k for k, v in AS_PUBLISHED.items() if cfg.get(k) != v]
    if off or cfg.get("rope_scaling", {}).get("rope_type") != "default" \
            or cfg.get("routed_scaling_factor") not in (None, 1, 1.0) or cfg.get("n_shared_experts"):
        raise ValueError(f"the configuration asks for what MiMoV2ForCausalLM has not: "
                         f"{off or 'rope_scaling, routed_scaling_factor or a shared expert'}")
    if cfg["attention_chunk_size"] != cfg["sliding_window"]:
        raise ValueError("attention_chunk_size is read as the window: the two must agree")
    first, held = held_experts(cfg)
    n = int(cfg["num_hidden_layers"])
    keys = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim", "v_head_dim",
            "swa_num_attention_heads", "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
            "sliding_window", "partial_rotary_factor", "attention_value_scale",
            "add_swa_attention_sink_bias", "num_experts_per_tok", "layernorm_epsilon")
    return MiMoV2Config(
        **{k: cfg[k] for k in keys}, num_hidden_layers=n,
        hybrid_layer_pattern=tuple(int(t) for t in cfg["hybrid_layer_pattern"][:n]),
        moe_layer_freq=tuple(int(t) for t in cfg["moe_layer_freq"][:n]),
        rope_theta=float(cfg["rope_theta"]), swa_rope_theta=float(cfg["swa_rope_theta"]),
        n_routed_experts=router_width(cfg), experts_held=held, first_expert=first,
        n_positions=int(cfg["n_positions"]), dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]), **extra)
