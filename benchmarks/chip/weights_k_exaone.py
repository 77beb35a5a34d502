"""Random K-EXAONE weights from a seed, one layer at a time.

A layer is a pure function of (seed, layer index), drawn in the reference's
layout (reference/k_exaone.py) in the configuration's `param_dtype`;
`layer_to_program` re-arranges the very same arrays into the tree
`accelerate_tpu.models.k_exaone.KExaoneForCausalLM` expects (gate and up side
by side), so both sides of `correct` hold identical numbers and neither takes
anything the other made. At published widths one expert layer's 16 held
experts are 1.21 GB in bfloat16 and the dense layer's MLP 0.68 GB: the
program's tree is built layer by layer (one compiled draw for each kind of
layer), and the reference asks for one layer at a time and upcasts it.

Scales (the configuration's `assumed.weights`): every projection normal(0,
0.02), the experts' among them; the router 6144^-1/2 = 0.01276, and
`e_score_correction_bias` normal(0, 0.01): the scores around the eighth place
lie some 0.006 apart, as in the Kimi K2 cell, so the bias moves the last place
or two of a token's choice. The bias is drawn stratified by chip
(`selection_bias`): a plain draw gives the 16 held experts a mean bias that
moves by a quarter of its deviation from seed to seed, and with it the picks
they take (0.87 to 1.06 a token) and the experts a step touches, which the
trained bias keeps level between chips. Embedding 0.02 and the untied head 0.0128 (logits
of standard deviation about 1 over the vocabulary: near-ties common, greedy
streams keep moving); norm weights 1 +- 0.1, the QK-norms' among them. The
sublayers read the raw residual stream (no pre-norm), so the router's logits
spread with the stream's RMS, which grows a little with depth. The router and
the bias stay float32 in every layout."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flops_k_exaone import kv_width, router_width
from reference.k_exaone import is_dense
from weights import seed_key
from weights_kimi_k2 import top_to_program
from weights_qwen3_next import _draw, _frozen, upcast  # noqa: F401  (upcast: drivers/serve_k_exaone.py takes it here)

# keys of the published config whose other value would be mathematics this model does not have
AS_PUBLISHED = {"hidden_act": "silu", "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
                "norm_topk_prob": True, "tie_word_embeddings": False}


def held_experts(cfg: dict) -> tuple[int, int]:
    """(first, count) of the routed experts this chip holds."""
    return int(cfg.get("deployment", {}).get("first_expert", 0)), int(cfg["num_experts"])


def layer_specs(cfg: dict, dense: bool) -> dict:
    """{leaf: (shape, mean, std, float32_always)} of one layer, reference layout."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * d
    specs = {
        "norm_attn": ((h,), 1.0, 0.1, False), "norm_ffn": ((h,), 1.0, 0.1, False),
        "wq": ((h, q), 0.0, 0.02, False), "wk": ((h, kv_width(cfg)), 0.0, 0.02, False),
        "wv": ((h, kv_width(cfg)), 0.0, 0.02, False), "wo": ((q, h), 0.0, 0.02, False),
        "q_norm": ((d,), 1.0, 0.1, False), "k_norm": ((d,), 1.0, 0.1, False),
    }
    if dense:
        f = cfg["intermediate_size"]
        specs.update({"wg": ((h, f), 0.0, 0.02, False), "wu": ((h, f), 0.0, 0.02, False),
                      "wd": ((f, h), 0.0, 0.02, False)})
    else:
        held, f = int(cfg["num_experts"]), cfg["moe_intermediate_size"]
        fs = f * int(cfg["num_shared_experts"])
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
            "head": ((h, v), 0.0, 0.0128, False)}


def chip_block(cfg: dict) -> int:
    """The routed experts one chip of the deployment holds."""
    chips = int(cfg.get("deployment", {}).get("chips_sharing_a_layer", 1))
    if router_width(cfg) % chips:
        raise ValueError(f"a router {router_width(cfg)} wide does not split over {chips} chips")
    return router_width(cfg) // chips


def selection_bias(key, width: int, block: int, std: float):
    """The `width` mid-quantiles of normal(0, std), sorted into `block` strata,
    every chip's `block` experts taking one value of each stratum: the key
    orders each stratum over the chips and each chip's values over its
    experts, so that every chip holds nearly the same biases."""
    chips = width // block
    quantiles = std * jax.scipy.special.ndtri((jnp.arange(width) + 0.5) / width)
    k_strata, k_chips = jax.random.split(key)
    strata = jax.vmap(jax.random.permutation)(jax.random.split(k_strata, block), quantiles.reshape(block, chips))
    return jax.vmap(jax.random.permutation)(jax.random.split(k_chips, chips), strata.T).reshape(width)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_cfg, held: int, router: int, block: int, dense: bool, dtype_name: str, program: bool):
    cfg = dict(frozen_cfg, num_experts=held, published={"num_experts": router})
    specs = layer_specs(cfg, dense)

    def draw(key):
        layer = _draw(key, specs, jnp.dtype(dtype_name))
        if not dense:
            layer["bias"] = selection_bias(jax.random.fold_in(key, len(specs)), router, block, specs["bias"][2])
        return layer_to_program(layer, dense) if program else layer

    return jax.jit(draw)


def _layer(seed: int, cfg: dict, i: int, dtype, program: bool) -> dict:
    fn = _layer_fn(_frozen(cfg), int(cfg["num_experts"]), router_width(cfg), chip_block(cfg), is_dense(i, cfg),
                   jnp.dtype(dtype).name, program)
    return fn(jax.random.fold_in(seed_key(seed), 1 + i))


def make_layer(seed: int, cfg: dict, i: int, dtype=jnp.bfloat16) -> dict:
    """Layer `i` in the reference's layout, in `dtype`."""
    return _layer(seed, cfg, i, dtype, program=False)


def make_top(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    specs = top_specs(cfg)
    return jax.jit(lambda k: _draw(k, specs, jnp.dtype(dtype)))(jax.random.fold_in(seed_key(seed), 0))


def layer_to_program(p: dict, dense: bool) -> dict:
    """A reference-layout layer as the program's `layer_i` subtree."""
    out = {
        "attn": {"q_proj": {"kernel": p["wq"]}, "k_proj": {"kernel": p["wk"]},
                 "v_proj": {"kernel": p["wv"]}, "o_proj": {"kernel": p["wo"]},
                 "q_norm": {"scale": p["q_norm"]}, "k_norm": {"scale": p["k_norm"]}},
        "post_attention_norm": {"scale": p["norm_attn"]},
        "post_feedforward_norm": {"scale": p["norm_ffn"]},
    }
    if dense:
        out["mlp"] = {"gate_up": jnp.concatenate([p["wg"], p["wu"]], -1), "down": p["wd"]}
    else:
        out["moe"] = {"router": p["router"], "e_score_correction_bias": p["bias"],
                      "w_gate_up": jnp.concatenate([p["wg"], p["wu"]], -1), "w_down": p["wd"],
                      "shared_gate_up": jnp.concatenate([p["s_wg"], p["s_wu"]], -1),
                      "shared_down": p["s_wd"]}
    return out


def make_program(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of `KExaoneForCausalLM`, layer by layer."""
    tree = top_to_program(make_top(seed, cfg, dtype))
    for i in range(int(cfg["num_hidden_layers"])):
        tree[f"layer_{i}"] = _layer(seed, cfg, i, dtype, program=True)
    return tree


def make_reference(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """{"top", "layers"} in float32, the values those of `dtype`: for the
    unit tests; at published widths `drivers/serve_k_exaone.py` walks layer by layer."""
    return {"top": upcast(make_top(seed, cfg, dtype)),
            "layers": [upcast(make_layer(seed, cfg, i, dtype))
                       for i in range(int(cfg["num_hidden_layers"]))]}


def model_config(cfg: dict, **extra):
    """The configuration file as the program's `KExaoneConfig`. A published
    key whose other value this model has no code for must read as published."""
    from accelerate_tpu.models.k_exaone import KExaoneConfig

    off = [k for k, v in AS_PUBLISHED.items() if cfg.get(k) != v]
    rope = cfg["rope_parameters"]
    if off or rope.get("rope_type") != "default":
        raise ValueError(f"the configuration asks for what KExaoneForCausalLM has not: {off or 'rope_type'}")
    first, held = held_experts(cfg)
    n = int(cfg["num_hidden_layers"])
    keys = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim", "sliding_window",
            "num_experts_per_tok", "num_shared_experts", "routed_scaling_factor", "rms_norm_eps")
    return KExaoneConfig(
        **{k: cfg[k] for k in keys}, num_hidden_layers=n, layer_types=tuple(cfg["layer_types"][:n]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"][:n]), rope_theta=float(rope["rope_theta"]),
        num_experts=router_width(cfg), experts_held=held, first_expert=first,
        n_positions=int(cfg["n_positions"]), dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]), **extra)
