"""Random Qwen3-Next weights from a seed, one layer at a time.

A layer is a pure function of (seed, layer index), drawn in the reference's
layout (reference/qwen3_next.py) in the configuration's `param_dtype`;
`layer_to_program` re-arranges the very same arrays into the tree
`accelerate_tpu.models.qwen3_next.Qwen3NextForCausalLM` expects (gate and up
projections side by side), so both sides of `correct` hold identical numbers
and neither takes anything the other made. At published widths one layer's
experts are 1.6 GB in bfloat16: the program's tree is built layer by layer
(one compiled draw for each kind of layer), and the reference asks for one
layer at a time and upcasts it.

Scales (the configuration's `assumed.weights`): projections normal(0, 0.02),
the routed experts' down projection 0.04 so that the routed part is a visible
share of the residual stream; the router 0.045, which spreads its 512 logits
to a standard deviation of about 2, so that a token's ten weights fall from
about 0.3 to 0.03 as a trained router's do (at 0.02 the ten are nearly equal,
and a near-tie at the tenth place, which bf16 activations flip against the
float32 reference, moves the output most); embedding and head 0.02: logits of
standard deviation 0.9 over the vocabulary, near-ties common, greedy streams
that keep moving. Zero-centred norms normal(0, 0.1), the plain norm 1 +- 0.1.
DeltaNet: `A_log` normal(0, 1) and `dt_bias` normal(-3, 1) give a per-token
decay between about 0.999 and 0.7 across heads; convolution taps 0.3. The
router, `A_log` and `dt_bias` stay float32 in every layout."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flops_qwen3_next import layer_kind, router_width
from weights import seed_key


def held_experts(cfg: dict) -> tuple[int, int]:
    """(first, count) of the routed experts this chip holds."""
    return int(cfg.get("deployment", {}).get("first_expert", 0)), int(cfg["num_experts"])


def layer_specs(cfg: dict, kind: str) -> dict:
    """{leaf: (shape, mean, std, float32_always)} of one layer, reference layout."""
    h, held, f = cfg["hidden_size"], int(cfg["num_experts"]), cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    specs = {
        "norm1": ((h,), 0.0, 0.1, False), "norm2": ((h,), 0.0, 0.1, False),
        "router": ((h, router_width(cfg)), 0.0, 0.045, True),
        "wg": ((held, h, f), 0.0, 0.02, False), "wu": ((held, h, f), 0.0, 0.02, False),
        "wd": ((held, f, h), 0.0, 0.04, False),
        "s_gate": ((h,), 0.0, 0.02, False),
        "s_wg": ((h, fs), 0.0, 0.02, False), "s_wu": ((h, fs), 0.0, 0.02, False),
        "s_wd": ((fs, h), 0.0, 0.02, False),
    }
    if kind == "full":
        hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        specs.update({
            "wq": ((h, hq * 2 * d), 0.0, 0.02, False), "wk": ((h, hkv * d), 0.0, 0.02, False),
            "wv": ((h, hkv * d), 0.0, 0.02, False), "wo": ((hq * d, h), 0.0, 0.02, False),
            "q_norm": ((d,), 0.0, 0.1, False), "k_norm": ((d,), 0.0, 0.1, False),
        })
    else:
        hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
        hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
        n_qkv = 2 * hk * dk + hv * dv
        specs.update({
            "wqkvz": ((h, n_qkv + hv * dv), 0.0, 0.02, False), "wba": ((h, 2 * hv), 0.0, 0.02, False),
            "conv_w": ((int(cfg["linear_conv_kernel_dim"]), n_qkv), 0.0, 0.3, False),
            "A_log": ((hv,), 0.0, 1.0, True), "dt_bias": ((hv,), -3.0, 1.0, True),
            "out_norm": ((dv,), 1.0, 0.1, False), "wout": ((hv * dv, h), 0.0, 0.02, False),
        })
    return specs


def top_specs(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": ((v, h), 0.0, 0.02, False), "final_norm": ((h,), 0.0, 0.1, False),
            "head": ((h, v), 0.0, 0.02, False)}


def _draw(key, specs: dict, dtype) -> dict:
    out = {}
    for i, (name, (shape, mean, std, keep32)) in enumerate(sorted(specs.items())):
        leaf = mean + std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = leaf if keep32 else leaf.astype(dtype)
    return out


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_cfg, held: int, router: int, kind: str, dtype_name: str, program: bool):
    cfg = dict(frozen_cfg, num_experts=held, published={"num_experts": router})
    specs = layer_specs(cfg, kind)

    def draw(key):
        layer = _draw(key, specs, jnp.dtype(dtype_name))
        return layer_to_program(layer, kind) if program else layer

    return jax.jit(draw)


def _layer(seed: int, cfg: dict, i: int, dtype, program: bool) -> dict:
    fn = _layer_fn(_frozen(cfg), int(cfg["num_experts"]), router_width(cfg), layer_kind(i, cfg),
                   jnp.dtype(dtype).name, program)
    return fn(jax.random.fold_in(seed_key(seed), 1 + i))


def make_layer(seed: int, cfg: dict, i: int, dtype=jnp.bfloat16) -> dict:
    """Layer `i` in the reference's layout, in `dtype`."""
    return _layer(seed, cfg, i, dtype, program=False)


def make_top(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    specs = top_specs(cfg)
    return jax.jit(lambda k: _draw(k, specs, jnp.dtype(dtype)))(jax.random.fold_in(seed_key(seed), 0))


def upcast(tree: dict) -> dict:
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def layer_to_program(p: dict, kind: str) -> dict:
    """A reference-layout layer as the program's `layer_i` subtree."""
    out = {
        "input_norm": {"scale": p["norm1"]}, "post_norm": {"scale": p["norm2"]},
        "moe": {"router": p["router"],
                "w_gate_up": jnp.concatenate([p["wg"], p["wu"]], -1), "w_down": p["wd"],
                "shared_gate": p["s_gate"],
                "shared_gate_up": jnp.concatenate([p["s_wg"], p["s_wu"]], -1),
                "shared_down": p["s_wd"]},
    }
    if kind == "full":
        out["attn"] = {"q_proj": {"kernel": p["wq"]}, "k_proj": {"kernel": p["wk"]},
                       "v_proj": {"kernel": p["wv"]}, "o_proj": {"kernel": p["wo"]},
                       "q_norm": {"scale": p["q_norm"]}, "k_norm": {"scale": p["k_norm"]}}
    else:
        out["delta"] = {"in_proj_qkvz": {"kernel": p["wqkvz"]}, "in_proj_ba": {"kernel": p["wba"]},
                        "conv_w": p["conv_w"], "A_log": p["A_log"], "dt_bias": p["dt_bias"],
                        "norm": p["out_norm"], "out_proj": {"kernel": p["wout"]}}
    return out


def top_to_program(top: dict) -> dict:
    return {"embed": top["embed"], "final_norm": {"scale": top["final_norm"]}, "lm_head": top["head"]}


def make_program(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of `Qwen3NextForCausalLM`, layer by layer."""
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
    """The configuration file as the program's `Qwen3NextConfig`."""
    from accelerate_tpu.models.qwen3_next import Qwen3NextConfig

    first, held = held_experts(cfg)
    keys = ("vocab_size", "hidden_size", "num_hidden_layers", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim", "partial_rotary_factor",
            "rms_norm_eps", "linear_num_key_heads", "linear_key_head_dim",
            "linear_num_value_heads", "linear_value_head_dim", "linear_conv_kernel_dim",
            "num_experts_per_tok", "moe_intermediate_size", "shared_expert_intermediate_size")
    return Qwen3NextConfig(
        **{k: cfg[k] for k in keys}, rope_theta=float(cfg["rope_theta"]),
        num_experts=router_width(cfg), experts_held=held, first_expert=first,
        n_positions=int(cfg["n_positions"]), dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]), **extra)
