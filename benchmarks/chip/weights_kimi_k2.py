"""Random Kimi K2 weights from a seed, one layer at a time.

A layer is a pure function of (seed, layer index), drawn in the reference's
layout (reference/kimi_k2.py) in the configuration's `param_dtype`;
`layer_to_program` re-arranges the very same arrays into the tree
`accelerate_tpu.models.kimi_k2.KimiK2ForCausalLM` expects (gate and up side by
side, `kv_b` with its heads unfolded), so both sides of `correct` hold
identical numbers and neither takes anything the other made. At published
widths one expert layer's 12 held experts are 1.06 GB in bfloat16 and the dense
layer's MLP 0.79 GB: the program's tree is built layer by layer (one compiled
draw for each kind of layer), and the reference asks for one layer at a time
and upcasts it.

Scales (the configuration's `assumed.weights`): every projection normal(0,
0.02), the experts' among them (a held pick enters with a weight of about
2.827 / 8, so one pick is about a tenth of the residual stream: a near-tie at
the router's eighth place that bfloat16 activations flip against the float32
reference then moves the logits by hundredths, not by tenths); the router
0.0118, which spreads its 384 logits to a standard deviation of about 1: the
eight chosen sigmoid scores then lie between about 0.88 and 0.95 and the eight
weights fall from about 0.131 to 0.122 of their sum (a sigmoid router that
reads zero-mean inputs has no steeper fall-off; a trained one's inputs carry a
mean); `e_score_correction_bias` normal(0, 0.01): the scores around the eighth
place lie about 0.006 apart, so the bias moves the choice at the last place or
two of most tokens and never the whole set; embedding 0.02 and the untied head
0.012 (logits of standard deviation 1.0 over the vocabulary: near-ties common,
greedy streams keep moving); norm weights 1 +- 0.1. The router and the bias
stay float32 in every layout."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flops_kimi_k2 import dense_layers, router_width
from weights import seed_key
from weights_qwen3_next import _draw, _frozen, upcast  # noqa: F401  (upcast: the driver takes it here)


def held_experts(cfg: dict) -> tuple[int, int]:
    """(first, count) of the routed experts this chip holds."""
    return int(cfg.get("deployment", {}).get("first_expert", 0)), int(cfg["n_routed_experts"])


def is_dense(i: int, cfg: dict) -> bool:
    return i < dense_layers(cfg)


def layer_specs(cfg: dict, dense: bool) -> dict:
    """{leaf: (shape, mean, std, float32_always)} of one layer, reference layout."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    specs = {
        "norm1": ((h,), 1.0, 0.1, False), "norm2": ((h,), 1.0, 0.1, False),
        "wqa": ((h, ql), 0.0, 0.02, False), "qa_norm": ((ql,), 1.0, 0.1, False),
        "wqb": ((ql, heads * (nope + rope)), 0.0, 0.02, False),
        "wkva": ((h, rank + rope), 0.0, 0.02, False), "kva_norm": ((rank,), 1.0, 0.1, False),
        "wkvb": ((rank, heads * (nope + dv)), 0.0, 0.02, False),
        "wo": ((heads * dv, h), 0.0, 0.02, False),
    }
    if dense:
        f = cfg["intermediate_size"]
        specs.update({"wg": ((h, f), 0.0, 0.02, False), "wu": ((h, f), 0.0, 0.02, False),
                      "wd": ((f, h), 0.0, 0.02, False)})
    else:
        held, f = int(cfg["n_routed_experts"]), cfg["moe_intermediate_size"]
        fs = f * int(cfg["n_shared_experts"])
        specs.update({
            "router": ((h, router_width(cfg)), 0.0, 0.0118, True),
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
            "head": ((h, v), 0.0, 0.012, False)}


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen_cfg, held: int, router: int, dense: bool, dtype_name: str, program: bool):
    cfg = dict(frozen_cfg, n_routed_experts=held, published={"n_routed_experts": router})
    specs = layer_specs(cfg, dense)

    def draw(key):
        layer = _draw(key, specs, jnp.dtype(dtype_name))
        return layer_to_program(layer, cfg, dense) if program else layer

    return jax.jit(draw)


def _layer(seed: int, cfg: dict, i: int, dtype, program: bool) -> dict:
    fn = _layer_fn(_frozen(cfg), int(cfg["n_routed_experts"]), router_width(cfg), is_dense(i, cfg),
                   jnp.dtype(dtype).name, program)
    return fn(jax.random.fold_in(seed_key(seed), 1 + i))


def make_layer(seed: int, cfg: dict, i: int, dtype=jnp.bfloat16) -> dict:
    """Layer `i` in the reference's layout, in `dtype`."""
    return _layer(seed, cfg, i, dtype, program=False)


def make_top(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    specs = top_specs(cfg)
    return jax.jit(lambda k: _draw(k, specs, jnp.dtype(dtype)))(jax.random.fold_in(seed_key(seed), 0))


def layer_to_program(p: dict, cfg: dict, dense: bool) -> dict:
    """A reference-layout layer as the program's `layer_i` subtree."""
    heads = cfg["num_attention_heads"]
    out = {
        "input_norm": {"scale": p["norm1"]}, "post_norm": {"scale": p["norm2"]},
        "attn": {"q_a_proj": {"kernel": p["wqa"]}, "q_a_norm": {"scale": p["qa_norm"]},
                 "q_b_proj": {"kernel": p["wqb"]}, "kv_a_proj": {"kernel": p["wkva"]},
                 "kv_a_norm": {"scale": p["kva_norm"]},
                 "kv_b_proj": p["wkvb"].reshape(p["wkvb"].shape[0], heads, -1),
                 "o_proj": {"kernel": p["wo"]}},
    }
    if dense:
        out["mlp"] = {"gate_up": jnp.concatenate([p["wg"], p["wu"]], -1), "down": p["wd"]}
    else:
        out["moe"] = {"router": p["router"], "e_score_correction_bias": p["bias"],
                      "w_gate_up": jnp.concatenate([p["wg"], p["wu"]], -1), "w_down": p["wd"],
                      "shared_gate_up": jnp.concatenate([p["s_wg"], p["s_wu"]], -1),
                      "shared_down": p["s_wd"]}
    return out


def top_to_program(top: dict) -> dict:
    return {"embed": top["embed"], "final_norm": {"scale": top["final_norm"]}, "lm_head": top["head"]}


def make_program(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree of `KimiK2ForCausalLM`, layer by layer."""
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
    """The configuration file as the program's `KimiK2Config`."""
    from accelerate_tpu.models.kimi_k2 import KimiK2Config

    first, held = held_experts(cfg)
    keys = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "n_shared_experts", "routed_scaling_factor", "rms_norm_eps")
    rope = cfg["rope_scaling"]
    return KimiK2Config(
        **{k: cfg[k] for k in keys}, n_routed_experts=router_width(cfg), experts_held=held,
        first_expert=first, rope_theta=float(cfg["rope_theta"]), rope_factor=float(rope["factor"]),
        rope_original_max_position=int(rope["original_max_position_embeddings"]),
        rope_beta_fast=float(rope["beta_fast"]), rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]), rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        n_positions=int(cfg["n_positions"]), dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]), **extra)
