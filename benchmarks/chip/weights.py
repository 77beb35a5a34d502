"""Random GPT-2 weights from a seed, made on the device in one jitted call.

The values are drawn in the reference's stacked layout (reference/gpt2.py);
`to_program` re-arranges the very same arrays into the tree
`accelerate_tpu.models.gpt2.GPT2LMHead` expects, and `from_program` goes
back, so both sides of `correct` hold identical numbers and neither takes
anything the other made."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number: the low 31 bits seed it, the rest fold in
    (a seed past 2**31 does not fit the 32 signed bits a key takes)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf_specs(cfg: dict) -> dict:
    """{leaf: (shape, mean, std)}; "blocks/<leaf>" for the stacked ones.
    Normal(0, 0.02) projections as in the GPT-2 release, but the residual
    outputs are not damped by 1/sqrt(2L) and the embeddings are four times
    smaller: under the release's own scales a random tied-head model repeats
    one token with a logit margin far above any rounding (my CPU runs at full
    width, PR 25), so no precision could be told from another. With these the
    greedy path keeps moving and near-ties are common. Norms and biases get
    small random values so that no path is multiplied by exactly 0 or 1."""
    e, layers, vocab, pos = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"], cfg["n_positions"]
    hid = cfg.get("mlp_ratio", 4) * e
    res, bias = 0.02, 0.002
    blocks = {
        "ln1_g": ((e,), 1.0, 0.1), "ln1_b": ((e,), 0.0, bias),
        "ln2_g": ((e,), 1.0, 0.1), "ln2_b": ((e,), 0.0, bias),
        "qkv_w": ((e, 3 * e), 0.0, 0.02), "qkv_b": ((3 * e,), 0.0, bias),
        "proj_w": ((e, e), 0.0, res), "proj_b": ((e,), 0.0, bias),
        "up_w": ((e, hid), 0.0, 0.02), "up_b": ((hid,), 0.0, bias),
        "down_w": ((hid, e), 0.0, res), "down_b": ((e,), 0.0, bias),
    }
    specs = {"wte": ((vocab, e), 0.0, 0.005), "wpe": ((pos, e), 0.0, 0.0025),
             "lnf_g": ((e,), 1.0, 0.1), "lnf_b": ((e,), 0.0, bias)}
    specs.update({f"blocks/{k}": ((layers,) + shape, mean, std)
                  for k, (shape, mean, std) in blocks.items()})
    return specs


def _draw(key, cfg: dict, dtype):
    tree: dict = {"blocks": {}}
    for i, (name, (shape, mean, std)) in enumerate(sorted(leaf_specs(cfg).items())):
        leaf = (mean + std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
        leaf = leaf.astype(dtype)
        if name.startswith("blocks/"):
            tree["blocks"][name.split("/", 1)[1]] = leaf
        else:
            tree[name] = leaf
    return tree


_PROGRAM_LEAVES = {  # stacked leaf -> path inside the program's block_i
    "ln1_g": ("ln_1", "scale"), "ln1_b": ("ln_1", "bias"),
    "ln2_g": ("ln_2", "scale"), "ln2_b": ("ln_2", "bias"),
    "qkv_w": ("attn", "qkv", "kernel"), "qkv_b": ("attn", "qkv", "bias"),
    "proj_w": ("attn", "proj", "kernel"), "proj_b": ("attn", "proj", "bias"),
    "up_w": ("mlp", "up", "kernel"), "up_b": ("mlp", "up", "bias"),
    "down_w": ("mlp", "down", "kernel"), "down_b": ("mlp", "down", "bias"),
}


def to_program(stacked: dict) -> dict:
    """The stacked tree as `GPT2LMHead`'s parameter tree."""
    layers = stacked["blocks"]["ln1_g"].shape[0]
    tree = {"wte": stacked["wte"], "wpe": stacked["wpe"],
            "ln_f": {"scale": stacked["lnf_g"], "bias": stacked["lnf_b"]}}
    for i in range(layers):
        blk: dict = {}
        for name, path in _PROGRAM_LEAVES.items():
            node = blk
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = stacked["blocks"][name][i]
        tree[f"block_{i}"] = blk
    return tree


def from_program(tree: dict) -> dict:
    """A tree in the program's layout (parameters, or Adam's moments of them)
    back in the stacked layout."""
    layers = sum(1 for k in tree if k.startswith("block_"))
    blocks = {}
    for name, path in _PROGRAM_LEAVES.items():
        rows = []
        for i in range(layers):
            node = tree[f"block_{i}"]
            for part in path:
                node = node[part]
            rows.append(node)
        blocks[name] = jnp.stack(rows)
    return {"wte": tree["wte"], "wpe": tree["wpe"], "lnf_g": tree["ln_f"]["scale"],
            "lnf_b": tree["ln_f"]["bias"], "blocks": blocks}


def make_stacked(seed: int, cfg: dict, dtype=jnp.float32) -> dict:
    return jax.jit(lambda k: _draw(k, cfg, dtype))(seed_key(seed))


def make_program(seed: int, cfg: dict, dtype=jnp.float32) -> dict:
    return jax.jit(lambda k: to_program(_draw(k, cfg, dtype)))(seed_key(seed))
