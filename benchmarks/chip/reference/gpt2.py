"""GPT-2 in plain `jax.numpy` and float32, from the published equations
(Radford et al. 2019; pre-norm blocks, learned positions, tanh GELU, tied
head), with next-token cross entropy, its gradients and AdamW (Loshchilov &
Hutter 2019, optax's defaults). No kernels, no cache, no batching tricks.

It imports nothing from the program. Matmuls run at `highest` precision: on
a TPU a float32 product is otherwise computed in bf16 passes.

Parameters are one dict with the layers stacked on a leading axis:
  wte [V, E], wpe [P, E], lnf_g [E], lnf_b [E], and under "blocks":
  ln1_g ln1_b ln2_g ln2_b [L, E]; qkv_w [L, E, 3E] qkv_b [L, 3E];
  proj_w [L, E, E] proj_b [L, E]; up_w [L, E, 4E] up_b [L, 4E];
  down_w [L, 4E, E] down_b [L, E].

`quant` turns it into the lower-precision control. "int8": every linear
layer's operands (and, on the way back, its gradients) pass through
vector-wise absmax int8, the kindest int8 scheme in use (LLM.int8). "fp8":
through per-tensor scaled float8, e4m3 for operands and e5m2 for gradients,
the usual fp8 recipe."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


# ------------------------------------------------------------------ precision
def _int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _fp8(x, dtype):
    """Through an 8-bit float and back, the tensor scaled to the format's range."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(x)) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@jax.custom_vjp
def _linear_fp8(x, w):
    return _mm(_fp8(x, jnp.float8_e4m3fn), _fp8(w, jnp.float8_e4m3fn))


def _linear_fp8_fwd(x, w):
    return _linear_fp8(x, w), (x, w)


def _linear_fp8_bwd(res, g):
    x, w = res
    gq = _fp8(g, jnp.float8_e5m2)
    dx = _mm(gq, _fp8(w, jnp.float8_e4m3fn).T)
    dw = _mm(_fp8(x, jnp.float8_e4m3fn).reshape(-1, x.shape[-1]).T, gq.reshape(-1, g.shape[-1]))
    return dx, dw


_linear_fp8.defvjp(_linear_fp8_fwd, _linear_fp8_bwd)


@jax.custom_vjp
def _linear_int8(x, w):
    return _mm(_int8(x, -1), _int8(w, 0))


def _linear_int8_fwd(x, w):
    return _linear_int8(x, w), (x, w)


def _linear_int8_bwd(res, g):
    x, w = res
    gq = _int8(g, -1)
    dx = _mm(gq, _int8(w, 1).T)
    x2, g2 = x.reshape(-1, x.shape[-1]), g.reshape(-1, g.shape[-1])
    dw = _mm(_int8(x2, 0).T, _int8(g2, 0))
    return dx, dw


_linear_int8.defvjp(_linear_int8_fwd, _linear_int8_bwd)


def linear(x, w, b, quant):
    if quant is None:
        y = _mm(x, w)
    elif quant == "int8":
        y = _linear_int8(x, w)
    elif quant == "fp8":
        y = _linear_fp8(x, w)
    else:
        raise ValueError(f"unknown precision {quant!r}")
    return y if b is None else y + b


# ---------------------------------------------------------------------- model
def layer_norm(x, g, b):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, n_head: int, quant=None):
    """One pre-norm block on x [B, S, E]; p holds one layer's leaves."""
    b, s, e = x.shape
    d = e // n_head
    h = layer_norm(x, p["ln1_g"], p["ln1_b"])
    qkv = linear(h, p["qkv_w"], p["qkv_b"], quant)
    q, k, v = (t.reshape(b, s, n_head, d).transpose(0, 2, 1, 3) for t in jnp.split(qkv, 3, -1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v, precision=HIGHEST)
    x = x + linear(att.transpose(0, 2, 1, 3).reshape(b, s, e), p["proj_w"], p["proj_b"], quant)
    h = layer_norm(x, p["ln2_g"], p["ln2_b"])
    return x + linear(gelu(linear(h, p["up_w"], p["up_b"], quant)), p["down_w"], p["down_b"], quant)


def embed(params, ids):
    return params["wte"][ids] + params["wpe"][: ids.shape[1]][None]


def head(params, x, quant=None):
    return linear(layer_norm(x, params["lnf_g"], params["lnf_b"]), params["wte"].T, None, quant)


def hidden(params, ids, n_head: int, quant=None, remat: bool = False):
    """The last block's output [B, S, E] for token ids [B, S]."""
    step = functools.partial(block, n_head=n_head, quant=quant)
    if remat:
        step = jax.checkpoint(step)
    x, _ = jax.lax.scan(lambda x, p: (step(x, p), None), embed(params, ids), params["blocks"])
    return x


def forward(params, ids, n_head: int, quant=None, remat: bool = False):
    """Logits [B, S, V] of token ids [B, S]."""
    return head(params, hidden(params, ids, n_head, quant, remat), quant)


def logits_at(params, ids, positions, n_head: int, quant=None):
    """Logits [B, T, V] at `positions` [B, T] of token ids [B, S]: the blocks
    run over whole rows, the head over those positions alone."""
    x = jnp.take_along_axis(hidden(params, ids, n_head, quant), positions[..., None], 1)
    return head(params, x, quant)


# ----------------------------------------------------------------------- loss
def token_losses(params, ids, n_head: int, quant=None):
    """Sum of next-token cross entropies over ids [B, S] and their count: the
    label of position i is token i + 1, the last position has none."""
    logits = forward(params, ids, n_head, quant, remat=True)[:, :-1]
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return -picked.sum(), picked.size


def loss_and_grads(params, ids, n_head: int, quant=None, rows_per_block: int = 1):
    """Mean loss over the batch and its gradient, computed in blocks of rows
    so that a full-size batch fits beside the state."""
    b = ids.shape[0]
    if b % rows_per_block:
        raise ValueError(f"batch {b} is not a multiple of rows_per_block {rows_per_block}")
    blocks = ids.reshape(b // rows_per_block, rows_per_block, -1)

    def one(carry, rows):
        total, grads = carry
        (s, _), g = jax.value_and_grad(
            lambda p: token_losses(p, rows, n_head, quant), has_aux=True)(params)
        return (total + s, jax.tree.map(jnp.add, grads, g)), None

    zero = jax.tree.map(jnp.zeros_like, params)
    (total, grads), _ = jax.lax.scan(one, (jnp.zeros((), jnp.float32), zero), blocks)
    count = b * (ids.shape[1] - 1)
    return total / count, jax.tree.map(lambda g: g / count, grads)


# ---------------------------------------------------------------------- AdamW
def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params), "t": jnp.zeros((), jnp.int32)}


def adamw_update(params, grads, state, hp: dict):
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    lr, wd = hp["learning_rate"], hp["weight_decay"]
    t = state["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    c1, c2 = 1 - b1 ** t.astype(jnp.float32), 1 - b2 ** t.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p), params, m, v)
    return new, {"m": m, "v": v, "t": t}


@functools.partial(jax.jit, static_argnames=("n_head", "quant", "rows_per_block", "hp"),
                   donate_argnums=(0, 1))
def _train_step(params, state, ids, *, n_head, quant, rows_per_block, hp):
    loss, grads = loss_and_grads(params, ids, n_head, quant, rows_per_block)
    norms = leaf_norms(grads)
    params, state = adamw_update(params, grads, state, dict(hp))
    return params, state, loss, norms


def _leaves(tree):
    """(name, leaf, stacked) in sorted-key order, the fused qkv leaves cut
    into their q, k and v thirds: under softmax the key's bias has no
    gradient, and a rule on a leaf's gradient can only see it on its own."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [str(getattr(k, "key", k)) for k in path]
        stacked = "blocks" in keys
        if keys[-1] in ("qkv_w", "qkv_b"):
            for part, piece in zip("qkv", jnp.split(leaf, 3, axis=-1)):
                yield keys[-1].replace("qkv", part), piece, stacked
        else:
            yield keys[-1], leaf, stacked


def leaf_norms(tree):
    """One L2 norm per leaf, a stacked leaf giving one per layer; a flat
    vector in `leaf_names` order."""
    parts = []
    for _, leaf, stacked in _leaves(tree):
        sq = leaf.astype(jnp.float32) ** 2
        parts.append(sq.reshape(leaf.shape[0], -1).sum(1) if stacked else sq.sum()[None])
    return jnp.sqrt(jnp.concatenate(parts))


def leaf_names(tree) -> list[str]:
    names = []
    for name, leaf, stacked in _leaves(tree):
        names += [f"block_{i}/{name}" for i in range(leaf.shape[0])] if stacked else [name]
    return names


def train(params, batches, n_head: int, hp: dict, quant=None, rows_per_block: int = 1):
    """Drive `len(batches)` AdamW steps from `params` (consumed). Returns the
    loss of each step, the per-leaf norms of the first gradient and the
    per-leaf norms of the parameters' change after the last step."""
    start = jax.tree.map(jnp.copy, params)
    state = adamw_init(params)
    losses, first = [], None
    frozen = tuple(sorted(hp.items()))
    for ids in batches:
        params, state, loss, norms = _train_step(
            params, state, jnp.asarray(ids), n_head=n_head, quant=quant,
            rows_per_block=rows_per_block, hp=frozen)
        losses.append(float(loss))
        if first is None:
            first = jax.device_get(norms)
    delta = jax.device_get(jax.jit(
        lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))(params, start))
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}
