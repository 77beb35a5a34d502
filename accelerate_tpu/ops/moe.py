"""Mixture-of-Experts layer with expert parallelism.

Capability position: the reference's only MoE support is marking MoE classes as
ZeRO-3 leaves for DeepSpeed (SURVEY.md §2.4 EP row — "not implemented"); this is
the native TPU design. Switch/GShard-style top-k routing with static capacity:

  - routing, dispatch and combine are one-hot einsums — static shapes, MXU-
    friendly, no gather/scatter (the GSPMD MoE recipe).
  - expert-stacked weights [E, in, out] shard their leading dim over the
    ``tensor`` mesh axis (EP shares the TP axis, the common economical choice);
    XLA inserts the token all-to-alls from the shardings.
  - aux load-balancing loss (Switch Transformer) is sown into the
    ``intermediates`` collection; include ``"intermediates": {}`` in the
    variables passed to ``Accelerator.prepare`` and, *inside* ``loss_fn``,
    add ``collect_aux_losses(m.extra_state)`` to the task loss (it must be
    inside the differentiated function for the router to receive gradient).

Dropped tokens (over capacity) pass through the residual stream untouched, as in
GShard/Switch.

`held_experts_mlp` is the other kind of expert layer: one chip's share of
many small experts under expert parallelism. It is told which experts it
holds, routes over all of them, drops no token, and computes the held experts'
part of the result with a grouped matrix product over the picks sorted by
expert (`grouped_product`: the Pallas grouped matmul that ships with JAX on
the TPU, for a prefill's rows and a decode step's alike, `jax.lax.ragged_dot`
on any other backend), a long segment's in windows of the sorted held picks
(`expert_pass_rows`). `shared_expert_mlp` is
the always-on expert (behind a sigmoid gate, or ungated) that such models put
beside the routed ones. Two routers: `route_top_k` (softmax over all experts)
and `route_sigmoid_top_k` (sigmoid scores chosen under a selection bias,
within the best few groups of experts where the model limits the choice).
"""

from __future__ import annotations

import collections
from collections.abc import Mapping
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.sharding import ShardingRules


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    hidden_size: int = 768
    intermediate_size: int = 3072
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32


def build_dispatch_combine(
    expert_idx: jax.Array,  # [T, k] chosen experts per token
    gate_vals: jax.Array,  # [T, k] combine weights per choice
    num_experts: int,
    capacity: int,
    dtype: Any,
) -> tuple[jax.Array, jax.Array]:
    """Static-capacity dispatch/combine one-hots [T, E, C] (GShard recipe).

    Position of each token within its expert's capacity buffer comes from a
    masked cumsum; slots are processed in order, later slots offset by earlier
    slots' fill counts. Tokens beyond capacity are dropped (their dispatch and
    combine rows stay zero, so they pass through the residual stream).
    Shared by `MoEMLP` and `models.mixtral.MixtralSparseMoeBlock`.
    """
    n_tokens, k = expert_idx.shape
    E = num_experts
    dispatch = jnp.zeros((n_tokens, E, capacity), dtype=dtype)
    combine = jnp.zeros((n_tokens, E, capacity), dtype=jnp.float32)
    fill = jnp.zeros((E,), dtype=jnp.float32)
    for slot in range(k):
        onehot = jax.nn.one_hot(expert_idx[:, slot], E, dtype=jnp.float32)  # [T, E]
        within = jnp.cumsum(onehot, axis=0) - onehot  # earlier tokens, this slot
        pos_in_expert = jnp.sum((within + fill[None, :]) * onehot, axis=-1)  # [T]
        keep = pos_in_expert < capacity
        pos_oh = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity, dtype=jnp.float32)
        contrib = onehot[:, :, None] * pos_oh[:, None, :] * keep[:, None, None]
        dispatch = dispatch + contrib.astype(dtype)
        combine = combine + contrib * gate_vals[:, slot][:, None, None]
        fill = fill + jnp.sum(onehot * keep[:, None], axis=0)
    return dispatch, combine


def sow_aux_loss(module: nn.Module, aux: jax.Array) -> None:
    """Sum-reduce sow of a router aux loss into ``intermediates`` (stable pytree
    across steps; see the MoEMLP docstring for why sum-reduce, not append)."""
    module.sow(
        "intermediates",
        "aux_loss",
        aux,
        reduce_fn=lambda prev, new: prev + new,
        init_fn=lambda: jnp.zeros((), jnp.float32),
    )


class MoEMLP(nn.Module):
    """Top-k routed expert MLP over [batch, seq, hidden] activations."""

    config: MoEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        b, s, e = x.shape
        n_tokens = b * s
        E = cfg.num_experts
        capacity = max(int(cfg.capacity_factor * n_tokens * cfg.top_k / E), 1)

        xt = x.reshape(n_tokens, e)
        # router in fp32 for stable softmax
        router_logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                                 param_dtype=cfg.param_dtype, name="router")(xt.astype(jnp.float32))
        probs = jax.nn.softmax(router_logits, axis=-1)  # [T, E]

        # top-k expert choice per token
        gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)  # [T, k]
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        dispatch, combine = build_dispatch_combine(
            expert_idx, gate_vals, E, capacity, cfg.dtype
        )

        # expert-stacked weights: leading dim shards over the tensor axis (EP)
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (E, e, cfg.intermediate_size), cfg.param_dtype)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (E, cfg.intermediate_size, e), cfg.param_dtype)

        # dispatch -> expert compute -> combine (all einsums; static shapes)
        expert_in = jnp.einsum("tec,td->ecd", dispatch, xt.astype(cfg.dtype))
        h = jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(cfg.dtype))
        h = nn.gelu(h, approximate=True)
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(cfg.dtype))
        out = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), expert_out)

        # Switch aux loss: fraction-routed x mean-prob per expert. Sown with a
        # sum-reduce into a single scalar leaf: stable pytree structure across
        # steps (tuple-append sow would grow and force recompiles when threaded
        # as extra_state), yet repeated application of one instance (weight
        # sharing / recurrence) still accumulates every call's contribution —
        # the incoming collection is emptied per call by the apply wrapper, so
        # sums never leak across steps.
        me = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
        ce = jnp.mean(probs, axis=0)
        aux = cfg.aux_loss_weight * E * jnp.sum(me * ce)
        sow_aux_loss(self, aux)
        return out.reshape(b, s, e).astype(x.dtype)


def route_top_k(x: jax.Array, router: jax.Array, top_k: int) -> tuple[jax.Array, jax.Array]:
    """Softmax over ALL of the router's experts in float32, the ``top_k``
    largest, renormalised to sum 1: ``(weights [T, k], expert ids [T, k])``."""
    with jax.named_scope("moe_router"):
        logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(probs, top_k)
        return top / jnp.sum(top, axis=-1, keepdims=True), idx


def route_sigmoid_top_k(x: jax.Array, router: jax.Array, bias: jax.Array, top_k: int,
                        scaling: float = 1.0, n_group: int = 1,
                        topk_group: int = 1) -> tuple[jax.Array, jax.Array]:
    """Sigmoid scores ``s = sigmoid(x W_g)`` in float32 over ALL of the
    router's experts; the ``top_k`` with the largest ``s + bias`` are chosen
    (the bias chooses, it never weighs); their own scores, renormalised to sum
    1 and times ``scaling``, are the weights: ``(weights [T, k], ids [T, k])``.

    ``n_group > 1`` is DeepSeek-V3's group-limited choice: the experts lie in
    ``n_group`` groups of equal size, a group's score is the sum of its two
    largest ``s + bias``, only the ``topk_group`` best groups are kept, and
    the ``top_k`` are chosen inside them. Under expert parallelism a chip
    holds whole groups, so a token sends it several picks or none."""
    with jax.named_scope("moe_router"):
        logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        choose = scores + bias.astype(jnp.float32)
        if n_group > 1:
            grouped = choose.reshape(choose.shape[:-1] + (n_group, -1))
            group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
            _, kept = jax.lax.top_k(group_score, topk_group)
            keep = jax.nn.one_hot(kept, n_group, dtype=bool).any(-2)
            choose = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(choose.shape)
        _, idx = jax.lax.top_k(choose, top_k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
        return scaling * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20), idx


GMM_ROW_TILE = 128  # rows a tile of the Pallas grouped product: a few small groups share one
GMM_WEIGHT_TILE = 2048 * 1024  # elements of a group's matrix a tile: 4 MB in bfloat16, twice in VMEM
GMM_FLOPS_PER_BYTE = 240  # the v5e's bfloat16 peak over its HBM bandwidth: 197e12 / 819e9
# the intermediates one pass of `held_experts_mlp` may hold: just over the
# largest segment a serving cell ran in one pass before windows, Qwen3-Next's
# 4 x 1,536-token admit (61,440 picks of 17 KB, 1.07 GB)
EXPERT_PASS_BYTES = 1 << 30

# Grouped products by the path they took and their row count, and
# `held_experts_mlp`'s windowed calls as ("window", rows a window), counted
# where the path is decided: when a program is TRACED. The kernel's tests read
# it; on the chip the device trace names the kernel that ran (`%gmm.N` events
# in the benchmark's `device_ops`, a window's with `f32[rows,` in their line).
GROUPED_PRODUCT_TRACES: collections.Counter = collections.Counter()


def _dividing_tiles(d: int) -> list[int]:
    """``d`` itself and the multiples of 128 that divide it."""
    return [d] + [t for t in range(128, d, 128) if d % t == 0]


def grouped_tiling(k: int, n: int) -> tuple[int, int, int]:
    """``(row tile, tile_k, tile_n)`` of the Pallas grouped matmul for rows
    ``[R, k]`` times ``[G, k, n]``, read off the weights' shape alone. The
    kernel's time is the fetch of its weight tiles, so a tile is 4 MB (two
    of them and the rows' fit the 16 MiB of scoped VMEM): at most 2,048 of
    k by the rest of the budget. A tile that overhangs the matrix computes
    its masked k remainder and its n overhang on the MXU all the same, so
    where a row tile's product over the tiles' area would outlast the read
    of the weights, the tile is instead the largest whose sides DIVIDE the
    matrix (each the whole dimension or a multiple of 128; the larger
    ``tile_k`` on a tie): Ling 3.0 flash's ``[2560, 1536]`` in tiles of
    2,048 x 1,024 computed over 2.13 times its area. One rule for a
    prefill's thousands of rows and for a decode step's 3-5 a group: on the
    v5e it is within 2% of the best tile of a sweep at the three MoE
    serving cells' decode shapes and Ling 3.0 flash's admits (PERF.md
    section 6; the earlier one also over row tiles: a smaller one only
    makes more groups straddle two tiles and fetch their weights twice),
    but at Kimi K2's gate and up, which overhang 1.14 times and keep their
    tile: the sweep's best there, 7,168 x 256, ran 12% faster alone and
    moved the cell's decode step under 1%."""
    tile_k = min(k, 2048)
    tile_n = min(n, GMM_WEIGHT_TILE // tile_k)
    computed = -(-k // tile_k) * tile_k * -(-n // tile_n) * tile_n
    if GMM_ROW_TILE * computed > GMM_FLOPS_PER_BYTE * k * n:
        fits = [(tk * tn, tk, tn) for tk in _dividing_tiles(k) for tn in _dividing_tiles(n)
                if tk * tn <= GMM_WEIGHT_TILE]
        if fits:
            _, tile_k, tile_n = max(fits)
    return GMM_ROW_TILE, tile_k, tile_n


def grouped_product(rows: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    """``rows [R, K]`` sorted by group times each group's ``w [G, K, N]``,
    ``sizes [G]`` rows a group, float32 ``[R, N]``; rows past the last group
    hold anything. On the TPU every call, a prefill's and a decode step's,
    goes through the Pallas grouped matmul that ships with JAX (megablox
    `gmm`; trace events `%gmm.N`), tiled by `grouped_tiling`: it visits only
    the row tiles that hold a group and fetches a group's weight tiles once a
    visit, so each touched expert's weights are read once (twice where a
    group straddles two row tiles). XLA's `ragged_dot` takes 1.7 to 2.7
    times the touched weights' read at a decode step's 3-5 rows a group and
    2.5 to 3 times at a prefill's dozen. A row count the row tile does not
    divide is padded up to it: the pad lies past the last group. Any other
    backend keeps `jax.lax.ragged_dot`."""
    from ..utils.environment import on_tpu_platform

    n_rows, (_, k, n) = rows.shape[0], w.shape
    if not on_tpu_platform():
        GROUPED_PRODUCT_TRACES["ragged_dot", n_rows] += 1
        return jax.lax.ragged_dot(rows, w, sizes, preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    GROUPED_PRODUCT_TRACES["pallas", n_rows] += 1
    tiling = grouped_tiling(k, n)
    pad = -n_rows % tiling[0]
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w, sizes, jnp.float32, tiling=tiling)
    return out[:n_rows] if pad else out


def expert_pass_rows(hidden: int, width: int, dtype: Any) -> int:
    """Picks one pass of `held_experts_mlp` takes at most: the largest
    multiple of the row tile whose gathered rows, float32 gate-up, activation
    and float32 output fit `EXPERT_PASS_BYTES`. 18,688 at K-EXAONE's widths,
    16,896 at Kimi K2's, 46,592 at Ling 3.0 flash's, 61,568 at Qwen3-Next's."""
    item = jnp.dtype(dtype).itemsize
    per_pick = hidden * item + 2 * width * 4 + width * item + hidden * 4
    return max(GMM_ROW_TILE, EXPERT_PASS_BYTES // per_pick // GMM_ROW_TILE * GMM_ROW_TILE)


def _expert_products(rows: jax.Array, sizes: jax.Array, w_gate_up: jax.Array,
                     w_down: jax.Array) -> jax.Array:
    """``down_e(silu(gate_e r) * up_e r)`` of rows sorted by expert, float32."""
    two_f = w_gate_up.shape[-1]
    gate_up = grouped_product(rows, w_gate_up.astype(rows.dtype), sizes)
    act = (jax.nn.silu(gate_up[:, : two_f // 2]) * gate_up[:, two_f // 2:]).astype(rows.dtype)
    return grouped_product(act, w_down.astype(rows.dtype), sizes)


def held_experts_mlp(
    x: jax.Array,  # [T, hidden] tokens, compute dtype
    weights: jax.Array,  # [T, k] float32 combine weights (`route_top_k`)
    expert_idx: jax.Array,  # [T, k] int32 ids over the router's full width
    w_gate_up: jax.Array,  # [E_held, hidden, 2 * F]: gate columns, then up
    w_down: jax.Array,  # [E_held, F, hidden]
    first_expert: int = 0,  # this share holds experts [first, first + E_held)
    n_experts: int | None = None,  # the router's width; None: the experts held
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``sum_e p_e * down_e(silu(gate_e x) * up_e x)`` over each token's chosen
    experts that are held here; a pick that falls on an absent expert adds
    nothing (its chip adds it, and the exchange sums the parts). No capacity:
    the ``T * k`` picks are sorted by expert, absent ones last, and the held
    ones run through two grouped products whose group sizes are the experts'
    pick counts. Returns ``(out [T, hidden] float32, picks_held, experts_touched)``
    with the two int32 counts of this call.

    Where the ``T * k`` picks fit `expert_pass_rows` (what `EXPERT_PASS_BYTES`
    holds at these widths: every decode step's and every admit the engine
    ran whole before windows), they go through in this one pass, with no
    loop. Where they do not, the sorted held picks go through in windows in a
    `lax.while_loop` of ``ceil(held / C)`` turns, ``C`` being the held picks
    the segment is expected to bring (``T * k * E_held / n_experts``, up to
    the row tile) and at most that budget: a window gathers its ``C`` rows,
    runs the grouped products with the group sizes clipped to it (the Pallas
    grouped matmul fetches only the experts whose group is not empty, so a
    segment reads each held expert about once, twice where its group
    straddles two windows, where token chunks read them once a chunk) and
    adds each row times its weight into a float32 ``[T, hidden]`` sum by
    scatter. Each product is the one the single pass computes; only the
    order in which a token's picks are summed differs."""
    n_tokens, k = expert_idx.shape
    n_held, hidden, two_f = w_gate_up.shape
    # picks place by place, pick j of token t at j * T + t: the weighted sum
    # over a token's picks is then over the leading axis of [k, T, hidden],
    # which no tiling pads (k = 10 as a second-minor axis is relaid to 16)
    local = expert_idx.T.reshape(-1) - first_expert
    held = (local >= 0) & (local < n_held)
    group = jnp.where(held, local, n_held).astype(jnp.int32)  # absent picks sort last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=n_held + 1)[:n_held].astype(jnp.int32)
    cap = expert_pass_rows(hidden, two_f // 2, x.dtype)
    with jax.named_scope("moe_experts"):
        if n_tokens * k <= cap:
            y = _expert_products(x[order % n_tokens], sizes, w_gate_up, w_down)
            inverse = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
            # rows past the last group belong to absent experts: whatever the
            # grouped product left there is dropped, not weighted
            scale = jnp.where(held, weights.T.reshape(-1), 0.0)[:, None]
            out = jnp.where(held[:, None], y[inverse] * scale, 0.0).reshape(k, n_tokens, -1).sum(axis=0)
        else:
            expected = -(-n_tokens * k * n_held // ((n_experts or n_held) * GMM_ROW_TILE)) * GMM_ROW_TILE
            window = min(cap, expected)
            GROUPED_PRODUCT_TRACES["window", window] += 1
            out = _held_by_windows(x, weights.T.reshape(-1), order, sizes, jnp.sum(held),
                                   window, w_gate_up, w_down)
    return out, jnp.sum(held).astype(jnp.int32), jnp.sum(sizes > 0).astype(jnp.int32)


def _held_by_windows(x, place_weights, order, sizes, n_live, window, w_gate_up, w_down):
    """`held_experts_mlp`'s sum over the first ``n_live`` picks of ``order``
    (held, sorted by expert) in windows of ``window`` rows."""
    n_tokens = x.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    order = jnp.pad(order, (0, -order.shape[0] % window))  # whole windows; the pad lies past the live picks

    def one_window(carry):
        lo, acc = carry
        part = jax.lax.dynamic_slice(order, (lo,), (window,))
        clipped = jnp.clip(ends, lo, lo + window) - jnp.clip(starts, lo, lo + window)
        y = _expert_products(x[part % n_tokens], clipped, w_gate_up, w_down)
        # rows past the live picks hold anything: sent past the end, dropped
        token = jnp.where(lo + jnp.arange(window) < n_live, part % n_tokens, n_tokens)
        return lo + window, acc.at[token].add(y * place_weights[part][:, None], mode="drop")

    acc = jnp.zeros((n_tokens, x.shape[1]), jnp.float32)
    return jax.lax.while_loop(lambda c: c[0] < n_live, one_window, (jnp.int32(0), acc))[1]


def shared_expert_mlp(x: jax.Array, gate: jax.Array | None, w_gate_up: jax.Array,
                      w_down: jax.Array) -> jax.Array:
    """``sigmoid(x . gate) * down(silu(gate_proj x) * up_proj x)``: the expert
    every token passes through; ``gate=None`` is the ungated form (no sigmoid
    in front). ``w_gate_up`` is ``[hidden, 2 * F]``. float32."""
    f = w_gate_up.shape[-1] // 2
    gu = jnp.matmul(x, w_gate_up.astype(x.dtype), preferred_element_type=jnp.float32)
    act = (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(x.dtype)
    y = jnp.matmul(act, w_down.astype(x.dtype), preferred_element_type=jnp.float32)
    if gate is None:
        return y
    on = jax.nn.sigmoid(jnp.sum(x.astype(jnp.float32) * gate.astype(jnp.float32), -1, keepdims=True))
    return on * y


def collect_aux_losses(extra_state: Any) -> jax.Array:
    """Sum every sown ``aux_loss`` leaf out of a mutable-state pytree.

    Usage inside a loss_fn driven by `Accelerator.make_train_step`:
    ``loss = task_loss + collect_aux_losses(m.extra_state)`` (the BoundModel's
    ``extra_state`` holds the post-forward ``intermediates`` collection when
    the user passed one in their variables).
    """
    total = jnp.zeros((), jnp.float32)
    if not extra_state:
        return total
    inter = extra_state.get("intermediates", extra_state)
    for val in _aux_loss_leaves(inter):
        total = total + jnp.sum(jnp.asarray(val, jnp.float32))
    return total


def _aux_loss_leaves(tree: Any):
    """Yield every leaf stored under a key named 'aux_loss'."""
    if isinstance(tree, Mapping):
        for k, v in tree.items():
            if k == "aux_loss":
                yield from jax.tree.leaves(v)
            else:
                yield from _aux_loss_leaves(v)


def moe_sharding_rules() -> ShardingRules:
    """Expert parallelism: expert-stacked weights shard their leading (expert)
    dim over the tensor axis; the router stays replicated."""
    return ShardingRules(
        rules=[
            (r".*w_up", P("tensor", None, None)),
            (r".*w_down", P("tensor", None, None)),
            (r".*router.*", P(None, None)),
        ]
    )
