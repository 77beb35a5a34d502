"""Parameter-sharding inference: every parallelism strategy as a sharding plan.

This is the TPU-native replacement for the reference's per-engine wrapping code
paths (DDP wrap `accelerator.py:1458`, FSDP wrap `:1463-1507`, DeepSpeed ZeRO init
`:1632-1872`, Megatron TP rebuild `utils/megatron_lm.py:91-141`): under GSPMD all of
them collapse to *where each parameter array is placed on the mesh*:

  - DP            -> replicate params, shard the batch on ``data``
  - FSDP / ZeRO-3 -> additionally shard each param's largest divisible dim on
                     ``fsdp`` (XLA schedules the all-gather/reduce-scatter pairs
                     that DeepSpeed hand-codes)
  - ZeRO-1        -> params replicated, *optimizer state* sharded on ``fsdp``
  - TP            -> rule-based Megatron-style column/row splits on ``tensor``
  - SP/PP         -> activation shardings, handled in the step/kernels, not here

Rules are (path-regex -> PartitionSpec) pairs, first match wins, mirroring the
plugin surface of `FullyShardedDataParallelPlugin.auto_wrap_policy` at far lower
complexity.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec


def param_path_names(params: Any) -> Any:
    """Pytree of '/'-joined path strings, aligned with the params tree."""

    def _name(path) -> str:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            elif hasattr(p, "name"):
                parts.append(str(p.name))
            else:
                parts.append(str(p))
        return "/".join(parts)

    paths_leaves = jax.tree_util.tree_flatten_with_path(params)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    names = [_name(path) for path, _ in paths_leaves[0]]
    return jax.tree_util.tree_unflatten(treedef, names)


@dataclass
class ShardingRules:
    """Ordered (regex, PartitionSpec) rules mapping parameter paths to shardings.

    Example TP rules for a transformer block::

        ShardingRules(rules=[
            (r".*attention.*(query|key|value).*kernel", P(None, "tensor")),   # column
            (r".*attention.*out.*kernel",               P("tensor", None)),   # row
            (r".*mlp.*up.*kernel",                      P(None, "tensor")),
            (r".*mlp.*down.*kernel",                    P("tensor", None)),
        ])
    """

    rules: list[tuple[str, PartitionSpec]] = field(default_factory=list)

    def match(self, path: str) -> PartitionSpec | None:
        for pattern, spec in self.rules:
            if re.fullmatch(pattern, path) or re.search(pattern, path):
                return spec
        return None


def _fsdp_spec(shape: tuple[int, ...], existing: PartitionSpec | None, fsdp_size: int) -> PartitionSpec:
    """Add ``fsdp`` sharding on the largest dim divisible by the axis size that is
    not already sharded; replicate scalars/indivisible leaves.

    1-D leaves (biases, layernorm scales) are deliberately left replicated:
    sharding a vector the size of the embedding dim saves nothing but makes XLA
    propagate an embedding-dim sharding onto the (batch, seq, embed) activation
    gradients in the backward, which conflicts with their batch sharding and
    triggers involuntary full rematerialization (spmd_partitioner warnings).
    """
    used = set()
    parts: list = list(existing) if existing is not None else [None] * len(shape)
    while len(parts) < len(shape):
        parts.append(None)
    for p in parts:
        if p is None:
            continue
        for name in (p if isinstance(p, tuple) else (p,)):
            used.add(name)
    if "fsdp" in used or fsdp_size <= 1 or len(shape) < 2:
        return PartitionSpec(*parts)
    candidates = [
        (shape[i], i)
        for i in range(len(shape))
        if parts[i] is None and shape[i] % fsdp_size == 0 and shape[i] >= fsdp_size
    ]
    if not candidates:
        return PartitionSpec(*parts)
    _, dim = max(candidates)
    parts[dim] = "fsdp"
    return PartitionSpec(*parts)


def _sanitize_spec(spec: PartitionSpec, shape: tuple[int, ...], mesh: Mesh) -> PartitionSpec:
    """Make ``spec`` valid for a leaf of ``shape`` on ``mesh``, degrading to
    replication instead of erroring.

    Three repair steps, each dropping only the offending piece:
      - axis names the mesh does not carry are removed (a serving mesh without
        an ``fsdp`` axis treats an fsdp reference as degree 1 — no sharding);
      - a spec longer than the leaf's rank collapses to fully replicated (the
        scalar/1-D fallback: GPT-2 layernorm scales/biases matched by a 2-D
        rule must come out replicated, not raise in ``device_put``);
      - a dim whose size is not divisible by its axes' total degree is
        replicated (uneven param shards would silently pad).
    """
    if len(spec) > len(shape):
        return PartitionSpec(*([None] * len(shape)))
    parts: list = []
    for dim, entry in enumerate(spec):
        if entry is None:
            parts.append(None)
            continue
        names = tuple(n for n in (entry if isinstance(entry, tuple) else (entry,))
                      if n in mesh.shape)
        degree = math.prod(mesh.shape[n] for n in names)
        if not names or (degree > 1 and shape[dim] % degree != 0):
            parts.append(None)
        else:
            parts.append(names if len(names) > 1 else names[0])
    return PartitionSpec(*parts)


def infer_param_shardings(
    params: Any,
    mesh: Mesh,
    rules: ShardingRules | None = None,
    shard_params_on_fsdp: bool = True,
) -> Any:
    """Pytree of NamedShardings for a params pytree.

    TP rules apply first (by path); the ``fsdp`` axis is then folded into whatever
    dims remain free. With ``shard_params_on_fsdp=False`` the fsdp axis only shards
    optimizer state (ZeRO-1 semantics, reference `DeepSpeedPlugin.zero_stage==1`).

    Leaves no rule fits — or that a rule fits *invalidly* (spec rank above the
    leaf's, axes the mesh lacks, indivisible dims) — come out REPLICATED rather
    than raising: scalar and 1-D leaves like layernorm scales/biases must never
    block sharding the tree they ride in (see `_sanitize_spec`).
    """
    fsdp_size = mesh.shape.get("fsdp", 1)
    names = param_path_names(params)

    def _spec(name: str, leaf: Any) -> NamedSharding:
        base = rules.match(name) if rules is not None else None
        shape = tuple(getattr(leaf, "shape", ()))
        if base is not None:
            base = _sanitize_spec(base, shape, mesh)
        if shard_params_on_fsdp:
            spec = _fsdp_spec(shape, base, fsdp_size)
        else:
            spec = base if base is not None else PartitionSpec()
        return NamedSharding(mesh, _sanitize_spec(spec, shape, mesh))

    return jax.tree.map(_spec, names, params)


def shard_params(params: Any, shardings: Any) -> Any:
    """Place every leaf according to its NamedSharding (the actual ZeRO-3 shard
    moment — after this, each device holds only its slice)."""
    return jax.tree.map(lambda p, s: jax.device_put(p, s), params, shardings)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    from .mesh import data_axes

    return NamedSharding(mesh, PartitionSpec(data_axes(mesh)))


def constrain(x: Any, mesh: Mesh, spec: PartitionSpec) -> Any:
    """with_sharding_constraint helper usable inside jitted code."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------- KV
# Serving-side sharding rules: the engine's block pool and the contiguous rows
# an admission prefills are pytrees of a known leaf zoo (models/kv_cache.py):
#   cached_key / cached_value  [slots, max_len, kv_heads, head_dim] rows, or
#                              [num_blocks, block_tokens, kv_heads * head_dim]
#   key_scale  / value_scale   [slots, max_len, kv_heads] or
#                              [num_blocks, block_tokens, kv_heads]  (int8 storage)
#   cache_index                [slots]
# Tensor parallelism shards the HEAD dim (attention is embarrassingly parallel
# over heads — the collectives stay in the proj/down matmuls, exactly where the
# training-mesh rules already put them); data parallelism shards the SLOT dim
# so replicas decode disjoint slot ranges. The block pool shards heads only —
# any replica's slot may own or alias any block.


@dataclass(frozen=True)
class KVCacheSharding:
    """The NamedShardings a per-slot decode cache needs (hashable, so it
    can ride inside a frozen model config — `GPT2Config.kv_cache_sharding` —
    down to `models/kv_cache.decode_cache_update`'s in-jit constraints).

    With ``paged=True`` (`kv_cache_sharding(..., paged=True)`) ``kv`` describes the
    shared ``[num_blocks, block_tokens, kv_heads * head_dim]`` block pool
    instead of slot rows: heads are folded into the last dim so the stored
    layout tiles (20 x 64 trailing dims pad to 32 x 128 on a TPU, 3.2x), and a
    model-axis shard of that dim is whole heads, each a contiguous run of
    ``head_dim``. ``gathered`` carries the layout of the per-slot attended view
    the paged update assembles (`models/kv_cache.paged_decode_update`) — the
    contiguous rows' layout, so attention math shards identically for both.
    """

    kv: NamedSharding  # [slots, max_len, kv_heads, head_dim] buffers, or the 3-dim block pool
    scale: NamedSharding  # [slots, max_len, kv_heads] int8 absmax scales
    index: NamedSharding  # [slots] write cursor
    gathered: NamedSharding | None = None  # paged: [slots, span, kv_heads, head_dim] view


def _leaf_name(path) -> str | None:
    return getattr(path[-1], "key", getattr(path[-1], "name", None))


def kv_cache_sharding(
    mesh: Mesh,
    *,
    slots: int | None = None,
    batch_axes: tuple[str, ...] = ("data",),
    head_axis: str = "tensor",
    paged: bool = False,
) -> KVCacheSharding:
    """Build the `KVCacheSharding` for a per-slot cache on ``mesh``.

    The slot dim is sharded over ``batch_axes`` only when ``slots`` divides
    their total degree (pass ``slots=None`` to force replication of the slot
    dim — the admission prefill's fresh rows use the head sharding alone).

    ``paged=True`` describes the paged-KV layout instead: the block pool
    replicates blocks across the data axis (any replica's slot may own or
    alias any block — block ids ride as data, the table gather must be able
    to reach the whole pool) and shards heads on the model axis, which is the
    pool's folded last dim ``kv_heads * head_dim``; the per-slot
    write cursor and the gathered attended view keep the slot-dim rules.
    """
    batch_axes = tuple(n for n in batch_axes if mesh.shape.get(n, 1) > 1)
    dsize = math.prod(mesh.shape[n] for n in batch_axes) if batch_axes else 1
    row = batch_axes if (slots is not None and dsize > 1 and slots % dsize == 0) else None
    head = head_axis if mesh.shape.get(head_axis, 1) > 1 else None
    if paged:
        return KVCacheSharding(
            kv=NamedSharding(mesh, P(None, None, head)),
            scale=NamedSharding(mesh, P(None, None, head)),
            index=NamedSharding(mesh, P(row)),
            gathered=NamedSharding(mesh, P(row, None, head, None)),
        )
    return KVCacheSharding(
        kv=NamedSharding(mesh, P(row, None, head, None)),
        scale=NamedSharding(mesh, P(row, None, head)),
        index=NamedSharding(mesh, P(row)),
    )


def block_table_sharding(
    mesh: Mesh,
    *,
    slots: int | None = None,
    batch_axes: tuple[str, ...] = ("data",),
) -> NamedSharding:
    """Sharding for the paged engine's ``[slots, blocks_per_slot]`` block
    tables: the slot dim follows the cache's slot rule (sharded on the data
    axes only when divisible), the table entries themselves replicate —
    they are pool block IDS, data consumed by every tensor shard's gather."""
    batch_axes = tuple(n for n in batch_axes if mesh.shape.get(n, 1) > 1)
    dsize = math.prod(mesh.shape[n] for n in batch_axes) if batch_axes else 1
    row = batch_axes if (slots is not None and dsize > 1 and slots % dsize == 0) else None
    return NamedSharding(mesh, P(row, None))


def infer_cache_shardings(cache: Any, sharding: KVCacheSharding) -> Any:
    """Pytree of NamedShardings congruent with a decode cache pytree (or its
    `jax.eval_shape` ShapeDtypeStructs) — the engine's jit in/out_shardings for
    every donated cache argument."""

    def pick(path, leaf):
        # by name: a paged K/V leaf is 3-dim like a scale plane
        name = _leaf_name(path)
        if name == "cache_index":
            return sharding.index
        return sharding.scale if name in ("key_scale", "value_scale") else sharding.kv

    return jax.tree_util.tree_map_with_path(pick, cache)
