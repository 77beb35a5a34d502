"""Device-mesh construction over ICI x DCN axes.

This replaces the reference's process-group machinery (`state.py:710-767` backend
selection + `init_process_group`): on TPU there is no NCCL/MPI rendezvous — a single
logical mesh over all chips is built once, and every parallelism strategy (DP, FSDP,
TP, SP, PP) is a sharding annotation over its axes rather than a separate engine.

Axis order follows `constants.MESH_AXIS_NAMES`: the leading axes change slowest
across the device list, so with multiple hosts/slices the ``data`` (and ``fsdp``)
axes naturally span DCN while ``tensor``/``sequence`` stay inside a slice on ICI —
the layout the scaling playbook prescribes (collectives for model parallelism ride
ICI; only gradient reductions cross DCN).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, fields

import jax
import numpy as np
from jax.sharding import Mesh

from ..utils.constants import MESH_AXIS_NAMES


@dataclass
class ParallelismConfig:
    """Degrees for each mesh axis. ``-1`` on ``data_parallel_size`` means "use all
    remaining devices" (the common case). Every strategy in the reference's plugin
    zoo (`DistributedDataParallelKwargs`, `FullyShardedDataParallelPlugin`,
    `MegatronLMPlugin` tp/pp degrees — reference `utils/dataclasses.py:974-2363`)
    maps onto one or more of these numbers.
    """

    data_parallel_size: int = -1
    fsdp_size: int = 1
    stage_size: int = 1  # pipeline stages
    sequence_size: int = 1  # sequence/context parallelism (ring attention)
    tensor_size: int = 1

    def axis_sizes(self, num_devices: int) -> dict[str, int]:
        sizes = {
            "data": self.data_parallel_size,
            "fsdp": self.fsdp_size,
            "stage": self.stage_size,
            "sequence": self.sequence_size,
            "tensor": self.tensor_size,
        }
        fixed = math.prod(v for v in sizes.values() if v != -1)
        n_infer = sum(1 for v in sizes.values() if v == -1)
        if n_infer > 1:
            raise ValueError("At most one mesh axis may be -1 (inferred).")
        if n_infer == 1:
            if num_devices % fixed != 0:
                raise ValueError(
                    f"Cannot infer axis size: {num_devices} devices not divisible by {fixed}."
                )
            sizes = {k: (num_devices // fixed if v == -1 else v) for k, v in sizes.items()}
        total = math.prod(sizes.values())
        if total != num_devices:
            raise ValueError(
                f"Mesh {sizes} covers {total} devices but {num_devices} are available."
            )
        return sizes

    @classmethod
    def from_kwargs(cls, **kwargs) -> "ParallelismConfig":
        valid = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in kwargs.items() if k in valid})

    @property
    def non_data_degree(self) -> int:
        return (
            max(self.fsdp_size, 1)
            * max(self.stage_size, 1)
            * max(self.sequence_size, 1)
            * max(self.tensor_size, 1)
        )


def build_mesh(
    config: ParallelismConfig | None = None,
    devices: list | None = None,
) -> Mesh:
    """Build the global device mesh.

    Device ordering: ``jax.devices()`` enumerates host-major, so reshaping with
    ``data`` as the leading axis places replica boundaries at host boundaries —
    gradient all-reduce crosses DCN only on the ``data``/``fsdp`` axes while
    ``tensor``/``sequence``/``stage`` collectives stay on ICI.
    """
    config = config or ParallelismConfig()
    if devices is None:
        devices = jax.devices()
    sizes = config.axis_sizes(len(devices))
    shape = tuple(sizes[name] for name in MESH_AXIS_NAMES)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXIS_NAMES)


def serving_mesh(
    data: int = 1,
    model: int = 1,
    devices: list | None = None,
) -> Mesh:
    """The serving engine's ``(data, model)`` mesh: ``data`` replicas each
    holding a ``model``-way tensor-parallel shard of the weights and KV pool
    (`serving/engine.py` ``mesh=``). The model axis IS the standard ``tensor``
    axis, so `gpt2_sharding_rules()` and the training-path TP annotations apply
    unchanged; the remaining axes are degree 1.

    Unlike `build_mesh` this takes the FIRST ``data * model`` devices instead
    of requiring an exact cover — a (2, 2) serving mesh on an 8-device host is
    a normal single-host-multi-device test topology.
    """
    data, model = int(data), int(model)
    if data < 1 or model < 1:
        raise ValueError(f"mesh degrees must be >= 1, got data={data} model={model}")
    if devices is None:
        devices = jax.devices()
    need = data * model
    if len(devices) < need:
        raise ValueError(
            f"serving mesh ({data}, {model}) needs {need} devices, "
            f"only {len(devices)} available"
        )
    sizes = {"data": data, "tensor": model}
    shape = tuple(sizes.get(name, 1) for name in MESH_AXIS_NAMES)
    dev_array = np.asarray(devices[:need]).reshape(shape)
    return Mesh(dev_array, MESH_AXIS_NAMES)


def mesh_axis_size(mesh: Mesh, *names: str) -> int:
    """Product of the sizes of the given axes."""
    return math.prod(mesh.shape[n] for n in names)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes over which the global batch is sharded (data + fsdp: FSDP shards both
    parameters and, like ZeRO, the batch — each fsdp group member sees distinct data)."""
    return tuple(n for n in ("data", "fsdp") if mesh.shape.get(n, 1) >= 1)


def active_batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """The non-trivial batch-sharding axes — the shard_map in_spec form used by
    the ring/ulysses/flash wrappers (one definition so they cannot drift)."""
    return tuple(n for n in ("data", "fsdp") if mesh.shape.get(n, 1) > 1)


def pallas_shard_axes(batch: int) -> tuple[Mesh, tuple[str, ...] | None] | None:
    """Where a Pallas call must be wrapped in shard_map: ``(mesh, rows)`` for
    the live `AcceleratorState` mesh when it spans several devices and the
    trace is not already inside a shard_map over it, else None. XLA cannot
    partition a custom call — a bare one inside a multi-device jit fails to
    lower ("Mosaic kernels cannot be automatically partitioned") — so every
    kernel call site asks here. ``rows`` are the batch axes a leading dim of
    ``batch`` splits over, or None when it does not divide them (batch-1
    eval): the dim then stays whole in every shard, redundant but correct."""
    from ..state import AcceleratorState

    if "mesh" not in AcceleratorState._shared_state:  # initialized check only:
        return None  # a bare truthiness test could side-effect-init the singleton
    mesh = AcceleratorState().mesh
    if mesh.size <= 1 or inside_shard_map(mesh):
        return None  # nesting inside a pipeline/ring region would fail
    axes = active_batch_axes(mesh)
    divides = axes and batch % math.prod(mesh.shape[a] for a in axes) == 0
    return mesh, (axes if divides else None)


def inside_shard_map(mesh: Mesh) -> bool:
    """True when tracing inside a shard_map region that binds any of this
    mesh's axes — nesting another shard_map over the same mesh there would
    fail at trace time."""
    import jax

    for name in mesh.axis_names:
        try:
            jax.lax.axis_index(name)
            return True
        except Exception:
            continue
    return False
