"""Process/device state singletons.

Capability parity with reference `src/accelerate/state.py`:
  - ``PartialState``   (reference `state.py:115-813`)  — topology, rank accessors,
    barriers, process-slicing helpers, rank-gated execution.
  - ``AcceleratorState`` (reference `state.py:816-1131`) — adds mixed precision and
    the parallelism plan (here: the device mesh).
  - ``GradientState``  (reference `state.py:1134-1260`) — gradient-accumulation
    bookkeeping shared between Accelerator, dataloaders and optimizers.

TPU-native re-founding: there is no backend-selection matrix and no
``init_process_group`` rendezvous. A JAX process == one host; ``jax.distributed``
(coordinator on host 0, over DCN) replaces the TCP store; intra-host devices are
already visible. Collectives are either implicit (XLA inserts them from shardings
inside jit) or explicit host-level ops in `utils/operations.py`.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
from contextlib import contextmanager
from typing import Any, Callable, Iterator

import jax
import numpy as np

from .parallel.mesh import ParallelismConfig, build_mesh, data_axes, mesh_axis_size
from .utils.environment import parse_choice_from_env, parse_flag_from_env

logger = logging.getLogger(__name__)


class DistributedType(str):
    """Topology descriptor. Unlike the reference (which needs one enum value per
    engine — DEEPSPEED/FSDP/MEGATRON_LM/XLA...), SPMD subsumes every strategy, so
    only the topology is distinguished."""

    NO = "NO"
    SPMD = "SPMD"  # >1 device, single host
    MULTI_HOST = "MULTI_HOST"  # >1 JAX process


def _sagemaker_env_to_contract() -> None:
    """Translate SageMaker's cluster env (SM_HOSTS JSON list + SM_CURRENT_HOST,
    set inside every training container) into the JAX_COORDINATOR/PROCESS_ID
    contract — JAX has no SageMaker autodetect, and without this a
    num_machines>1 job would run N duplicate single-process trainings
    (reference role: `utils/launch.py` SageMaker env plumbing)."""
    if os.environ.get("ACCELERATE_TPU_USE_SAGEMAKER") != "true":
        return
    hosts_raw, current = os.environ.get("SM_HOSTS"), os.environ.get("SM_CURRENT_HOST")
    if not hosts_raw or not current or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        return
    import json as _json

    try:
        hosts = sorted(_json.loads(hosts_raw))
    except ValueError:
        logger.warning("SM_HOSTS is not JSON (%r); skipping cluster translation", hosts_raw)
        return
    if len(hosts) <= 1 or current not in hosts:
        return
    os.environ["JAX_COORDINATOR_ADDRESS"] = f"{hosts[0]}:8476"
    os.environ["JAX_NUM_PROCESSES"] = str(len(hosts))
    os.environ["JAX_PROCESS_ID"] = str(hosts.index(current))
    os.environ["ACCELERATE_TPU_NUM_PROCESSES"] = str(len(hosts))


def _in_multitask_slurm_step() -> bool:
    """True inside an `srun` task of a multi-task SLURM step (the only case
    where distributed init is needed and autodetectable). Discriminates on the
    STEP task count, not the allocation's: a plain `sbatch --ntasks=N` batch
    script also exports SLURM_NTASKS=N and SLURM_PROCID=0, but its single
    batch-step process would block forever waiting for N-1 peers."""
    if "SLURM_PROCID" not in os.environ or "SLURM_JOB_ID" not in os.environ:
        return False
    try:
        step_tasks = int(os.environ.get("SLURM_STEP_NUM_TASKS") or 1)
    except ValueError:
        return False
    return step_tasks > 1


def _maybe_init_distributed(initialization_timeout: int | None = None) -> None:
    """Initialize jax.distributed from the launcher env contract if present.

    Env contract (set by `commands/launch.py`): ``JAX_COORDINATOR_ADDRESS``,
    ``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``. On Cloud TPU pods, plain
    ``jax.distributed.initialize()`` autodetects everything from metadata; the env
    vars only override. Mirrors the role of reference `state.py:212` init_process_group.
    ``initialization_timeout`` comes from ``InitProcessGroupKwargs.timeout_seconds``
    (reference `InitProcessGroupKwargs.timeout` -> init_process_group).
    """
    _sagemaker_env_to_contract()
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = os.environ.get("JAX_NUM_PROCESSES") or os.environ.get("ACCELERATE_TPU_NUM_PROCESSES")
    if coord is None and nproc is None:
        if _in_multitask_slurm_step():
            # SLURM job step (reference: examples/slurm submit scripts feed
            # torch.distributed via MASTER_ADDR; here jax's built-in cluster
            # detection resolves coordinator/num_processes/process_id from the
            # SLURM_* env directly — no launcher arguments needed)
            if not jax.distributed.is_initialized():
                extra: dict[str, Any] = {}
                if initialization_timeout is not None:
                    extra["initialization_timeout"] = int(initialization_timeout)
                try:
                    jax.distributed.initialize(**extra)
                except (RuntimeError, ValueError) as e:
                    # the user explicitly ran a multi-task srun step; falling
                    # back to N duplicate single-process worlds is NOT benign —
                    # every task would claim main-process and write the same
                    # checkpoint/output paths. Refuse unless explicitly opted
                    # out (the opt-out keeps salvage-a-broken-cluster debugging
                    # possible).
                    from .utils.environment import parse_flag_from_env

                    if parse_flag_from_env("ACCELERATE_TPU_ALLOW_SLURM_FALLBACK"):
                        logger.warning(
                            "multi-task SLURM step detected but "
                            "jax.distributed.initialize failed (%s); "
                            "ACCELERATE_TPU_ALLOW_SLURM_FALLBACK=1 set — each "
                            "task now runs as an independent single-process "
                            "world", e,
                        )
                    else:
                        raise RuntimeError(
                            "multi-task SLURM step detected (SLURM_STEP_NUM_TASKS"
                            " > 1) but jax.distributed.initialize failed; "
                            "continuing would run N independent duplicate "
                            "single-process jobs that overwrite each other's "
                            "outputs. Set ACCELERATE_TPU_ALLOW_SLURM_FALLBACK=1 "
                            "to allow the single-process fallback anyway."
                        ) from e
        return
    # NOTE: must not touch jax.devices()/process_count() here — that would
    # initialize the backend single-process and make distributed init impossible
    if jax.distributed.is_initialized():
        return
    pid = os.environ.get("JAX_PROCESS_ID")
    extra: dict[str, Any] = {}
    if initialization_timeout is not None:
        extra["initialization_timeout"] = int(initialization_timeout)
    try:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(nproc) if nproc else None,
            process_id=int(pid) if pid is not None else None,
            **extra,
        )
    except (RuntimeError, ValueError) as e:
        # "already initialized" returned above; anything else means the
        # launcher promised a world this process could not join. Carrying on
        # as a world of one would run N duplicates that each claim
        # main-process and overwrite each other's outputs.
        raise RuntimeError(
            f"the launcher set a coordinator contract (JAX_COORDINATOR_ADDRESS="
            f"{coord!r}, num_processes={nproc!r}, JAX_PROCESS_ID={pid!r}) but "
            f"jax.distributed.initialize failed: {e}"
        ) from e


class PartialState:
    """Singleton holding topology facts and process-coordination primitives.

    Shared-state borg pattern (reference `SharedDict`, `state.py:83-110`): every
    instance shares one ``_shared_state`` dict, so constructing it anywhere returns
    the same initialized state.
    """

    _shared_state: dict[str, Any] = {}
    _lock = threading.Lock()

    def __init__(self, cpu: bool = False, **kwargs: Any):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        with self._lock:
            if self.initialized:
                return
            self._init(cpu=cpu, **kwargs)

    def _init(self, cpu: bool = False, initialization_timeout: int | None = None, **kwargs: Any) -> None:
        _maybe_init_distributed(initialization_timeout)
        self.debug = parse_flag_from_env("ACCELERATE_TPU_DEBUG_MODE")
        self._cpu = cpu
        self.devices = jax.devices()
        self.local_devices = jax.local_devices()
        self.process_index = jax.process_index()
        self.num_processes = jax.process_count()
        self.device = self.local_devices[0]
        self.fork_launched = parse_flag_from_env("FORK_LAUNCHED", False)
        if self.num_processes > 1:
            self.distributed_type = DistributedType.MULTI_HOST
        elif len(self.devices) > 1:
            self.distributed_type = DistributedType.SPMD
        else:
            self.distributed_type = DistributedType.NO

    # ------------------------------------------------------------------ topology
    @property
    def initialized(self) -> bool:
        return "devices" in self._shared_state

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    @property
    def local_process_index(self) -> int:
        # one JAX process per host: local index is always 0 for the process itself
        return 0

    @property
    def use_distributed(self) -> bool:
        return self.num_devices > 1 or self.num_processes > 1

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return True if self.num_processes == 1 else self.process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    # ------------------------------------------------------------ coordination
    def wait_for_everyone(self) -> None:
        """Cross-host barrier (reference `state.py:343`). Implemented as a named
        sync over DCN; a no-op in single-process topologies (devices under one
        process are synchronized by the runtime)."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("accelerate_tpu.wait_for_everyone")

    @contextmanager
    def main_process_first(self):
        """Main host runs the body first, others wait (reference `state.py:478`).
        Used for things like dataset preprocessing caches."""
        if not self.is_main_process:
            self.wait_for_everyone()
        yield
        if self.is_main_process:
            self.wait_for_everyone()

    @contextmanager
    def local_main_process_first(self):
        with self.main_process_first():
            yield

    @contextmanager
    def split_between_processes(
        self, inputs: list | tuple | dict | np.ndarray, apply_padding: bool = False
    ) -> Iterator[Any]:
        """Yield this process's slice of ``inputs`` (reference `state.py:389-476`).

        Lists/tuples/arrays are sliced on their first dimension; dicts are sliced
        per-value. With ``apply_padding`` the last process's share is padded (by
        repeating the final element) so all processes yield equal-length slices.
        """
        if self.num_processes == 1:
            yield inputs
            return

        def _slice(obj):
            length = len(obj)
            base, extra = divmod(length, self.num_processes)
            # first `extra` processes get one more element
            start = self.process_index * base + min(self.process_index, extra)
            stop = start + base + (1 if self.process_index < extra else 0)
            piece = obj[start:stop]
            if apply_padding and extra != 0:
                target = base + 1
                pad_n = target - len(piece)
                if pad_n > 0 and length > 0:
                    if isinstance(piece, np.ndarray):
                        piece = np.concatenate([piece, np.repeat(piece[-1:], pad_n, axis=0)])
                    else:
                        piece = list(piece) + [obj[-1]] * pad_n
            return piece

        if isinstance(inputs, dict):
            lengths = {len(v) for v in inputs.values()}
            if len(lengths) > 1:
                raise ValueError(f"All dict values must have equal length, got {lengths}.")
            yield {k: _slice(v) for k, v in inputs.items()}
        else:
            yield _slice(inputs)

    # ------------------------------------------------------------ rank gating
    def on_main_process(self, function: Callable) -> Callable:
        @functools.wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_local_main_process(self, function: Callable) -> Callable:
        @functools.wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)

        return wrapper

    def on_last_process(self, function: Callable) -> Callable:
        @functools.wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)

        return wrapper

    def on_process(self, function: Callable | None = None, process_index: int = 0) -> Callable:
        if function is None:
            return functools.partial(self.on_process, process_index=process_index)

        @functools.wraps(function)
        def wrapper(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)

        return wrapper

    def on_local_process(
        self, function: Callable | None = None, local_process_index: int = 0
    ) -> Callable:
        """Run only on the given LOCAL process index (reference `state.py:641`).
        One JAX process per host means local index 0 is the only inhabitant,
        so this gates to "every host runs it" vs "no host does"."""
        if function is None:
            return functools.partial(
                self.on_local_process, local_process_index=local_process_index
            )

        @functools.wraps(function)
        def wrapper(*args, **kwargs):
            if self.local_process_index == local_process_index:
                return function(*args, **kwargs)

        return wrapper

    @property
    def default_device(self):
        """The device computation lands on by default (reference
        `state.py:682` picks MPS/CUDA/...; here it is jax's first device —
        TPU when attached, else CPU)."""
        import jax

        return jax.devices()[0]

    def print(self, *args, **kwargs) -> None:
        """Print once per job (main host only) — reference `state.py:677`."""
        if self.is_local_main_process:
            print(*args, **kwargs)

    def shutdown(self) -> None:
        """Teardown (reference `destroy_process_group`, `state.py:793-801`)."""
        if self.num_processes > 1:
            jax.distributed.shutdown()

    @classmethod
    def _reset_state(cls) -> None:
        """Clear the singleton (test isolation — reference `state.py:808`)."""
        cls._shared_state.clear()

    def __repr__(self) -> str:
        return (
            f"PartialState(distributed_type={self.distributed_type}, "
            f"num_processes={self.num_processes}, num_devices={self.num_devices}, "
            f"process_index={self.process_index})"
        )


class AcceleratorState:
    """PartialState + the training plan: mixed precision and the device mesh.

    Reference `state.py:816-1131` promotes DistributedType per plugin engine; here
    the "plugins" collapse into a `ParallelismConfig` whose axes configure one mesh.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mixed_precision: str | None = None,
        cpu: bool = False,
        parallelism_config: ParallelismConfig | None = None,
        **kwargs: Any,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        self._partial = PartialState(cpu=cpu, **kwargs)
        if mixed_precision is None:
            mixed_precision = parse_choice_from_env("ACCELERATE_TPU_MIXED_PRECISION", "no")
        self.mixed_precision_mode = mixed_precision.lower()
        self.parallelism_config = parallelism_config or ParallelismConfig()
        self.mesh = build_mesh(self.parallelism_config, self._partial.devices)
        self.initialized_cpu = cpu

    @property
    def initialized(self) -> bool:
        return "mesh" in self._shared_state

    @property
    def mixed_precision(self) -> str:
        return self.mixed_precision_mode

    # --- DeepSpeed plugin registry (reference `state.py` deepspeed_plugins +
    # get/select accessors). Plugins here only shape optax/mesh config
    # (utils/deepspeed.py); the registry preserves the multi-plugin selection
    # API so reference scripts that switch plugins keep working.
    @property
    def deepspeed_plugin(self):
        """The currently selected DeepSpeed plugin, or None (reference
        `AcceleratorState.deepspeed_plugin`)."""
        plugins = self._shared_state.get("deepspeed_plugins") or {}
        return plugins.get(self._shared_state.get("active_deepspeed_plugin"))

    def register_deepspeed_plugins(self, plugins) -> None:
        """Accept one plugin or a dict of named plugins; the first becomes
        active (reference multi-plugin constructor contract)."""
        if plugins is None:
            return
        if not isinstance(plugins, dict):
            plugins = {"default": plugins}
        self._shared_state["deepspeed_plugins"] = plugins
        # re-registering under different names must not leave a stale active
        # name pointing outside the new registry (deepspeed_plugin would
        # silently return None)
        if self._shared_state.get("active_deepspeed_plugin") not in plugins:
            self._shared_state["active_deepspeed_plugin"] = next(iter(plugins))

    def get_deepspeed_plugin(self, name: str):
        """Look up a registered plugin by name (reference `get_deepspeed_plugin`)."""
        plugins = self._shared_state.get("deepspeed_plugins") or {}
        if name not in plugins:
            raise ValueError(
                f"No DeepSpeed plugin named {name!r}; registered: {sorted(plugins)}"
            )
        return plugins[name]

    def select_deepspeed_plugin(self, name: str) -> None:
        """Make the named plugin active (reference `select_deepspeed_plugin`)."""
        self.get_deepspeed_plugin(name)  # raises with the registry listed
        self._shared_state["active_deepspeed_plugin"] = name

    # Delegate topology to PartialState
    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(PartialState(), name)

    @property
    def data_parallel_size(self) -> int:
        return mesh_axis_size(self.mesh, *data_axes(self.mesh))

    @property
    def batch_sharding(self):
        """NamedSharding for the global batch (leading dim over data+fsdp axes)."""
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec(data_axes(self.mesh)))

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = False) -> None:
        cls._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()

    def __repr__(self) -> str:
        return (
            f"AcceleratorState(mesh={dict(self.mesh.shape)}, "
            f"mixed_precision={self.mixed_precision_mode!r})"
        )


class GradientState:
    """Gradient-accumulation bookkeeping (reference `state.py:1134-1260`).

    Shared between the Accelerator (sets num_steps / sync schedule), prepared
    dataloaders (push/pop + end_of_dataloader), optimizers (skip while
    accumulating) and schedulers (step only on sync).
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, gradient_accumulation_steps: int | None = None, **plugin_kwargs: Any):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references: list[Any] = [None]
            self.num_steps = gradient_accumulation_steps or 1
            self.adjust_scheduler = plugin_kwargs.get("adjust_scheduler", True)
            self.sync_with_dataloader = plugin_kwargs.get("sync_with_dataloader", True)
            self.step = 0
        elif gradient_accumulation_steps is not None:
            self.num_steps = gradient_accumulation_steps

    @property
    def initialized(self) -> bool:
        return "sync_gradients" in self._shared_state

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        """Number of extra (duplicated) samples in the final global batch, used by
        gather_for_metrics to drop padding (reference `state.py:1196`)."""
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _set_sync_gradients(self, sync: bool) -> None:
        self.sync_gradients = sync

    @property
    def is_xla_gradients_synced(self) -> bool:
        """Reference `GradientState.is_xla_gradients_synced`: whether the XLA
        gradient reduction already ran this step. Under SPMD the reduction is
        part of the compiled step itself, so this is exactly the sync
        boundary."""
        return self.sync_gradients

    def _add_dataloader(self, dataloader: Any) -> None:
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader: Any) -> None:
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1]

    @classmethod
    def _reset_state(cls) -> None:
        cls._shared_state.clear()

    def __repr__(self) -> str:
        return (
            f"GradientState(num_steps={self.num_steps}, sync_gradients={self.sync_gradients}, "
            f"step={self.step})"
        )
