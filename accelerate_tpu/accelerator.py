"""The `Accelerator` facade — the single user entry point.

Capability parity: reference `src/accelerate/accelerator.py` (3597 LoC): `prepare`,
`backward`, `accumulate`/`no_sync`, `clip_grad_norm_`, collectives facade
(`gather`, `gather_for_metrics`, `reduce`, `pad_across_processes`), checkpoint
orchestration (`save_state`/`load_state`), trackers, trigger, autocast/profile.

TPU-native re-founding (SURVEY.md §7): the reference spends most of its complexity
compensating for eager per-rank execution (DDP buckets, no_sync, grad scaler
plumbing, per-backend collectives, rank-0 dispatch). Here one jitted SPMD step +
`NamedSharding` subsumes DDP/FSDP/TP/SP; "backward" builds and caches a jitted
value-and-grad; gradient accumulation is a buffer add between jitted calls (or a
fused in-jit microbatch loop via `make_train_step`, the fast path). The imperative
call sequence — forward/backward/clip/step/zero_grad — is preserved so reference
users keep their training-loop shape.
"""

from __future__ import annotations

import contextlib
import functools
import os
import weakref
from collections.abc import Mapping
from dataclasses import dataclass
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from .data_loader import DataLoaderShard, prepare_data_loader, skip_first_batches
from .optimizer import AcceleratedOptimizer
from .parallel.mesh import ParallelismConfig, data_axes
from .parallel.sharding import ShardingRules, infer_param_shardings, shard_params
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, DistributedType, GradientState, PartialState
from .utils import operations
from .utils.operations import convert_to_fp32, recursively_apply
from .utils.precision import DynamicGradScaler, GradScalerState, PrecisionPolicy
from .utils.random import split_rng_key


def _is_optax_tx(obj: Any) -> bool:
    return hasattr(obj, "init") and hasattr(obj, "update") and not hasattr(obj, "apply")


def _is_flax_module(obj: Any) -> bool:
    return hasattr(obj, "apply") and hasattr(obj, "init") and hasattr(obj, "bind")


class BoundModel:
    """A model with params bound — what user ``loss_fn(model, batch)`` receives.
    Calling it runs the forward with those exact params, so gradients flow.

    When the model carries mutable non-param collections (``batch_stats``,
    ``fp8_meta``, …), each call threads them through and keeps the updated
    state on ``self.extra_state`` for the train step to collect."""

    __slots__ = ("apply_fn", "params", "extra_state")

    def __init__(self, apply_fn: Callable, params: Any, extra_state: Any = None):
        self.apply_fn = apply_fn
        self.params = params
        self.extra_state = extra_state

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if self.extra_state is not None:
            out, self.extra_state = self.apply_fn(
                self.params, *args, extra_state=self.extra_state, **kwargs
            )
            return out
        return self.apply_fn(self.params, *args, **kwargs)


class PreparedModel:
    """Sharded, precision-managed model handle returned by `Accelerator.prepare`.

    Holds the *master* (fp32) parameter pytree placed on the mesh, the functional
    ``apply_fn(params, *args, **kwargs)``, and the sharding plan. Calling it runs
    an eagerly-jitted forward with the compute-dtype cast applied and outputs
    upcast to fp32 (the reference's autocast forward patch,
    `accelerator.py:1391-1402`, as a functional wrapper).
    """

    def __init__(
        self,
        apply_fn: Callable,
        params: Any,
        policy: PrecisionPolicy,
        mesh,
        shardings: Any,
        module: Any = None,
        extra_state: Any = None,
    ):
        self.apply_fn = apply_fn
        self.params = params
        self.policy = policy
        self.mesh = mesh
        self.shardings = shardings
        self.module = module  # the original user object, for unwrap_model
        self.extra_state = extra_state  # mutable non-param collections (replicated)
        self._acc_grads = None  # used only when no optimizer is prepared
        # keyed by (autocast_enabled, sorted static flag kwargs) — one compiled
        # forward per flag combination (see __call__)
        self._jit_forwards: dict[tuple, Callable] = {}
        self._hook = None  # hooks.ModelHook attachment point
        self.training = True

    @classmethod
    def _extract(cls, obj: Any) -> tuple[Callable, Any, Any, Any]:
        """Normalize user model objects to (apply_fn, params, extra_state, original).

        ``extra_state`` is non-None when a flax ``variables`` dict with mutable
        collections besides ``params`` (``batch_stats``, ``fp8_meta``, …) was
        passed; the returned apply_fn then accepts ``extra_state=`` and returns
        ``(out, new_extra_state)``.
        """
        if isinstance(obj, tuple) and len(obj) == 2:
            fn_or_module, params = obj
            if _is_flax_module(fn_or_module):
                module = fn_or_module
                extra_state = None
                if isinstance(params, Mapping) and "params" in params and len(params) > 1:
                    extra_state = {k: dict(v) if isinstance(v, Mapping) else v
                                   for k, v in params.items() if k != "params"}
                    params = params["params"]

                def apply_fn(p, *args, extra_state=None, **kwargs):
                    if extra_state is not None:
                        ins = dict(extra_state)
                        if "intermediates" in ins:
                            # write-only collection (flax sow convention): each
                            # call starts fresh so sown values never leak across
                            # steps when the state is threaded through
                            ins["intermediates"] = {}
                        out, mutated = module.apply(
                            {"params": p, **ins},
                            *args,
                            mutable=list(extra_state.keys()),
                            **kwargs,
                        )
                        return out, dict(mutated)
                    variables = {"params": p} if "params" not in p else p
                    return module.apply(variables, *args, **kwargs)

                return apply_fn, params, extra_state, module
            if callable(fn_or_module):
                if isinstance(params, Mapping) and "params" in params and len(params) > 1:
                    # plain-callable analogue of the flax mutable-collections
                    # contract: apply_fn(params, *args, extra_state=...) must
                    # return (out, new_extra_state). Used by the torch interop
                    # bridge for BN running stats + dropout rng.
                    extra_state = {k: v for k, v in params.items() if k != "params"}
                    return fn_or_module, params["params"], extra_state, fn_or_module
                return fn_or_module, params, None, fn_or_module
        raise TypeError(
            "Model must be a (flax_module, params) or (apply_fn, params) tuple, "
            f"got {type(obj)}. Initialize params first (module.init(key, sample))."
        )

    def bind(self, params: Any | None = None) -> BoundModel:
        return BoundModel(
            self.apply_fn, self.params if params is None else params, self.extra_state
        )

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        from .utils.precision import autocast_enabled

        cast = autocast_enabled()  # False inside autocast(AutocastKwargs(enabled=False))
        params = self.params
        if self._hook is not None:
            params, args, kwargs = self._hook.pre_forward(self, params, args, kwargs)
        # flag kwargs (deterministic=False, decode=True, return_hidden=True, …)
        # are Python control flow, not data: tracing them raises
        # TracerBoolConversionError inside the model. Route them around the jit
        # as part of the compilation key instead.
        static_kwargs = {
            k: v for k, v in kwargs.items() if isinstance(v, (bool, str)) or v is None
        }
        traced_kwargs = {k: v for k, v in kwargs.items() if k not in static_kwargs}
        key = (cast, tuple(sorted(static_kwargs.items())))
        if key not in self._jit_forwards:
            policy = self.policy
            has_state = self.extra_state is not None

            def fwd(params, state, args, kwargs, _cast=cast, _static=dict(static_kwargs)):
                p = policy.cast_to_compute(params) if _cast else params
                if has_state:
                    out, new_state = self.apply_fn(p, *args, extra_state=state, **kwargs, **_static)
                else:
                    out, new_state = self.apply_fn(p, *args, **kwargs, **_static), None
                return (policy.cast_to_output(out) if _cast else out), new_state

            self._jit_forwards[key] = jax.jit(fwd)
        out, new_state = self._jit_forwards[key](params, self.extra_state, args, traced_kwargs)
        if new_state is not None and self.training:
            # eval() forwards must be side-effect free: discard state mutations
            # (fp8 amax rolls, batch_stats updates) outside training mode
            self.extra_state = new_state
        if self._hook is not None:
            out = self._hook.post_forward(self, out)
        return out

    def eval(self) -> "PreparedModel":
        self.training = False
        return self

    def train(self, mode: bool = True) -> "PreparedModel":
        self.training = mode
        return self

    def state_dict(self) -> Any:
        return self.params

    def load_state_dict(self, params: Any) -> None:
        self.params = shard_params(params, self.shardings)


@dataclass
class ProjectConfiguration:
    """Where checkpoints/logs go (reference `utils/dataclasses.py:ProjectConfiguration`)."""

    project_dir: str | None = None
    logging_dir: str | None = None
    automatic_checkpoint_naming: bool = False
    total_limit: int | None = None
    iteration: int = 0
    # background disk writes for save_state: the call returns after the
    # device->host copy; bytes land before the next save/load/exit barrier
    # (SURVEY §7.6 async sharded save — beyond the reference's sync save)
    async_save: bool = False

    def __post_init__(self):
        if self.logging_dir is None:
            self.logging_dir = self.project_dir


@dataclass
class GradientAccumulationPlugin:
    """Reference `utils/dataclasses.py:GradientAccumulationPlugin`."""

    num_steps: int = 1
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True


class _RemovableHandle:
    """Deregistration handle for state pre-hooks (torch RemovableHandle role)."""

    _next_id = 0

    def __init__(self, registry: dict):
        self._registry = registry
        self.id = _RemovableHandle._next_id
        _RemovableHandle._next_id += 1

    def remove(self) -> None:
        self._registry.pop(self.id, None)


class Accelerator:
    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: str | None = None,
        gradient_accumulation_steps: int = 1,
        gradient_accumulation_plugin: GradientAccumulationPlugin | None = None,
        cpu: bool = False,
        parallelism_config: ParallelismConfig | None = None,
        sharding_rules: ShardingRules | None = None,
        log_with: str | list | None = None,
        project_dir: str | None = None,
        project_config: ProjectConfiguration | None = None,
        even_batches: bool = True,
        step_scheduler_with_optimizer: bool = True,
        rng_types: list[str] | None = None,
        dispatch_batches: bool | None = None,
        dataloader_config: Any = None,
        deepspeed_plugin: Any = None,
        fsdp_plugin: Any = None,
        megatron_lm_plugin: Any = None,
        kwargs_handlers: list[Any] | None = None,
        **kwargs: Any,
    ):
        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        # ---- engine plugins + kwargs handlers (reference accelerator.py:246-412):
        # resolve the migration-surface objects into the run plan BEFORE state
        # is built, so ds_config-derived precision/parallelism actually apply.
        (
            mixed_precision,
            gradient_accumulation_steps,
            parallelism_config,
            scaler_config,
            init_pg_timeout,
        ) = self._resolve_plugins(
            mixed_precision,
            gradient_accumulation_steps,
            parallelism_config,
            deepspeed_plugin,
            fsdp_plugin,
            megatron_lm_plugin,
            kwargs_handlers,
        )
        self._use_seedable_sampler = True
        self._use_stateful_dataloader = True
        if dataloader_config is not None:
            if split_batches or not even_batches or dispatch_batches is not None:
                raise ValueError(
                    "Pass dataloader behavior EITHER via dataloader_config= OR via the "
                    "split_batches/even_batches/dispatch_batches kwargs, not both."
                )
            split_batches = dataloader_config.split_batches
            even_batches = dataloader_config.even_batches
            dispatch_batches = dataloader_config.dispatch_batches
            self._use_seedable_sampler = dataloader_config.use_seedable_sampler
            self._use_stateful_dataloader = dataloader_config.use_stateful_dataloader
        if parallelism_config is None:
            # launcher env contract (commands/launch.py): dp,fsdp,stage,seq,tp
            env_par = os.environ.get("ACCELERATE_TPU_PARALLELISM")
            if env_par:
                dp, fsdp, stage, seq, tp = (int(x) for x in env_par.split(","))
                parallelism_config = ParallelismConfig(
                    data_parallel_size=dp, fsdp_size=fsdp, stage_size=stage,
                    sequence_size=seq, tensor_size=tp,
                )
        if gradient_accumulation_steps == 1:
            gradient_accumulation_steps = int(os.environ.get("ACCELERATE_TPU_GRAD_ACCUM_STEPS", 1))
        self.state = AcceleratorState(
            mixed_precision=mixed_precision,
            cpu=cpu,
            parallelism_config=parallelism_config,
            initialization_timeout=init_pg_timeout,
        )
        if self.deepspeed_plugin is not None:
            # reference keeps (possibly several, selectable) DS plugins on
            # AcceleratorState — preserve those accessors
            self.state.register_deepspeed_plugins(self.deepspeed_plugin)
        self.policy = PrecisionPolicy.from_mode(self.state.mixed_precision)
        if self.policy.requires_loss_scaling:
            self.scaler = DynamicGradScaler(**scaler_config) if scaler_config.pop("enabled", True) else None
        else:
            self.scaler = None
        if gradient_accumulation_plugin is not None:
            self.gradient_state = GradientState(
                gradient_accumulation_steps=gradient_accumulation_plugin.num_steps,
                adjust_scheduler=gradient_accumulation_plugin.adjust_scheduler,
                sync_with_dataloader=gradient_accumulation_plugin.sync_with_dataloader,
            )
        else:
            self.gradient_state = GradientState(gradient_accumulation_steps=gradient_accumulation_steps)
        self.device_placement = device_placement
        self.split_batches = split_batches
        self.even_batches = even_batches
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.rng_types = rng_types
        self.dispatch_batches = dispatch_batches
        self.sharding_rules = sharding_rules
        self.step = 0
        self.flag_tensor = None
        self._models: list[PreparedModel] = []
        self._save_state_pre_hooks: dict[int, Callable] = {}
        self._load_state_pre_hooks: dict[int, Callable] = {}
        self._optimizers: list[AcceleratedOptimizer] = []
        self._schedulers: list[AcceleratedScheduler] = []
        self._dataloaders: list[DataLoaderShard] = []
        self._custom_objects: list[Any] = []
        self._dummy_optim_map: dict[int, AcceleratedOptimizer] = {}
        # model -> (loss_fn -> jitted grad fn), both levels weakly keyed
        self._grad_fns: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
        self._train_steps: dict[tuple, Any] = {}
        self.trackers: list = []
        self._log_with = log_with

    def _resolve_plugins(
        self,
        mixed_precision,
        gradient_accumulation_steps,
        parallelism_config,
        deepspeed_plugin,
        fsdp_plugin,
        megatron_lm_plugin,
        kwargs_handlers,
    ):
        """Resolve engine plugins + kwargs handlers into the run plan — the
        reference ctor's plugin negotiation (`accelerator.py:246-412`), with the
        engines collapsed onto mesh axes. Env activation mirrors the reference's
        ``ACCELERATE_USE_DEEPSPEED``/``_FSDP``/``_MEGATRON_LM`` switches."""
        from .utils.dataclasses import (
            AutocastKwargs,
            DataLoaderConfiguration,
            DeepSpeedPlugin,
            DistributedDataParallelKwargs,
            FP8RecipeKwargs,
            FullyShardedDataParallelPlugin,
            GradScalerKwargs,
            InitProcessGroupKwargs,
            MegatronLMPlugin,
            ProfileKwargs,
        )
        from .utils.environment import parse_flag_from_env

        if deepspeed_plugin is None and parse_flag_from_env("ACCELERATE_TPU_USE_DEEPSPEED"):
            deepspeed_plugin = DeepSpeedPlugin(
                hf_ds_config=os.environ.get("ACCELERATE_TPU_DEEPSPEED_CONFIG_FILE") or None
            )
        if fsdp_plugin is None and parse_flag_from_env("ACCELERATE_TPU_USE_FSDP"):
            fsdp_plugin = FullyShardedDataParallelPlugin()
        if megatron_lm_plugin is None and parse_flag_from_env("ACCELERATE_TPU_USE_MEGATRON_LM"):
            megatron_lm_plugin = MegatronLMPlugin()
        engines = [p for p in (deepspeed_plugin, fsdp_plugin, megatron_lm_plugin) if p is not None]
        if len(engines) > 1:
            raise ValueError(
                "Pass at most one of deepspeed_plugin / fsdp_plugin / megatron_lm_plugin."
            )
        self.deepspeed_plugin = deepspeed_plugin
        self.fsdp_plugin = fsdp_plugin
        self.megatron_lm_plugin = megatron_lm_plugin

        self.ddp_handler = None
        self.profile_handler = None
        self.fp8_recipe_handler = None
        self.init_handler = None
        self.autocast_handler = None
        scaler_kwargs = None
        seen: set[type] = set()
        for handler in kwargs_handlers or []:
            if type(handler) in seen:
                raise ValueError(f"Duplicate kwargs handler of type {type(handler).__name__}.")
            seen.add(type(handler))
            if isinstance(handler, GradScalerKwargs):
                scaler_kwargs = handler
            elif isinstance(handler, DistributedDataParallelKwargs):
                self.ddp_handler = handler
            elif isinstance(handler, ProfileKwargs):
                self.profile_handler = handler
            elif isinstance(handler, FP8RecipeKwargs):
                self.fp8_recipe_handler = handler
            elif isinstance(handler, InitProcessGroupKwargs):
                self.init_handler = handler
            elif isinstance(handler, AutocastKwargs):
                self.autocast_handler = handler
            elif isinstance(handler, DataLoaderConfiguration):
                raise ValueError("Pass DataLoaderConfiguration as dataloader_config=, not a handler.")
            else:
                raise ValueError(f"Unsupported kwargs handler: {handler!r}")

        self.gradient_clipping = None
        if deepspeed_plugin is not None:
            if mixed_precision is None and getattr(deepspeed_plugin, "mixed_precision", None):
                mixed_precision = deepspeed_plugin.mixed_precision
            if gradient_accumulation_steps == 1 and deepspeed_plugin.gradient_accumulation_steps > 1:
                gradient_accumulation_steps = deepspeed_plugin.gradient_accumulation_steps
            if deepspeed_plugin.gradient_clipping is not None:
                self.gradient_clipping = deepspeed_plugin.gradient_clipping
            if parallelism_config is None:
                # stage >=3 -> fsdp over all devices; stages 0-2 -> the default
                # data mesh (opt-state sharding is a placement choice downstream)
                parallelism_config = deepspeed_plugin.to_parallelism_config(0)
        elif fsdp_plugin is not None and parallelism_config is None:
            parallelism_config = fsdp_plugin.to_parallelism_config()
        elif megatron_lm_plugin is not None and parallelism_config is None:
            parallelism_config = megatron_lm_plugin.to_parallelism_config()

        scaler_config: dict[str, Any] = {}
        if scaler_kwargs is not None:
            scaler_config = scaler_kwargs.to_dict()
        timeout = self.init_handler.timeout_seconds if self.init_handler is not None else None
        return mixed_precision, gradient_accumulation_steps, parallelism_config, scaler_config, timeout

    # ------------------------------------------------------------- topology
    @property
    def project_dir(self) -> str | None:
        """Reference `Accelerator.project_dir` (ProjectConfiguration passthrough)."""
        return self.project_configuration.project_dir

    @property
    def logging_dir(self) -> str | None:
        return self.project_configuration.logging_dir

    @property
    def partial_state(self) -> PartialState:
        return PartialState()

    @property
    def distributed_type(self) -> str:
        return self.partial_state.distributed_type

    @property
    def num_processes(self) -> int:
        return self.partial_state.num_processes

    @property
    def process_index(self) -> int:
        return self.partial_state.process_index

    @property
    def local_process_index(self) -> int:
        return self.partial_state.local_process_index

    @property
    def device(self):
        return self.partial_state.device

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def num_devices(self) -> int:
        return self.partial_state.num_devices

    @property
    def is_main_process(self) -> bool:
        return self.partial_state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.partial_state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.partial_state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @gradient_accumulation_steps.setter
    def gradient_accumulation_steps(self, value: int) -> None:
        self.gradient_state.num_steps = value

    @property
    def use_distributed(self) -> bool:
        return self.partial_state.use_distributed

    # ------------------------------------------------------------ rank gating
    def on_main_process(self, function: Callable) -> Callable:
        return self.partial_state.on_main_process(function)

    def on_local_main_process(self, function: Callable) -> Callable:
        return self.partial_state.on_local_main_process(function)

    def on_last_process(self, function: Callable) -> Callable:
        return self.partial_state.on_last_process(function)

    def on_process(self, function: Callable | None = None, process_index: int = 0) -> Callable:
        return self.partial_state.on_process(function, process_index)

    def on_local_process(
        self, function: Callable | None = None, local_process_index: int = 0
    ) -> Callable:
        """Run only on processes with this LOCAL index (reference
        `accelerator.py` on_local_process). One process owns each host here, so
        every process has local index 0: index 0 runs everywhere (each host's
        sole process), other indices nowhere."""
        if function is None:
            return functools.partial(self.on_local_process, local_process_index=local_process_index)

        @functools.wraps(function)
        def wrapper(*args: Any, **kwargs: Any):
            if self.partial_state.local_process_index == local_process_index:
                return function(*args, **kwargs)

        return wrapper

    def print(self, *args: Any, **kwargs: Any) -> None:
        self.partial_state.print(*args, **kwargs)

    def wait_for_everyone(self) -> None:
        self.partial_state.wait_for_everyone()

    @contextlib.contextmanager
    def main_process_first(self):
        with self.partial_state.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.partial_state.local_main_process_first():
            yield

    def split_between_processes(self, inputs: Any, apply_padding: bool = False):
        return self.partial_state.split_between_processes(inputs, apply_padding=apply_padding)

    # ---------------------------------------------------------------- prepare
    def prepare(self, *args: Any, device_placement: list[bool] | None = None) -> Any:
        """Prepare models/optimizers/dataloaders/schedulers in any order,
        returning them in the same order (reference `accelerator.py:1215`).

        Models are (module, params) or (apply_fn, params) tuples; optimizers are
        optax GradientTransformations; dataloaders are torch DataLoaders or batch
        iterables; schedulers expose ``step()``.
        """
        from .utils.deepspeed import DummyOptim, DummyScheduler

        result: list[Any] = [None] * len(args)
        model_indices: list[int] = []
        for obj in args:
            if self.verify_device_map(obj):
                raise ValueError(
                    "You can't train a model that has been loaded with a "
                    "multi-entry device map (big-model inference dispatch); "
                    "prepare the underlying params on a mesh instead."
                )
        # pass 1: models and dataloaders
        for i, obj in enumerate(args):
            if isinstance(obj, (DummyOptim, DummyScheduler)):
                continue  # passes 2/3
            if isinstance(obj, PreparedModel):
                result[i] = obj
                model_indices.append(i)
            elif _is_optax_tx(obj) or isinstance(obj, AcceleratedOptimizer):
                continue  # pass 2 (checked before the tuple case: an optax
                # GradientTransformation is itself a (init, update) namedtuple)
            elif (
                isinstance(obj, tuple)
                and len(obj) == 2
                and (callable(obj[0]) or _is_flax_module(obj[0]))
                and not callable(obj[1])
            ):
                result[i] = self.prepare_model(obj)
                model_indices.append(i)
            elif hasattr(obj, "step") and not hasattr(obj, "__iter__"):
                continue  # pass 3
            elif hasattr(obj, "__iter__"):
                result[i] = self.prepare_data_loader(obj)
            else:
                result[i] = obj
        # pass 2: optimizers attach to the (single) model. A DummyOptim's
        # sibling DummyScheduler (same prepare call) supplies the warmup/total
        # step counts for 'auto' resolution, matching the reference's joint
        # engine build (`accelerator.py:1741-1803`).
        dummy_sched = next((o for o in args if isinstance(o, DummyScheduler)), None)
        for i, obj in enumerate(args):
            if result[i] is not None:
                continue
            if isinstance(obj, DummyOptim):
                model = result[model_indices[0]] if model_indices else None
                result[i] = self._prepare_dummy_optim(obj, dummy_sched, model=model)
            elif _is_optax_tx(obj) or isinstance(obj, AcceleratedOptimizer):
                model = result[model_indices[0]] if model_indices else None
                result[i] = self.prepare_optimizer(obj, model=model)
        # pass 3: schedulers attach to optimizers
        for i, obj in enumerate(args):
            if result[i] is None:
                result[i] = self.prepare_scheduler(obj)
        return result[0] if len(result) == 1 else tuple(result)

    def prepare_model(self, model: Any, device_placement: bool | None = None) -> PreparedModel:
        """Shard+place parameters per the parallelism plan (reference
        `prepare_model`, `accelerator.py:1351-1593`, minus all engine wrapping)."""
        if isinstance(model, PreparedModel):
            return model
        apply_fn, params, extra_state, module = PreparedModel._extract(model)
        params = self.policy.cast_to_param(params)
        shardings = infer_param_shardings(
            params,
            self.mesh,
            rules=self.sharding_rules,
            shard_params_on_fsdp=self.state.parallelism_config.fsdp_size > 1
            or self.state.parallelism_config.tensor_size > 1,
        )
        if device_placement if device_placement is not None else self.device_placement:
            params = shard_params(params, shardings)
        prepared = PreparedModel(
            apply_fn,
            params,
            policy=self.policy,
            mesh=self.mesh,
            shardings=shardings,
            module=module,
            extra_state=extra_state,
        )
        self._models.append(prepared)
        return prepared

    def prepare_optimizer(
        self, optimizer: Any, model: PreparedModel | None = None, device_placement: bool | None = None
    ) -> AcceleratedOptimizer:
        if isinstance(optimizer, AcceleratedOptimizer):
            if optimizer.model is None and model is not None:
                optimizer.attach_model(model)
            self._optimizers.append(optimizer)
            return optimizer
        if model is None:
            if len(self._models) != 1:
                raise ValueError(
                    "prepare_optimizer needs `model=` when zero or multiple models are prepared."
                )
            model = self._models[0]
        if getattr(self.fp8_recipe_handler, "opt_level", "O1") == "O2":
            # a user-supplied optax transformation cannot be rewritten into the
            # fp8-state form — say so instead of silently ignoring the recipe
            from .ops.fp8 import ScaleByAdamFp8State  # noqa: F401

            probe = jax.eval_shape(optimizer.init, {"w": jnp.zeros((1,))})
            if not any(
                isinstance(s, ScaleByAdamFp8State)
                for s in jax.tree.leaves(
                    probe, is_leaf=lambda s: isinstance(s, ScaleByAdamFp8State)
                )
            ):
                import warnings

                warnings.warn(
                    "FP8RecipeKwargs(opt_level='O2') is configured, but the "
                    "optimizer passed to prepare() does not carry fp8 state. "
                    "Construct it with accelerate_tpu.adamw_fp8(..., "
                    "opt_level='O2') (or define it in a ds_config and use "
                    "DummyOptim) to get the low-precision moments."
                )
        prepared = AcceleratedOptimizer(optimizer, model=model, scaler=self.scaler)
        self._optimizers.append(prepared)
        return prepared

    def _prepare_dummy_optim(
        self, dummy, dummy_sched=None, model: PreparedModel | None = None
    ) -> AcceleratedOptimizer:
        """Compile a `DummyOptim` (+ sibling `DummyScheduler`) against the
        deepspeed_plugin's ds_config sections (reference swaps placeholders for
        engine-built objects in `_prepare_deepspeed`, `accelerator.py:1741-1803`)."""
        from .utils.deepspeed import build_ds_optimizer, build_ds_schedule

        plugin = self.deepspeed_plugin
        if plugin is None:
            raise ValueError(
                "DummyOptim requires a deepspeed_plugin (its optimizer comes from "
                "the ds_config 'optimizer' section)."
            )
        opt_cfg = getattr(plugin, "optimizer_config", None)
        sched_cfg = getattr(plugin, "scheduler_config", None)
        base_lr = dummy.lr
        if opt_cfg:
            p = opt_cfg.get("params", {})
            lr = p.get("lr")
            if lr is not None and lr != "auto":
                base_lr = float(lr)
        schedule_fn = build_ds_schedule(sched_cfg, dummy_sched, base_lr)
        fp8_opt_level = getattr(self.fp8_recipe_handler, "opt_level", "O1") or "O1"
        tx = build_ds_optimizer(opt_cfg, dummy, schedule_fn, fp8_opt_level=fp8_opt_level)
        prepared = self.prepare_optimizer(tx, model=model)
        prepared._ds_schedule_fn = schedule_fn
        prepared._ds_base_lr = base_lr  # the lr the optimizer actually uses
        self._dummy_optim_map[id(dummy)] = prepared
        return prepared

    def prepare_data_loader(self, data_loader: Any, device_placement: bool | None = None) -> DataLoaderShard:
        if isinstance(data_loader, DataLoaderShard):
            self._dataloaders.append(data_loader)
            return data_loader
        prepared = prepare_data_loader(
            data_loader,
            device_placement=device_placement if device_placement is not None else self.device_placement,
            split_batches=self.split_batches,
            rng_types=self.rng_types,
            dispatch_batches=self.dispatch_batches,
            even_batches=self.even_batches,
            use_seedable_sampler=self._use_seedable_sampler,
            mesh=self.mesh,
        )
        self._dataloaders.append(prepared)
        return prepared

    def prepare_scheduler(self, scheduler: Any) -> AcceleratedScheduler:
        if isinstance(scheduler, AcceleratedScheduler):
            return scheduler
        from .utils.deepspeed import DeepSpeedSchedulerView, DummyScheduler

        if isinstance(scheduler, DummyScheduler):
            opt = self._dummy_optim_map.get(id(scheduler.optimizer))
            if opt is None:
                opt = self._optimizers[-1] if self._optimizers else None
            if opt is None:
                raise ValueError(
                    "DummyScheduler must be prepared together with (or after) its "
                    "DummyOptim — the schedule is embedded in the built optimizer."
                )
            schedule_fn = getattr(opt, "_ds_schedule_fn", None)
            if schedule_fn is None:
                # constant-lr config: report the ds_config-RESOLVED lr the
                # optimizer actually runs at, not the placeholder's field
                base = getattr(opt, "_ds_base_lr", None)
                if base is None:
                    base = getattr(scheduler.optimizer, "lr", 0.0) if scheduler.optimizer else 0.0
                schedule_fn = lambda _count, _base=base: _base  # noqa: E731
            scheduler = DeepSpeedSchedulerView(schedule_fn, opt)
        prepared = AcceleratedScheduler(
            scheduler,
            optimizers=self._optimizers,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.split_batches,
        )
        self._schedulers.append(prepared)
        return prepared

    # ------------------------------------------------------- gradient machinery
    def _do_sync(self) -> None:
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            self.gradient_state._set_sync_gradients(
                (self.step % self.gradient_state.num_steps) == 0
            )

    @contextlib.contextmanager
    def accumulate(self, *models: Any):
        """Gradient-accumulation context (reference `accelerator.py:1050`):
        decides whether this batch is a sync boundary; `backward` scales the loss
        by 1/num_steps and `optimizer.step()`/`zero_grad()` no-op off-boundary."""
        self._do_sync()
        yield

    @contextlib.contextmanager
    def no_sync(self, model: Any = None):
        """Force-suppress gradient application inside the context (reference
        `no_sync`, `accelerator.py:935`). There is no per-rank allreduce to skip
        under SPMD; this only gates the optimizer."""
        prev = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(prev)

    def trigger_sync_in_backward(self, model: Any = None) -> None:
        """Make the NEXT backward apply gradients even though the step count
        says we're mid-accumulation (reference `trigger_sync_in_backward`,
        `accelerator.py:977`: sets DDP's require_backward_grad_sync after
        forwards under no_sync). Under SPMD there is no allreduce to re-arm —
        the equivalent observable effect is forcing the optimizer boundary."""
        self.gradient_state._set_sync_gradients(True)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables: list, even_batches: bool | None = None):
        """API parity with DDP's Join (reference `accelerator.py:1095-1182`).
        Uneven inputs cannot reach the jitted step (the loader pads to static
        shapes), so Join itself is coordination-free — but the ``even_batches``
        override IS honored: prepared loaders (and their shard samplers) run
        with the overridden value for the duration of the context, exactly like
        the reference's temporary `dl.batch_sampler.even_batches` swap."""
        overridden: list[tuple[Any, bool]] = []
        if even_batches is not None:
            for dl in self._dataloaders:
                for target in (dl, getattr(dl, "batch_sampler", None)):
                    if target is None or not hasattr(target, "even_batches"):
                        continue
                    if even_batches and getattr(target, "batch_size", 0) is None:
                        # same invariant as the BatchSamplerShard constructor:
                        # even_batches needs a declared batch_size to pad to —
                        # overriding past it would crash the trailing-group
                        # refill mid-iteration
                        import warnings

                        warnings.warn(
                            "join_uneven_inputs(even_batches=True) skipped a "
                            "loader whose batch sampler exposes no batch_size; "
                            "it keeps even_batches=False.",
                            stacklevel=2,
                        )
                        continue
                    overridden.append((target, target.even_batches))
                    target.even_batches = even_batches
            if not overridden:
                import warnings

                warnings.warn(
                    "join_uneven_inputs(even_batches=...) found no prepared "
                    "dataloaders to override; the argument has no effect.",
                    stacklevel=2,
                )
        try:
            yield
        finally:
            for target, prev in overridden:
                target.even_batches = prev

    def _get_grad_fn(self, loss_fn: Callable, model: PreparedModel) -> Callable:
        # Keyed on live object identity via weak references: an id()-keyed dict
        # can silently hand a new function a dead function's compiled program
        # after GC reuses the address. The cached value must NOT strongly
        # reference loss_fn (the key) — a value→key edge would pin the entry
        # forever — so `compute` closes over a weakref and the dict entry is
        # evicted by the weakref callback when loss_fn dies.
        per_model = self._grad_fns.get(model)
        if per_model is None:
            per_model = self._grad_fns[model] = {}
        try:
            probe = weakref.ref(loss_fn)
            cached = per_model.get(probe)  # hashes the referent — may also raise
        except TypeError:  # not weakref-able or not hashable: recompile each call
            probe, cached = None, None
        if cached is not None:
            return cached
        policy = self.policy
        apply_fn = model.apply_fn
        loss_ref = probe if probe is not None else (lambda fn=loss_fn: fn)

        def compute(params, mstate, batch, inner_scale, outer_scale):
            live_loss_fn = loss_ref()
            if live_loss_fn is None:  # pragma: no cover - entry evicted before call
                raise RuntimeError("loss_fn was garbage-collected before the step ran")

            def fwd(p):
                bound = BoundModel(apply_fn, policy.cast_to_compute(p), mstate)
                out = live_loss_fn(bound, batch)
                if isinstance(out, tuple):
                    loss, aux = out[0], out[1:]
                else:
                    loss, aux = out, ()
                # inner_scale rides INSIDE the reduced-precision backward (fp16
                # underflow protection, capped fp16-safe so a healthy cotangent
                # chain can't trip 65504); the outer remainder is applied to
                # the fp32 grads below. See DynamicGradScaler.split_scale.
                return (loss.astype(jnp.float32) * inner_scale, (loss, aux, bound.extra_state))

            (_, (loss, aux, new_mstate)), grads = jax.value_and_grad(fwd, has_aux=True)(params)
            grads = jax.tree.map(lambda g: g * outer_scale, grads)
            return convert_to_fp32(loss), aux, grads, new_mstate

        fn = jax.jit(compute)
        if probe is not None:
            key = weakref.ref(loss_fn, lambda ref, d=per_model: d.pop(ref, None))
            per_model[key] = fn
        return fn

    def backward(self, loss_fn: Callable, batch: Any = None, model: PreparedModel | None = None, **kwargs: Any):
        """Compute gradients of ``loss_fn(model, batch)`` and accumulate them.

        The reference's ``accelerator.backward(loss)`` rides torch's implicit
        tape; JAX has no tape, so the facade takes the loss *function* and returns
        the loss value. Gradients are scaled by 1/gradient_accumulation_steps
        (reference `accelerator.py:2199-2231`) and by the dynamic fp16 scale when
        active — applied to the fp32 grads after the backward, so the scaler's
        multiplier can never itself overflow the fp16 cotangent chain.
        """
        if model is None:
            if len(self._models) != 1:
                raise ValueError("backward() needs `model=` when zero or multiple models are prepared.")
            model = self._models[0]
        grad_fn = self._get_grad_fn(loss_fn, model)
        inv_k = 1.0 / self.gradient_state.num_steps
        inner = jnp.asarray(1.0, dtype=jnp.float32)
        outer = jnp.asarray(inv_k, dtype=jnp.float32)
        if self.scaler is not None:
            opt = self._optimizer_for(model)
            if opt is not None and opt.scaler_state is not None:
                inner, rest = self.scaler.split_scale(opt.scaler_state.scale)
                outer = rest * inv_k
        loss, aux, grads, new_mstate = grad_fn(
            model.params, model.extra_state, batch, inner, outer
        )
        model.extra_state = new_mstate
        opt = self._optimizer_for(model)
        if opt is not None:
            opt.accumulate_grads(grads)
        else:
            if model._acc_grads is None:
                model._acc_grads = grads
            else:
                model._acc_grads = jax.tree.map(jnp.add, model._acc_grads, grads)
        return (loss, *aux) if aux else loss

    def _optimizer_for(self, model: PreparedModel) -> AcceleratedOptimizer | None:
        for opt in self._optimizers:
            if opt.model is model:
                return opt
        return None

    def unscale_gradients(self, optimizer: AcceleratedOptimizer | None = None) -> None:
        """Explicit fp16 unscale (reference `accelerator.py:2293-2325`); normally
        `optimizer.step()` does this itself. Idempotent within one boundary —
        the optimizer's next real step clears the unscaled mark."""
        opts = [optimizer] if optimizer is not None else self._optimizers
        for opt in opts:
            if opt.scaler is not None and opt._acc_grads is not None and not opt._unscaled:
                grads, opt.scaler_state, finite = opt.scaler.unscale_and_update(
                    opt._acc_grads, opt.scaler_state
                )
                opt._acc_grads = grads
                opt.step_was_skipped = not bool(finite)
                opt._unscaled = True

    def clip_grad_norm_(self, parameters: Any = None, max_norm: float = 1.0, norm_type: float = 2.0):
        """Clip accumulated gradients by global norm, returning the pre-clip norm
        (reference `accelerator.py:2327-2382`). Unscales fp16 gradients first
        (reference behavior), computes ONE norm over every prepared optimizer's
        gradients together, and scales them all by the same factor. Runs jitted
        over the sharded grad pytrees — the cross-device reduction is XLA's."""
        if norm_type != 2.0:
            raise NotImplementedError("Only L2 global-norm clipping is supported.")
        self.unscale_gradients()
        with_grads = [opt for opt in self._optimizers if opt._acc_grads is not None]
        if not with_grads:
            return None
        clipped, total_norm = _clip_tree(
            tuple(opt._acc_grads for opt in with_grads), max_norm
        )
        for opt, tree in zip(with_grads, clipped):
            opt._acc_grads = tree
        return total_norm

    def clip_grad_value_(self, parameters: Any = None, clip_value: float = 1.0) -> None:
        for opt in self._optimizers:
            if opt._acc_grads is None:
                continue
            opt._acc_grads = jax.jit(
                lambda g: jax.tree.map(lambda x: jnp.clip(x, -clip_value, clip_value), g)
            )(opt._acc_grads)

    # ----------------------------------------------------- fused fast path
    def make_train_step(
        self,
        loss_fn: Callable,
        model: PreparedModel | None = None,
        optimizer: AcceleratedOptimizer | None = None,
        max_grad_norm: float | None = None,
        donate: bool = True,
        comm_hook: Any = None,
    ) -> Callable:
        """Build the fused jitted train step — the performance path.

        Returns ``step(batch) -> loss``. Internally: per-microbatch gradient
        computation with an in-buffer add, and on each sync boundary a single
        donated jitted update (grads mean + optional global-norm clip + optax
        update + apply). One device program per call; params/opt-state buffers are
        donated so HBM holds a single copy.

        ``comm_hook`` is the reference's DDP comm-hook analogue
        (`utils/dataclasses.py:117-213`): a `CommHookConfig` (or hook-name string:
        "fp16"/"bf16"/"power_sgd"/"batched_power_sgd") that compresses the
        cross-replica gradient reduction. Data-parallel only, like DDP comm hooks.
        With gradient accumulation the hook reduces every microbatch (DDP-without-
        no_sync semantics); the common ``k == 1`` path matches DDP exactly.

        fp16 note: overflow skip/backoff state stays on-device (no per-step
        sync), but a prepared *scheduler* must read ``step_was_skipped`` each
        boundary to mirror torch's skip-aware LR stepping — fp16 + scheduler
        therefore pays one host sync per boundary (torch's GradScaler does
        too); bf16 never does.
        """
        if model is None:
            model = self._models[0]
        if optimizer is None:
            optimizer = self._optimizer_for(model)
        if max_grad_norm is None:
            # ds_config gradient_clipping (reference applies it inside the engine)
            max_grad_norm = self.gradient_clipping
        if comm_hook is None and self.ddp_handler is not None:
            comm_hook = self.ddp_handler.to_comm_hook_config()
        policy = self.policy
        tx = optimizer.optimizer
        # NOTE: gradient_accumulation_steps is read LIVE from gradient_state at
        # every boundary (as a traced scalar, so changing it never recompiles) —
        # freezing it at build time silently mis-scaled the loss if the user
        # changed it after building the step.

        hook_cfg = None
        if comm_hook is not None:
            from .parallel.compression import CommHookConfig, init_comm_state, reduce_gradients

            if hasattr(comm_hook, "to_comm_hook_config"):  # DistributedDataParallelKwargs
                comm_hook = comm_hook.to_comm_hook_config()
            hook_cfg = CommHookConfig(comm_hook) if isinstance(comm_hook, str) else comm_hook
            if hook_cfg is not None and hook_cfg.comm_hook == "no":
                hook_cfg = None
        mesh = self.mesh
        n_replicas = 1
        if hook_cfg is not None:
            if mesh is None or mesh.shape.get("data", 1) <= 1:
                hook_cfg = None  # single replica: nothing to compress
            else:
                other = [a for a, s in mesh.shape.items() if a != "data" and s > 1]
                if other:
                    raise ValueError(
                        "comm_hook gradient compression is a data-parallel feature "
                        f"(like DDP comm hooks); mesh also shards axes {other}."
                    )
                n_replicas = mesh.shape["data"]

        scaler = optimizer.scaler if optimizer is not None else None

        def loss_and_grads(params, mstate, batch, inner):
            # mstate = mutable non-param collections (batch_stats/fp8_meta/…),
            # threaded through as value_and_grad aux — None for pure models.
            # ``inner`` is the fp16 loss-scale factor applied INSIDE the
            # reduced-precision backward (see DynamicGradScaler.split_scale);
            # 1.0 when no scaler is active.
            def f(p):
                bound = BoundModel(model.apply_fn, policy.cast_to_compute(p), mstate)
                out = loss_fn(bound, batch)
                loss = out[0] if isinstance(out, tuple) else out
                loss = loss.astype(jnp.float32)
                return loss * inner, (loss, bound.extra_state)

            (_, (loss, new_mstate)), grads = jax.value_and_grad(f, has_aux=True)(params)
            return loss, grads, new_mstate

        # lgr signature: (params, mstate, batch, comm_rep, comm_err, inner) ->
        #                (loss, grads, mstate, comm_rep, comm_err)
        def lgr_plain(params, mstate, batch, comm_rep, comm_err, inner):
            loss, grads, mstate = loss_and_grads(params, mstate, batch, inner)
            return loss, grads, mstate, comm_rep, comm_err

        lgr_hooked = None
        if hook_cfg is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            def _local(params, mstate, batch, comm_rep, comm_err, inner):
                # per-replica gradients; the only cross-replica traffic is the
                # compressed reduction + scalar loss pmean. Error-feedback buffers
                # (comm_err) stay worker-local: leading axis sharded over "data".
                loss, grads, mstate = loss_and_grads(params, mstate, batch, inner)
                grads, comm_rep, comm_err = reduce_gradients(
                    grads, comm_rep, comm_err, "data", hook_cfg
                )
                loss = jax.lax.pmean(loss, "data")
                # mutable collections are computed from the local shard; average
                # the floating leaves so the declared-replicated output is well
                # defined (SyncBN-style cross-replica statistics)
                if mstate is not None:
                    mstate = jax.tree.map(
                        lambda x: jax.lax.pmean(x, "data")
                        if jnp.issubdtype(x.dtype, jnp.floating)
                        else x,
                        mstate,
                    )
                return loss, grads, mstate, comm_rep, comm_err

            lgr_hooked = shard_map(
                _local,
                mesh=mesh,
                in_specs=(P(), P(), P("data"), P(), P("data"), P()),
                out_specs=(P(), P(), P(), P(), P("data")),
                check_vma=False,
            )

        # Pin gradients and updated params to the params' own shardings so the
        # whole fused step (grad -> clip -> optax update -> apply) carries ONE
        # consistent spec per leaf. Without this XLA is free to re-infer specs
        # in the backward, which on dp×fsdp×tp meshes produced involuntary full
        # rematerialization (VERDICT r1: spmd_partitioner warnings).
        param_shardings = getattr(model, "shardings", None)

        def constrain_like_params(tree):
            if param_shardings is None or tree is None:
                return tree
            return jax.tree.map(jax.lax.with_sharding_constraint, tree, param_shardings)

        def _split(scaler_state):
            # (inner loss-scale for the fp16 backward, its inverse factor) —
            # derived INSIDE the jit from the threaded scaler state, so there is
            # exactly one source of truth for both scaling and policy updates
            if scaler is None or scaler_state is None:
                return jnp.asarray(1.0, jnp.float32)
            inner, _ = scaler.split_scale(scaler_state.scale)
            return inner

        def make_micro(lgr):
            # acc / mstate / comm_err are consumed and replaced every call:
            # donating them keeps ONE gradient accumulator in HBM instead of
            # old+new copies during each microbatch.
            # NOTE: persistent comm-hook state is overflow-guarded per leaf
            # INSIDE reduce_gradients (compression._powersgd_leaf), so
            # non-finite microbatches can't poison it on ANY path and the
            # donated error buffers keep per-leaf lifetimes.
            @functools.partial(jax.jit, donate_argnums=(1, 2, 5) if donate else ())
            def micro_step(params, mstate, acc, batch, comm_rep, comm_err, scaler_state):
                inner = _split(scaler_state)
                loss, grads, mstate, comm_rep, comm_err = lgr(
                    params, mstate, batch, comm_rep, comm_err, inner
                )
                grads = constrain_like_params(grads)
                acc = grads if acc is None else jax.tree.map(jnp.add, acc, grads)
                return acc, mstate, loss, comm_rep, comm_err

            return micro_step

        def make_update(lgr):
            def _update(params, opt_state, mstate, acc, batch, comm_rep, comm_err, inv_k, scaler_state):
                inner = _split(scaler_state)
                loss, grads, mstate, comm_rep, comm_err = lgr(
                    params, mstate, batch, comm_rep, comm_err, inner
                )
                if acc is not None:
                    grads = jax.tree.map(jnp.add, acc, grads)
                # undo the inner loss scale and the accumulation factor in fp32
                grads = jax.tree.map(lambda g: g * (inv_k / inner), grads)
                grads = constrain_like_params(grads)
                finite = jnp.asarray(True)
                if scaler is not None:
                    finite = scaler.all_finite(grads)
                if max_grad_norm is not None:
                    grads, _ = _clip_tree(grads, max_grad_norm)
                updates, new_opt_state = tx.update(grads, opt_state, params)
                new_params = constrain_like_params(optax.apply_updates(params, updates))
                if scaler is not None:
                    # skip the update on overflow; torch-GradScaler growth/backoff
                    # (persistent comm-hook state is guarded inside the hook)
                    new_params = jax.tree.map(
                        lambda new, old: jnp.where(finite, new, old), new_params, params
                    )
                    new_opt_state = jax.tree.map(
                        lambda new, old: jnp.where(finite, new, old), new_opt_state, opt_state
                    )
                    scaler_state = scaler.update_state(scaler_state, finite)
                return new_params, new_opt_state, mstate, loss, comm_rep, comm_err, scaler_state, finite

            return jax.jit(_update, donate_argnums=(0, 1, 2, 3, 6) if donate else ())

        micro_plain, update_plain = make_micro(lgr_plain), make_update(lgr_plain)
        micro_hooked = update_hooked = None
        if hook_cfg is not None:
            micro_hooked, update_hooked = make_micro(lgr_hooked), make_update(lgr_hooked)
            comm_rep0, comm_err0 = init_comm_state(
                model.params, hook_cfg, n_replicas, mesh=mesh, axis="data"
            )
        else:
            comm_rep0 = comm_err0 = None
        warmup = hook_cfg.warmup_updates if hook_cfg is not None else 0
        state_box = {"acc": None, "count": 0, "rep": comm_rep0, "err": comm_err0}

        def step(batch: Any) -> jax.Array:
            self._do_sync()
            hooked = hook_cfg is not None and optimizer._num_updates >= warmup
            if self.gradient_state.sync_gradients:
                upd = update_hooked if hooked else update_plain
                inv_k = jnp.asarray(1.0 / self.gradient_state.num_steps, dtype=jnp.float32)
                (
                    params, opt_state, mstate, loss,
                    state_box["rep"], state_box["err"], new_scaler_state, finite,
                ) = upd(
                    model.params,
                    optimizer.opt_state,
                    model.extra_state,
                    state_box["acc"],
                    batch,
                    state_box["rep"],
                    state_box["err"],
                    inv_k,
                    optimizer.scaler_state,
                )
                model.params = params
                optimizer.opt_state = opt_state
                model.extra_state = mstate
                if scaler is not None:
                    optimizer.scaler_state = new_scaler_state
                    # lazy device scalars: reading (bool()/int()) syncs,
                    # assigning doesn't — skipped boundaries never count as
                    # applied updates (imperative-path semantics)
                    optimizer.step_was_skipped = jnp.logical_not(finite)
                    optimizer._skipped_updates = (
                        optimizer._skipped_updates + jnp.logical_not(finite).astype(jnp.int32)
                    )
                # boundary count: drives comm-hook warmup; `num_updates`
                # subtracts the device-tracked skips on read
                optimizer._num_updates += 1
                state_box["acc"] = None
                state_box["count"] = 0
            else:
                micro = micro_hooked if hooked else micro_plain
                state_box["acc"], model.extra_state, loss, state_box["rep"], state_box["err"] = (
                    micro(
                        model.params,
                        model.extra_state,
                        state_box["acc"],
                        batch,
                        state_box["rep"],
                        state_box["err"],
                        optimizer.scaler_state,
                    )
                )
                state_box["count"] += 1
            return loss

        def annotated(batch: Any) -> jax.Array:
            # host time to enqueue one step, on a profile's host plane beside
            # the loader's `train.input_wait` (`utils/spans.py`)
            with jax.profiler.TraceAnnotation("train.dispatch"):
                return step(batch)

        return annotated

    # -------------------------------------------------------- pipeline training
    def prepare_pipeline(
        self,
        stage_fn: Callable,
        per_stage_params: Any,
        *,
        pre: tuple[Callable, Any] | None = None,
        post: tuple[Callable, Any] | None = None,
        num_microbatches: int = 1,
        axis_name: str = "stage",
    ) -> PreparedModel:
        """Prepare a GPipe pipeline model over the mesh's ``stage`` axis.

        ``per_stage_params`` is a list of per-stage param pytrees (one per
        pipeline stage, all for the same homogeneous ``stage_fn``) or an
        already-stacked tree with a leading stage dim. ``pre``/``post`` are
        optional ``(fn, params)`` pairs for the replicated embedding/head
        around the pipelined trunk. The returned `PreparedModel` carries
        stage-axis shardings, so `save_state`/`load_state` round-trip the
        stage-sharded weights through orbax like any other model, and a
        prepared optimizer's state lands stage-sharded for free.

        Reference role: Megatron-LM model prep (`utils/megatron_lm.py` pp>1
        model partitioning) — here a sharding annotation, not an engine.
        """
        from .parallel.pipeline import pipeline_apply
        from .parallel.pipeline_train import build_pipeline_params, stage_shardings

        if self.mesh is None or self.mesh.shape.get(axis_name, 1) <= 1:
            raise ValueError(
                f"prepare_pipeline needs a mesh with a non-trivial {axis_name!r} axis "
                "(ParallelismConfig(stage_size=...))."
            )
        pre_fn, pre_params = pre if pre is not None else (None, None)
        post_fn, post_params = post if post is not None else (None, None)
        stage_size = self.mesh.shape[axis_name]
        if isinstance(per_stage_params, list) and len(per_stage_params) != stage_size:
            raise ValueError(
                f"got {len(per_stage_params)} per-stage param trees for a mesh "
                f"with {axis_name} axis size {stage_size}; pipeline stages must "
                "match the mesh one-to-one."
            )
        params = build_pipeline_params(per_stage_params, pre_params, post_params)
        params = self.policy.cast_to_param(params)
        shardings = stage_shardings(params, self.mesh, axis_name)
        if self.device_placement:
            params = shard_params(params, shardings)
        mesh = self.mesh

        def apply_fn(p, x):
            h = pre_fn(p["pre"], x) if pre_fn is not None else x
            y = pipeline_apply(
                stage_fn, p["stages"], h, mesh, num_microbatches, axis_name=axis_name
            )
            return post_fn(p["post"], y) if post_fn is not None else y

        prepared = PreparedModel(
            apply_fn,
            params,
            policy=self.policy,
            mesh=mesh,
            shardings=shardings,
            module=stage_fn,
        )
        self._models.append(prepared)
        return prepared

    def make_pipeline_train_step(
        self,
        stage_fn: Callable,
        loss_fn: Callable,
        model: PreparedModel | None = None,
        optimizer: AcceleratedOptimizer | None = None,
        *,
        num_microbatches: int,
        pre_fn: Callable | None = None,
        post_fn: Callable | None = None,
        max_grad_norm: float | None = None,
        donate: bool = True,
        axis_name: str = "stage",
    ) -> Callable:
        """`make_train_step` sibling for a `prepare_pipeline` model: one jitted
        SPMD program runs the GPipe microbatch schedule, backward, gradient
        accumulation and the optimizer tick over the ``stage`` mesh axis
        (reference Megatron train_step role, `utils/megatron_lm.py:1035-1057`).
        ``step(batch) -> loss`` with ``batch = (x, targets)``."""
        from .parallel.pipeline_train import make_pipeline_train_step

        return make_pipeline_train_step(
            self,
            stage_fn,
            loss_fn,
            model,
            optimizer,
            num_microbatches=num_microbatches,
            pre_fn=pre_fn,
            post_fn=post_fn,
            max_grad_norm=max_grad_norm,
            donate=donate,
            axis_name=axis_name,
        )

    # ------------------------------------------------------------- collectives
    def gather(self, tensor: Any) -> Any:
        return operations.gather(tensor)

    def gather_for_metrics(self, input_data: Any, use_gather_object: bool = False) -> Any:
        """Gather eval outputs and drop the duplicated tail of the final ragged
        batch (reference `accelerator.py:2443-2505` + GradientState.remainder)."""
        if use_gather_object or not _all_tensors(input_data):
            data = operations.gather_object(
                input_data if isinstance(input_data, list) else [input_data]
            )
        else:
            data = operations.gather(input_data)
        try:
            on_last = self.gradient_state.end_of_dataloader
            remainder = self.gradient_state.remainder
        except Exception:
            return data
        if on_last and remainder > 0:
            data = operations.recursively_apply(lambda t: t[:remainder], data)
        return data

    def reduce(self, tensor: Any, reduction: str = "sum", scale: float = 1.0) -> Any:
        return operations.reduce(tensor, reduction=reduction, scale=scale)

    def pad_across_processes(self, tensor: Any, dim: int = 0, pad_index: int = 0, pad_first: bool = False):
        return operations.pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    def broadcast(self, tensor: Any, from_process: int = 0) -> Any:
        return operations.broadcast(tensor, from_process=from_process)

    # -------------------------------------------------------------- triggers
    def set_trigger(self) -> None:
        """Set a breakpoint flag visible to all processes (reference
        `accelerator.py:2233-2290` — coordinated early-stop)."""
        self.flag_tensor = np.array([1], dtype=np.int64)

    def check_trigger(self) -> bool:
        flag = self.flag_tensor if self.flag_tensor is not None else np.array([0], dtype=np.int64)
        total = operations.reduce(flag, reduction="sum")
        if int(np.asarray(total)[0]) > 0:
            self.flag_tensor = None
            return True
        return False

    # -------------------------------------------------------------- contexts
    def save(self, obj: Any, f: str, safe_serialization: bool = False) -> None:
        """Rank-gated serialization of any object (reference `Accelerator.save`
        -> `utils/other.py:save`): array pytrees go to safetensors when
        ``safe_serialization`` (interchange format), anything else to pickle
        with array leaves converted to host numpy. Main process writes; other
        ranks no-op."""
        from .utils.other import save as _save

        _save(obj, f, safe_serialization=safe_serialization)

    @property
    def optimizer_step_was_skipped(self) -> bool:
        """True when any prepared optimizer skipped its last step (fp16
        overflow) — reference `Accelerator.optimizer_step_was_skipped`."""
        return any(bool(opt.step_was_skipped) for opt in self._optimizers)

    @property
    def use_seedable_sampler(self) -> bool:
        return self._use_seedable_sampler

    @property
    def non_blocking(self) -> bool:
        """Device transfers are asynchronous by nature in JAX (reference flag
        parity: always True)."""
        return True

    @property
    def use_stateful_dataloader(self) -> bool:
        """Echoes ``DataLoaderConfiguration.use_stateful_dataloader``. Prepared
        loaders here support state_dict/load_state_dict regardless (no
        torchdata dependency); the flag records the user's intent for
        reference-code compatibility."""
        return self._use_stateful_dataloader

    @property
    def save_iteration(self) -> int:
        """Next automatic checkpoint index (reference `save_iteration`)."""
        return self.project_configuration.iteration

    @property
    def fp8_backend(self) -> str | None:
        """'NATIVE' when fp8 training is configured (XLA-native delayed-scaling
        path, `ops/fp8.py`) — the reference reports TE/MSAMP here."""
        if self.mixed_precision == "fp8" or self.fp8_recipe_handler is not None:
            return "NATIVE"
        return None

    def verify_device_map(self, model: Any) -> bool:
        """True when ``model`` carries a multi-entry big-model device map;
        `prepare` calls this and refuses such models (reference
        `accelerator.py` verify_device_map role)."""
        device_map = getattr(model, "device_map", None)
        return isinstance(device_map, dict) and len(device_map) > 1

    def register_save_state_pre_hook(self, hook: Callable) -> "_RemovableHandle":
        """``hook(models, weights, output_dir)`` runs at the top of
        `save_state` (reference `accelerator.py` register_save_state_pre_hook);
        mutate ``weights`` in place to customize what is persisted."""
        handle = _RemovableHandle(self._save_state_pre_hooks)
        self._save_state_pre_hooks[handle.id] = hook
        return handle

    def register_load_state_pre_hook(self, hook: Callable) -> "_RemovableHandle":
        """``hook(models, input_dir)`` runs at the top of `load_state`."""
        handle = _RemovableHandle(self._load_state_pre_hooks)
        self._load_state_pre_hooks[handle.id] = hook
        return handle

    @contextlib.contextmanager
    def autocast(self, autocast_handler: Any = None):
        """Reference `accelerator.py:3422`. Precision is a functional cast
        policy applied inside prepared forwards, so *enabling* is the ambient
        state; the context's real lever is ``AutocastKwargs(enabled=False)``,
        which makes eager `PreparedModel` calls inside the block skip the
        compute-dtype cast (numerically sensitive regions run in the fp32
        master dtype)."""
        from .utils.precision import reset_autocast_enabled, set_autocast_enabled

        handler = autocast_handler or self.autocast_handler
        enabled = handler.enabled if handler is not None else True
        token = set_autocast_enabled(enabled)
        try:
            yield
        finally:
            reset_autocast_enabled(token)

    @contextlib.contextmanager
    def profile(self, profile_handler: Any = None, log_dir: str | None = None):
        """jax.profiler trace context, one trace per host (reference
        `accelerator.py:3449-3506` / torch.profiler). ``profile_handler``
        defaults to the ProfileKwargs passed via ``kwargs_handlers``."""
        handler = profile_handler or self.profile_handler
        target = log_dir or (
            (handler.output_trace_dir if handler is not None else None)
            or self.project_configuration.logging_dir
            or "profile_traces"
        )
        jax.profiler.start_trace(
            target,
            create_perfetto_link=bool(handler.create_perfetto_link) if handler is not None else False,
        )
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    # ---------------------------------------------------------- model export
    def unwrap_model(self, model: PreparedModel, keep_fp32_wrapper: bool = True) -> Any:
        """Return the original module the user handed to prepare (reference
        `extract_model_from_parallel`, `utils/other.py:64-133`)."""
        from .utils.other import extract_model_from_parallel

        return extract_model_from_parallel(model, keep_fp32_wrapper=keep_fp32_wrapper)

    def get_state_dict(self, model: PreparedModel, unwrap: bool = True, main_process_only: bool = False) -> Any:
        """Fully-gathered (unsharded) parameter pytree on host (reference
        `accelerator.py:3329-3383` — FSDP FULL_STATE_DICT / ZeRO-3 consolidation).

        Leaves stream to host one at a time. With ``main_process_only`` the
        rank0-only consolidation semantics apply: non-main processes receive
        ``None`` leaves and never hold a full replica (the safe mode for
        big models — every process must still make the call, it is collective)."""
        return operations.consolidate_on_main(model.params, keep_on_all=not main_process_only)

    def free_memory(self, *objects: Any) -> tuple:
        """Drop references to prepared objects and clear compiled caches
        (reference `accelerator.py:3257-3289`)."""
        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self._grad_fns.clear()
        self._train_steps.clear()
        self.step = 0
        jax.clear_caches()
        return objects

    def clear(self, *objects: Any) -> tuple:
        return self.free_memory(*objects)

    # ----------------------------------------------------------- checkpointing
    def register_for_checkpointing(self, *objects: Any) -> None:
        """Track custom stateful objects for save_state/load_state (reference
        `accelerator.py:3385`). Objects must expose state_dict/load_state_dict."""
        invalid = [o for o in objects if not (hasattr(o, "state_dict") and hasattr(o, "load_state_dict"))]
        if invalid:
            raise ValueError(f"Objects lack state_dict/load_state_dict: {invalid}")
        self._custom_objects.extend(objects)

    def save_state(
        self, output_dir: str | None = None, async_save: bool | None = None, **save_model_kwargs: Any
    ) -> str:
        """``async_save`` (default: ``ProjectConfiguration.async_save``) returns
        once device arrays are copied to host; disk writes complete in the
        background and are barriered at the next save/load/`wait_for_checkpoint`/exit."""
        from .checkpointing import get_checkpoint_dir, save_accelerator_state

        resolved = str(get_checkpoint_dir(self, output_dir))  # hooks see the real dir
        weights = [m.params for m in self._models]
        for hook in self._save_state_pre_hooks.values():
            hook(self._models, weights, resolved)  # hooks may replace entries
        if async_save is None:
            async_save = self.project_configuration.async_save
        return save_accelerator_state(self, resolved, weights=weights, async_save=async_save)

    def wait_for_checkpoint(self) -> None:
        """Block until every async save_state has fully landed on disk."""
        from .checkpointing import wait_for_checkpoint_saves

        wait_for_checkpoint_saves()

    def load_state(self, input_dir: str | None = None, **load_model_kwargs: Any) -> str:
        """With ``input_dir=None``, recovery walks the complete-checkpoint
        chain newest-first and restores from the first directory that loads
        cleanly (a corrupt latest checkpoint falls back instead of failing) —
        pre-hooks observe the newest candidate. Returns the directory actually
        restored."""
        from .checkpointing import latest_checkpoint_dir, load_accelerator_state

        resolved = str(latest_checkpoint_dir(self)) if input_dir is None else str(input_dir)
        for hook in self._load_state_pre_hooks.values():
            hook(self._models, resolved)
        return load_accelerator_state(self, input_dir)

    def save_model(
        self,
        model: PreparedModel,
        save_directory: str,
        max_shard_size: str | int = "10GB",
        safe_serialization: bool = True,
    ) -> None:
        from .checkpointing import save_model_weights

        save_model_weights(
            self.get_state_dict(model, main_process_only=True),
            save_directory,
            max_shard_size=max_shard_size,
            safe_serialization=safe_serialization,
        )

    # ---------------------------------------------------------------- tracking
    def init_trackers(self, project_name: str, config: dict | None = None, init_kwargs: dict | None = None):
        from .tracking import filter_trackers

        self.trackers = filter_trackers(
            self._log_with, self.project_configuration.logging_dir, project_name, config,
            init_kwargs or {},
        )

    def log(self, values: dict, step: int | None = None, log_kwargs: dict | None = None) -> None:
        if not self.is_main_process:
            return
        for tracker in self.trackers:
            tracker.log(values, step=step, **(log_kwargs or {}).get(tracker.name, {}))

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"Tracker {name} not initialized (have: {[t.name for t in self.trackers]})")

    def end_training(self) -> None:
        for tracker in self.trackers:
            tracker.finish()
        self.wait_for_everyone()

    # ------------------------------------------------------------- loader utils
    def skip_first_batches(self, dataloader: Any, num_batches: int = 0) -> Any:
        return skip_first_batches(dataloader, num_batches)

    def __repr__(self) -> str:
        return (
            f"Accelerator(mesh={dict(self.mesh.shape)}, mixed_precision={self.mixed_precision!r}, "
            f"grad_accum={self.gradient_state.num_steps})"
        )


def _all_tensors(data: Any) -> bool:
    ok = True

    def _check(t):
        nonlocal ok
        return t

    flat = jax.tree.leaves(data)
    return all(hasattr(leaf, "shape") and hasattr(leaf, "dtype") for leaf in flat)


@jax.jit
def _clip_tree(grads: Any, max_norm: float):
    norm = optax.global_norm(grads)
    factor = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(lambda g: g * factor, grads), norm


def _clip_by_global_norm(grads: Any, max_norm: float):
    return _clip_tree(grads, max_norm)
