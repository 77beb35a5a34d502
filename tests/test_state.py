"""Tests for PartialState / AcceleratorState / GradientState (L0)."""

import jax
import numpy as np
import pytest

from accelerate_tpu.parallel.mesh import ParallelismConfig, build_mesh
from accelerate_tpu.state import AcceleratorState, DistributedType, GradientState, PartialState


def test_partial_state_topology():
    state = PartialState()
    assert state.num_devices == 8
    assert state.num_processes == 1
    assert state.is_main_process
    assert state.is_last_process
    assert state.distributed_type == DistributedType.SPMD
    assert state.use_distributed


def test_partial_state_singleton():
    a = PartialState()
    b = PartialState()
    assert a.__dict__ is b.__dict__


def test_split_between_processes_single():
    state = PartialState()
    with state.split_between_processes([1, 2, 3]) as inputs:
        assert inputs == [1, 2, 3]


def test_rank_gated_decorators(capsys):
    state = PartialState()
    called = []

    @state.on_main_process
    def fn():
        called.append(1)

    fn()
    assert called == [1]
    state.print("hello")
    assert "hello" in capsys.readouterr().out


def test_accelerator_state_default_mesh():
    state = AcceleratorState()
    assert dict(state.mesh.shape) == {"data": 8, "fsdp": 1, "stage": 1, "sequence": 1, "tensor": 1}
    assert state.data_parallel_size == 8


def test_accelerator_state_custom_mesh():
    state = AcceleratorState(parallelism_config=ParallelismConfig(data_parallel_size=2, tensor_size=4))
    assert state.mesh.shape["data"] == 2
    assert state.mesh.shape["tensor"] == 4


def test_mesh_inference_and_validation():
    cfg = ParallelismConfig(data_parallel_size=-1, tensor_size=2)
    mesh = build_mesh(cfg, jax.devices())
    assert mesh.shape["data"] == 4
    with pytest.raises(ValueError):
        build_mesh(ParallelismConfig(data_parallel_size=3, tensor_size=2), jax.devices())


def test_gradient_state():
    gs = GradientState(gradient_accumulation_steps=4)
    assert gs.num_steps == 4
    assert gs.sync_gradients
    assert not gs.in_dataloader
    assert gs.remainder == -1
    gs2 = GradientState()
    assert gs2.num_steps == 4  # singleton


def test_split_between_processes_dict():
    state = PartialState()
    data = {"x": np.arange(6), "y": np.arange(6) * 2}
    with state.split_between_processes(data) as piece:
        np.testing.assert_array_equal(piece["x"], np.arange(6))


def test_sagemaker_env_translates_to_jax_contract(monkeypatch):
    """SM_HOSTS/SM_CURRENT_HOST become the JAX coordinator contract so a
    num_machines>1 SageMaker job forms one world instead of N duplicates."""
    import json

    from accelerate_tpu.state import _sagemaker_env_to_contract

    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES", "JAX_PROCESS_ID",
              "ACCELERATE_TPU_NUM_PROCESSES"):
        # set-then-delete registers the key with monkeypatch, so what the
        # function writes into os.environ is undone after the test (a leaked
        # contract makes every later PartialState() try to join a world)
        monkeypatch.setenv(k, "")
        monkeypatch.delenv(k)
    monkeypatch.setenv("ACCELERATE_TPU_USE_SAGEMAKER", "true")
    monkeypatch.setenv("SM_HOSTS", json.dumps(["algo-2", "algo-1"]))
    monkeypatch.setenv("SM_CURRENT_HOST", "algo-2")
    _sagemaker_env_to_contract()
    import os

    assert os.environ["JAX_COORDINATOR_ADDRESS"] == "algo-1:8476"
    assert os.environ["JAX_NUM_PROCESSES"] == "2"
    assert os.environ["JAX_PROCESS_ID"] == "1"  # sorted order


def test_on_local_process_and_default_device():
    state = PartialState()
    ran = []
    state.on_local_process(lambda: ran.append("a"))()
    state.on_local_process(local_process_index=3)(lambda: ran.append("b"))()
    assert ran == ["a"]  # single process per host: only local index 0 exists
    assert state.default_device is not None


def test_deepspeed_plugin_registry_and_selection():
    """Reference multi-plugin accessors: register, get by name, select active."""
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state()
    st = AcceleratorState()
    assert st.deepspeed_plugin is None
    a, b = object(), object()
    st.register_deepspeed_plugins({"train": a, "eval": b})
    assert st.deepspeed_plugin is a  # first registered is active
    assert st.get_deepspeed_plugin("eval") is b
    st.select_deepspeed_plugin("eval")
    assert st.deepspeed_plugin is b
    with pytest.raises(ValueError, match="registered"):
        st.get_deepspeed_plugin("nope")
    AcceleratorState._reset_state()


def test_gradient_state_xla_sync_alias():
    from accelerate_tpu.state import GradientState

    GradientState._reset_state()
    gs = GradientState()
    assert gs.is_xla_gradients_synced == gs.sync_gradients
    gs._set_sync_gradients(False)
    assert gs.is_xla_gradients_synced is False
    GradientState._reset_state()


def test_slurm_step_autodetects_distributed(monkeypatch):
    """Inside a multi-task srun step (reference examples/slurm submit scripts
    role) distributed init must fall through to jax's SLURM cluster detection:
    initialize() called with NO explicit coordinator arguments."""
    from accelerate_tpu import state as st

    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "ACCELERATE_TPU_NUM_PROCESSES", "JAX_PROCESS_ID"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("SLURM_JOB_ID", "4242")
    monkeypatch.setenv("SLURM_PROCID", "1")
    monkeypatch.setenv("SLURM_STEP_NUM_TASKS", "4")
    calls = []
    monkeypatch.setattr(st.jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    monkeypatch.setattr(st.jax.distributed, "is_initialized", lambda: False)
    st._maybe_init_distributed(initialization_timeout=60)
    assert calls == [{"initialization_timeout": 60}]


def test_sbatch_batch_step_stays_local(monkeypatch):
    """A plain sbatch batch script (no srun) exports SLURM_NTASKS=N with a
    single-task batch step — it must NOT attempt distributed init (it would
    block waiting for peers that never start). The discriminator is the STEP
    task count."""
    from accelerate_tpu import state as st

    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "ACCELERATE_TPU_NUM_PROCESSES", "SLURM_STEP_NUM_TASKS"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("SLURM_JOB_ID", "4242")
    monkeypatch.setenv("SLURM_PROCID", "0")
    monkeypatch.setenv("SLURM_NTASKS", "4")  # the allocation, not the step
    calls = []
    monkeypatch.setattr(st.jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    st._maybe_init_distributed()
    assert calls == []


def _slurm_step_env(monkeypatch):
    for k in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
              "ACCELERATE_TPU_NUM_PROCESSES", "JAX_PROCESS_ID",
              "ACCELERATE_TPU_ALLOW_SLURM_FALLBACK"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("SLURM_JOB_ID", "4242")
    monkeypatch.setenv("SLURM_PROCID", "1")
    monkeypatch.setenv("SLURM_STEP_NUM_TASKS", "4")


def test_slurm_step_init_failure_raises(monkeypatch):
    """A failed distributed init inside a multi-task srun step must REFUSE to
    continue: the old silent fallback ran N duplicate single-process worlds
    that all claimed main-process and overwrote each other's outputs."""
    from accelerate_tpu import state as st

    _slurm_step_env(monkeypatch)
    monkeypatch.setattr(st.jax.distributed, "is_initialized", lambda: False,
                        raising=False)

    def boom(**kw):
        raise RuntimeError("no coordinator")

    monkeypatch.setattr(st.jax.distributed, "initialize", boom)
    with pytest.raises(RuntimeError, match="ALLOW_SLURM_FALLBACK"):
        st._maybe_init_distributed()


def test_slurm_step_init_failure_fallback_opt_out(monkeypatch):
    """ACCELERATE_TPU_ALLOW_SLURM_FALLBACK=1 restores the old warn-and-continue
    behavior for salvage debugging."""
    from accelerate_tpu import state as st

    _slurm_step_env(monkeypatch)
    monkeypatch.setenv("ACCELERATE_TPU_ALLOW_SLURM_FALLBACK", "1")
    monkeypatch.setattr(st.jax.distributed, "is_initialized", lambda: False,
                        raising=False)

    def boom(**kw):
        raise RuntimeError("no coordinator")

    monkeypatch.setattr(st.jax.distributed, "initialize", boom)
    st._maybe_init_distributed()  # must not raise


def test_launcher_contract_init_failure_raises(monkeypatch):
    """A launcher-supplied coordinator contract whose initialize fails must
    not degrade to a world of one (it used to be logged at debug)."""
    from accelerate_tpu import state as st

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "127.0.0.1:1")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "2")
    monkeypatch.setenv("JAX_PROCESS_ID", "1")
    monkeypatch.setattr(st.jax.distributed, "is_initialized", lambda: False)

    def boom(**kw):
        raise RuntimeError("no coordinator")

    monkeypatch.setattr(st.jax.distributed, "initialize", boom)
    with pytest.raises(RuntimeError, match="coordinator contract.*no coordinator"):
        st._maybe_init_distributed()
    # an already-initialized world stays benign
    monkeypatch.setattr(st.jax.distributed, "is_initialized", lambda: True)
    st._maybe_init_distributed()


def test_reregistering_deepspeed_plugins_resets_stale_active(monkeypatch):
    """Re-registering under new names must re-point the active plugin at the
    new dict's first entry, not leave deepspeed_plugin silently None."""
    from accelerate_tpu import state as st
    from accelerate_tpu.state import AcceleratorState

    # the construction path probes jax.distributed.is_initialized; stub it so
    # the test exercises the registry, not the env
    monkeypatch.setattr(st.jax.distributed, "is_initialized", lambda: True)
    AcceleratorState._reset_state()
    st = AcceleratorState()
    a, b, c = object(), object(), object()
    st.register_deepspeed_plugins({"train": a, "eval": b})
    st.select_deepspeed_plugin("eval")
    st.register_deepspeed_plugins({"prod": c})  # "eval" is now stale
    assert st.deepspeed_plugin is c
    # re-registering with the active name still present keeps the selection
    st.register_deepspeed_plugins({"other": a, "prod": c})
    assert st.deepspeed_plugin is c
    AcceleratorState._reset_state()


def test_sagemaker_env_noop_outside_sagemaker(monkeypatch):
    from accelerate_tpu.state import _sagemaker_env_to_contract

    for k in ("JAX_COORDINATOR_ADDRESS", "ACCELERATE_TPU_USE_SAGEMAKER"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("SM_HOSTS", '["a", "b"]')
    monkeypatch.setenv("SM_CURRENT_HOST", "a")
    _sagemaker_env_to_contract()
    import os

    assert "JAX_COORDINATOR_ADDRESS" not in os.environ
