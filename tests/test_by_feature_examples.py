"""Every by_feature example must run end-to-end on the 8-device CPU mesh
(reference `tests/test_examples.py` runs `examples/by_feature/*` the same way)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
BY_FEATURE = REPO / "examples" / "by_feature"

SCRIPTS = sorted(p.name for p in BY_FEATURE.glob("*.py") if not p.name.startswith("_"))


def test_suite_is_complete():
    """The reference's by_feature roster must be covered (same or mapped name)."""
    expected = {
        "gradient_accumulation.py",
        "automatic_gradient_accumulation.py",
        "checkpointing.py",
        "cross_validation.py",
        "early_stopping.py",
        "local_sgd.py",
        "memory.py",
        "multi_process_metrics.py",
        "profiler.py",
        "tracking.py",
        "ddp_comm_hook.py",
        "schedule_free.py",
        "fsdp_with_peak_mem_tracking.py",
        "tensor_parallel_gpt_pretraining.py",  # megatron_lm_gpt_pretraining analogue
        "deepspeed_with_config_support.py",
    }
    assert expected.issubset(set(SCRIPTS)), expected - set(SCRIPTS)


@pytest.mark.parametrize("script", SCRIPTS)
def test_by_feature_example_runs(tmp_path, script):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "PYTHONPATH": str(REPO),
        }
    )
    cmd = [
        sys.executable,
        str(BY_FEATURE / script),
        "--tiny",
        "--num_epochs",
        "1",
        "--project_dir",
        str(tmp_path),
    ]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"{script}:\n{out.stdout}\n{out.stderr}"
    assert ("accuracy" in out.stdout) or ("loss" in out.stdout), out.stdout


def test_scripts_stay_in_sync_with_common_base():
    """Source-sync check (reference `tests/test_examples.py` diff-checks each
    by_feature script against the base example): every script must build on the
    shared `_common` workload and drive training through the Accelerator API,
    so feature scripts can't drift into bespoke setups that rot."""
    for name in SCRIPTS:
        src = (BY_FEATURE / name).read_text()
        assert "_common" in src, f"{name} does not use the shared _common base"
        assert "Accelerator(" in src, f"{name} does not construct an Accelerator"
        assert (
            "make_train_step" in src or "backward(" in src
            or "make_local_train_step" in src or "make_pipeline_train_step" in src
        ), f"{name} does not train through the framework API"
