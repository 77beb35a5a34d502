"""Elastic restart supervision (`launch --max_restarts`, the torchelastic
analogue) and DeepSpeed JSON config ingestion."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

CRASHY = """
import os, sys
from pathlib import Path
marker = Path(sys.argv[1])
attempt = int(os.environ.get("ACCELERATE_TPU_RESTART_COUNT", "0"))
marker.write_text(str(attempt))
if attempt < 2:
    sys.exit(17)  # simulated crash on the first two attempts
print(f"recovered on attempt {attempt}")
"""


def _launch(tmp_path, extra_args, script_body, script_args=()):
    script = tmp_path / "train.py"
    script.write_text(script_body)
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)})
    cmd = [
        sys.executable, "-m", "accelerate_tpu.commands.cli", "launch",
        *extra_args, str(script), *[str(a) for a in script_args],
    ]
    return subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=300)


def test_supervisor_restarts_until_success(tmp_path):
    marker = tmp_path / "attempt.txt"
    out = _launch(
        tmp_path,
        ["--max_restarts", "3", "--monitor_interval", "0.05"],
        CRASHY,
        [marker],
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert marker.read_text() == "2"  # third attempt (index 2) succeeded
    assert "restart 1/3" in out.stderr and "restart 2/3" in out.stderr
    assert "recovered on attempt 2" in out.stdout


def test_supervisor_gives_up_after_max_restarts(tmp_path):
    marker = tmp_path / "attempt.txt"
    out = _launch(
        tmp_path,
        ["--max_restarts", "1", "--monitor_interval", "0.05"],
        CRASHY,
        [marker],
    )
    assert out.returncode == 17
    assert "giving up" in out.stderr
    assert marker.read_text() == "1"  # ran attempts 0 and 1 only


def test_deepspeed_json_config_ingestion(tmp_path):
    from accelerate_tpu.utils.dataclasses import DeepSpeedPlugin

    cfg = {
        "zero_optimization": {"stage": 3, "offload_optimizer": {"device": "cpu"}},
        "gradient_accumulation_steps": 4,
        "gradient_clipping": 0.7,
        "bf16": {"enabled": True},
        "fp16": {"enabled": False},
        "aio": {"block_size": 1048576},  # engine-only: ignored
    }
    path = tmp_path / "ds_config.json"
    path.write_text(json.dumps(cfg))
    plugin = DeepSpeedPlugin(hf_ds_config=str(path))
    assert plugin.zero_stage == 3
    assert plugin.offload_optimizer_device == "cpu"
    assert plugin.gradient_accumulation_steps == 4
    assert plugin.gradient_clipping == 0.7
    assert plugin.mixed_precision == "bf16"
    pc = plugin.to_parallelism_config(8)
    assert pc.fsdp_size == -1 and pc.data_parallel_size == 1


def test_deepspeed_auto_values_keep_defaults(tmp_path):
    from accelerate_tpu.utils.dataclasses import DeepSpeedPlugin

    cfg = {
        "zero_optimization": {"stage": "auto", "offload_optimizer": {"device": "none"}},
        "gradient_accumulation_steps": "auto",
        "gradient_clipping": "auto",
    }
    path = tmp_path / "ds_config.json"
    path.write_text(json.dumps(cfg))
    plugin = DeepSpeedPlugin(hf_ds_config=str(path))
    assert plugin.zero_stage == 2  # default preserved
    assert plugin.offload_optimizer_device is None
    assert plugin.gradient_accumulation_steps == 1
    assert plugin.gradient_clipping is None
    assert plugin.mixed_precision is None


MULTIHOST_CRASHY = """
import os, sys
from pathlib import Path
attempt = int(os.environ.get("ACCELERATE_TPU_RESTART_COUNT", "0"))
pid = int(os.environ["JAX_PROCESS_ID"])
from accelerate_tpu.state import PartialState
state = PartialState()  # jax.distributed rendezvous at the shared coordinator
assert state.num_processes == 2
if attempt == 0 and pid == 1:
    sys.exit(23)  # host 1 dies in generation 0
# generation 1: both hosts must have re-rendezvoused; prove a collective works
from accelerate_tpu.utils import operations
got = operations.gather_object([f"p{state.process_index}a{attempt}"])
assert got == ["p0a1", "p1a1"], got
Path(sys.argv[1] + f".{pid}").write_text(str(attempt))
print(f"host {pid} recovered on generation {attempt}")
"""


def test_multihost_generation_restart(tmp_path):
    """Cross-host elastic tier (torchelastic rendezvous role): one host dying
    tears down the generation; ALL hosts restart and re-form at the same
    coordinator, and collectives work in the new generation."""
    marker = tmp_path / "gen"
    out = _launch(
        tmp_path,
        ["--debug_cpu", "2", "--max_restarts", "2", "--monitor_interval", "0.1"],
        MULTIHOST_CRASHY,
        script_args=[marker],
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert (tmp_path / "gen.0").read_text() == "1"
    assert (tmp_path / "gen.1").read_text() == "1"
    assert "restart 1/2" in out.stderr


POD_SLICE = """
import jax, sys
from accelerate_tpu.state import PartialState
s = PartialState()
assert s.num_processes == 2, s.num_processes
assert jax.local_device_count() == 4, jax.local_device_count()
assert jax.device_count() == 8, jax.device_count()
print(f"host {s.process_index} sees 4 local / 8 global")
"""


def test_debug_cpu_devices_per_process(tmp_path):
    """--debug_cpu N --devices_per_process M rehearses an N-host x M-chip pod
    slice without hardware (examples/tpu_pod/README.md recipe)."""
    out = _launch(
        tmp_path,
        ["--debug_cpu", "2", "--devices_per_process", "4"],
        POD_SLICE,
    )
    assert out.returncode == 0, out.stderr[-2000:]
