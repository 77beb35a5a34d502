"""The examples/inference/ suites stay runnable (reference
`tests/test_examples.py` role for its inference examples): each script runs
as a user would on the 8-device CPU mesh. Tier-2 (slow): real subprocesses,
one compile each."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

SCRIPTS = [
    "examples/inference/pippy/gpt2.py",
    "examples/inference/pippy/bert.py",
    "examples/inference/pippy/llama.py",
    "examples/inference/pippy/t5.py",
    "examples/inference/distributed/batch_text_generation.py",
    "examples/inference/distributed/image_classification.py",
]


def _cpu_env():
    env = dict(os.environ)
    env.update(
        PYTHONPATH=str(REPO),
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    # the children share the suite's compile cache, placed from outside
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(REPO / ".jax_cache"))
    return env


@pytest.mark.slow
@pytest.mark.parametrize("script", SCRIPTS)
def test_inference_example_runs(script):
    run = subprocess.run(
        [sys.executable, str(REPO / script)],
        capture_output=True, text=True, timeout=600, env=_cpu_env(), cwd=str(REPO),
    )
    assert run.returncode == 0, f"{script} failed:\n{run.stderr[-2000:]}"
    assert run.stdout.strip(), f"{script} produced no output"
