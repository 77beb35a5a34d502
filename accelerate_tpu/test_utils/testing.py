"""Test decorators and harness helpers.

Capability parity: reference `test_utils/testing.py` (689 LoC) — `require_*` skip
decorators, device probing, `AccelerateTestCase` (singleton reset),
`execute_subprocess_async`, launch-command builders.
"""

from __future__ import annotations

import asyncio
import os
import subprocess
import sys
import tempfile
import unittest
from functools import partial
from pathlib import Path
from typing import Callable

import pytest

from ..utils import imports


def get_backend() -> tuple[str, int]:
    """(platform, device_count) of the default JAX backend (reference `get_backend`)."""
    import jax

    devices = jax.devices()
    return devices[0].platform, len(devices)


def require_tpu(test_case: Callable) -> Callable:
    from ..utils.environment import on_tpu_platform

    return pytest.mark.skipif(not on_tpu_platform(), reason="test requires TPU")(test_case)


def require_multi_device(test_case: Callable) -> Callable:
    _, n = get_backend()
    return pytest.mark.skipif(n < 2, reason="test requires multiple devices")(test_case)


def require_cpu(test_case: Callable) -> Callable:
    platform, _ = get_backend()
    return pytest.mark.skipif(platform != "cpu", reason="test requires CPU backend")(test_case)


def require_torch(test_case: Callable) -> Callable:
    return pytest.mark.skipif(not imports.is_torch_available(), reason="test requires torch")(test_case)


def require_transformers(test_case: Callable) -> Callable:
    return pytest.mark.skipif(
        not imports.is_transformers_available(), reason="test requires transformers"
    )(test_case)


def require_tensorboard(test_case: Callable) -> Callable:
    return pytest.mark.skipif(
        not imports.is_tensorboard_available(), reason="test requires tensorboard"
    )(test_case)


def require_wandb(test_case: Callable) -> Callable:
    return pytest.mark.skipif(not imports.is_wandb_available(), reason="test requires wandb")(test_case)


def slow(test_case: Callable) -> Callable:
    """Skipped unless RUN_SLOW=1 (reference `testing.py:slow`)."""
    from ..utils.environment import parse_flag_from_env

    return pytest.mark.skipif(not parse_flag_from_env("RUN_SLOW"), reason="test is slow")(test_case)


class TempDirTestCase(unittest.TestCase):
    """Each test gets a fresh scratch dir in self.tmpdir (reference `testing.py:446`)."""

    clear_on_setup = True

    @classmethod
    def setUpClass(cls):
        cls._tmpdir_handle = tempfile.TemporaryDirectory()
        cls.tmpdir = Path(cls._tmpdir_handle.name)

    @classmethod
    def tearDownClass(cls):
        cls._tmpdir_handle.cleanup()

    def setUp(self):
        if self.clear_on_setup:
            for item in self.tmpdir.glob("**/*"):
                if item.is_file():
                    item.unlink()


class AccelerateTestCase(unittest.TestCase):
    """Resets the state singletons between tests so one test's Accelerator cannot
    leak topology/precision into the next (reference `testing.py:479-490`)."""

    def tearDown(self):
        from ..state import AcceleratorState, GradientState, PartialState

        super().tearDown()
        AcceleratorState._reset_state()
        GradientState._reset_state()
        PartialState._reset_state()


class SubprocessCallException(Exception):
    pass


def run_command(command: list[str], return_stdout: bool = False, env: dict | None = None):
    """Run a CLI command, raising with captured output on failure
    (reference `testing.py:619`)."""
    if env is None:
        env = dict(os.environ)
    try:
        output = subprocess.check_output(command, stderr=subprocess.STDOUT, env=env)
        if return_stdout:
            return output.decode()
    except subprocess.CalledProcessError as e:
        raise SubprocessCallException(
            f"Command `{' '.join(command)}` failed with:\n{e.output.decode()}"
        ) from e


def execute_subprocess_async(cmd: list[str], env: dict | None = None, timeout: int = 600) -> None:
    """Run a (possibly multi-process-launching) command asynchronously, streaming
    output, raising on nonzero exit (reference `testing.py:594`)."""

    async def _run():
        proc = await asyncio.create_subprocess_exec(
            *cmd,
            stdout=asyncio.subprocess.PIPE,
            stderr=asyncio.subprocess.STDOUT,
            env=env or dict(os.environ),
        )
        out, _ = await asyncio.wait_for(proc.communicate(), timeout=timeout)
        if proc.returncode != 0:
            raise SubprocessCallException(
                f"Command `{' '.join(cmd)}` exited {proc.returncode}:\n{out.decode()}"
            )
        return out.decode()

    return asyncio.run(_run())


def get_launch_command(num_processes: int = 1, **kwargs) -> list[str]:
    """Build the CLI launch prefix (reference `get_launch_command`, `testing.py:91`)."""
    cmd = [sys.executable, "-m", "accelerate_tpu.commands.cli", "launch"]
    if num_processes > 1:
        cmd += ["--debug_cpu", str(num_processes)]
    for k, v in kwargs.items():
        cmd += [f"--{k}", str(v)]
    return cmd


DEFAULT_LAUNCH_COMMAND = get_launch_command(num_processes=2)
