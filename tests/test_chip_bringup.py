"""What the chip bring-up added that a CPU can check: one strict platform
probe, the compile-cache helper, measured entry points that fail without a
chip, imports that initialise no backend, and the fused paged-decode kernel's
fit check at engine construction. What only a chip can check is in
`chip_smoke.py`; what libtpu can compile without one, in
`tests/test_tpu_aot_compile.py`."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from accelerate_tpu.utils import environment

REPO = Path(__file__).resolve().parent.parent


def _run(args, **env):
    full = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    full.update(PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=str(REPO), env=full, capture_output=True, text=True,
        timeout=300,
    )


# ------------------------------------------------------------ platform probe
class _Device:
    def __init__(self, platform, kind="x", stats=None):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,expected", [("tpu", True), ("cpu", False), ("gpu", False)])
def test_probe_is_strict(monkeypatch, platform, expected):
    monkeypatch.setattr(jax, "devices", lambda: [_Device(platform)])
    assert environment.on_tpu_platform() is expected


def test_require_tpu_names_what_it_found(monkeypatch):
    with pytest.raises(SystemExit, match=r"bench\.py measures the TPU.*platform='cpu'.*: X=1\."):
        environment.require_tpu("bench.py", rehearse="X=1")
    monkeypatch.setattr(jax, "devices", lambda: [_Device("tpu", "TPU v5 lite")] * 4)
    assert environment.require_tpu("bench.py") == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_tpu_without_memory_stats_is_an_error():
    assert environment.device_memory_stats(_Device("cpu", stats=None)) is None
    stats = {"bytes_limit": 16 << 30, "bytes_in_use": 1 << 30}
    assert environment.device_memory_stats(_Device("tpu", stats=stats)) == stats
    with pytest.raises(RuntimeError, match="bytes_limit"):
        environment.device_memory_stats(_Device("tpu", stats=None))
    with pytest.raises(RuntimeError, match="bytes_limit"):
        environment.device_memory_stats(_Device("tpu", stats={"bytes_in_use": 1}))


# ------------------------------------------------------------- compile cache
def test_cache_helper_leaves_an_outside_placement_alone(monkeypatch):
    writes = []
    monkeypatch.setattr(jax.config, "update", lambda *a: writes.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert environment.configure_compile_cache() == "/placed/from/outside"
    assert writes == []


def test_cache_helper_defaults_to_the_checkout(monkeypatch):
    writes = []
    monkeypatch.setattr(jax.config, "update", lambda *a: writes.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(REPO / ".jax_cache")
    assert environment.configure_compile_cache() == expected
    assert writes == [("jax_compilation_cache_dir", expected)]


def test_only_the_helper_sets_a_cache_directory():
    hits = subprocess.run(
        ["grep", "-rln", "--include=*.py", "--exclude-dir=_*", "--exclude-dir=chiprun_out",
         "jax_compilation_cache_dir", "."],
        cwd=str(REPO), capture_output=True, text=True,
    ).stdout.split()
    assert sorted(hits) == ["./accelerate_tpu/utils/environment.py", "./tests/test_chip_bringup.py"]


# ------------------------------------------------- no backend before it is asked
def test_imports_initialise_no_backend():
    """One process per chip: a launcher parent that merely imports the package
    (or any CLI command module) must not open the device its child needs."""
    code = (
        "import importlib, pkgutil\n"
        "import accelerate_tpu, accelerate_tpu.commands as commands\n"
        "for m in pkgutil.iter_modules(commands.__path__):\n"
        "    importlib.import_module(f'accelerate_tpu.commands.{m.name}')\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    done = _run(["-c", code])
    assert done.returncode == 0, done.stderr[-2000:]


# --------------------------------------------- measured paths fail without a chip
@pytest.mark.parametrize("script", [
    "bench.py", "chip_smoke.py",
    pytest.param("benchmarks/bench_serving.py", marks=pytest.mark.slow),
    pytest.param("tools/profile_step.py", marks=pytest.mark.slow),
])
def test_measured_entry_points_exit_nonzero_without_a_chip(script):
    done = _run([script])
    assert done.returncode != 0
    assert "measures the TPU" in done.stderr
    assert '"metric"' not in done.stdout and '"ok"' not in done.stdout


def test_chip_smoke_needs_the_repo_around_it(tmp_path):
    (tmp_path / "chip_smoke.py").write_bytes((REPO / "chip_smoke.py").read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(tmp_path), env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0 and '"ok"' not in done.stdout


@pytest.mark.slow
def test_bench_force_cpu_labels_its_row():
    import json

    done = _run(["bench.py"], BENCH_FORCE_CPU="1", BENCH_ITERS="2")
    assert done.returncode == 0, done.stderr[-2000:]
    row = json.loads(done.stdout.strip().splitlines()[-1])
    assert row["vs_baseline"] is None and row["detail"]["mfu"] is None
    assert row["detail"]["model"] == "gpt2-tiny(cpu)"
    assert row["detail"]["platform"] == "cpu" and row["detail"]["device_count"] == 1


# -------------------------------------------------- one process per chip
def test_notebook_launcher_refuses_to_share_tpu_chips(monkeypatch):
    from accelerate_tpu import launchers

    assert launchers._workers_could_land_on_tpu() is False  # the suite pins cpu
    monkeypatch.delenv("ACCELERATE_TPU_NUM_PROCESSES", raising=False)
    monkeypatch.setattr(launchers, "_workers_could_land_on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="a chip belongs to one process"):
        launchers.notebook_launcher(lambda: None, num_processes=2)


# ------------------------------------------- fused paged decode: fit, or refuse
@pytest.mark.parametrize("span, heads, head_dim, buffers", [
    (1024, 16, 64, 8 << 20),  # gpt2-medium: two 4 MiB [1024, 1024] fp32 buffers
    (1024, 20, 64, 10 << 20),  # gpt2-large: 1280 lanes, no padding (was 25 MB)
    (1024, 12, 64, 6 << 20),  # gpt2-small
    (128, 2, 32, 2 * 128 * 128 * 4),  # tiny: 64 merged lanes pad to one 128 tile
    (8192, 16, 64, 64 << 20),  # refused before the pool folded its heads
])
def test_fused_kernel_vmem_model(span, heads, head_dim, buffers):
    """Two fp32 ``[span, ceil128(kv_heads * head_dim)]`` buffers plus headroom."""
    from accelerate_tpu.ops.flash_attention import (
        PAGED_DECODE_VMEM_CAP,
        check_paged_decode_fits,
        paged_decode_vmem_bytes,
    )

    headroom = paged_decode_vmem_bytes(0, heads, head_dim)
    assert paged_decode_vmem_bytes(span, heads, head_dim) - headroom == buffers
    assert check_paged_decode_fits(span, heads, head_dim) == buffers + headroom
    assert buffers + headroom <= PAGED_DECODE_VMEM_CAP


def test_fused_kernel_refuses_the_first_span_that_cannot_fit():
    """gpt2-medium heads: 13,312 positions fill the cap to the byte, the next
    block of 16 is refused, and so is the next power of two."""
    from accelerate_tpu.ops.flash_attention import (
        PAGED_DECODE_VMEM_CAP,
        check_paged_decode_fits,
    )

    assert check_paged_decode_fits(13312, 16, 64) == PAGED_DECODE_VMEM_CAP
    with pytest.raises(ValueError, match=r"13328 positions x 16 kv heads x head_dim 64"):
        check_paged_decode_fits(13328, 16, 64)
    with pytest.raises(ValueError, match=r"needs 136 MiB \(two fp32 \[span, kv_heads\*head_dim\]"):
        check_paged_decode_fits(16384, 16, 64)


def test_engine_refuses_a_fused_kernel_that_cannot_fit():
    """`paged_attention="fused"` must never quietly become `gather`, and must
    not wait for the first decode step to fail."""
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.serving import ServingEngine

    module = GPT2LMHead(GPT2Config(
        vocab_size=64, n_positions=16384, n_embd=1024, n_layer=1, n_head=16))
    params = jax.eval_shape(lambda: module.init_params(jax.random.key(0)))
    with pytest.raises(ValueError, match="needs 136 MiB.*paged_attention='gather'"):
        ServingEngine(module, params, max_concurrency=2, paged_kv=True, paged_attention="fused")
