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
import jax.numpy as jnp
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
@pytest.mark.parametrize("kv_heads, head_dim, q_heads, itemsize, chunk_tokens, buffers", [
    (16, 64, 16, 2, 256, 4456448),  # gpt2-medium, bf16 pool: 4 x 512 KiB of chunk buffers
    (20, 64, 20, 2, 256, 5734400),  # gpt2-large: 1,280 lanes (two fp32 span buffers were 10 MiB)
    (12, 64, 12, 2, 256, 3342336),  # gpt2-small
    (2, 32, 2, 4, 256, 802816),  # tiny, float32: 64 merged lanes pad to one 128 tile
    (2, 256, 16, 2, 256, 2228224),  # the Qwen3-Next layer: 16 query heads on 2 of 256
    (20, 64, 20, 1, 256, 8355840),  # gpt2-large, int8 pool: 16-token blocks pad to 32 sublanes
    (64, 128, 64, 2, 128, 25165824),  # 8,192 lanes: past 4,096 a chunk is 128 tokens
])
def test_fused_kernel_vmem_model(kv_heads, head_dim, q_heads, itemsize, chunk_tokens, buffers):
    """Two chunk buffers and one float32 working copy a pool, the folded query
    and the accumulators, plus headroom: a function of the row's width."""
    from accelerate_tpu.ops.flash_attention import (
        _PAGED_DECODE_HEADROOM,
        PAGED_DECODE_VMEM_CAP,
        _paged_decode_chunk_blocks,
        check_paged_decode_fits,
        paged_decode_vmem_bytes,
    )

    kw = dict(q_heads=q_heads, itemsize=itemsize)
    assert _paged_decode_chunk_blocks(16, kv_heads * head_dim) * 16 == chunk_tokens
    assert paged_decode_vmem_bytes(kv_heads, head_dim, **kw) - _PAGED_DECODE_HEADROOM == buffers
    assert check_paged_decode_fits(kv_heads, head_dim, **kw) == buffers + _PAGED_DECODE_HEADROOM
    assert buffers + _PAGED_DECODE_HEADROOM <= PAGED_DECODE_VMEM_CAP


@pytest.mark.parametrize("positions", [1024, 8192, 32768])
def test_fused_kernel_asks_the_same_vmem_at_every_span(positions, monkeypatch):
    """16 heads of 64 over 1,024, 8,192 and 32,768 positions (the span-wide
    buffers of the kernel before refused 13,328): the call's scratch shapes
    and its ``vmem_limit_bytes`` do not see the block table's width."""
    from accelerate_tpu.ops import flash_attention as fa

    asked = []
    real = fa.pl.pallas_call

    def spy(kernel, *, grid_spec, compiler_params, **kw):
        asked.append(([(s.shape, s.dtype) for s in grid_spec.scratch_shapes
                       if hasattr(s, "shape")], compiler_params.vmem_limit_bytes))
        return real(kernel, grid_spec=grid_spec, compiler_params=compiler_params, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    rows, heads, d, bt = 2, 16, 64, 16

    def lower(positions):
        sds = jax.ShapeDtypeStruct
        pool = sds((2 * positions // bt, bt, heads * d), jnp.bfloat16)
        jax.jit(lambda *a: fa.paged_decode_attention(*a)).lower(
            sds((rows, heads, d), jnp.bfloat16), pool, pool,
            sds((rows, positions // bt), jnp.int32), sds((rows,), jnp.int32))

    lower(1024)
    lower(positions)
    assert asked[0] == asked[1]
    assert asked[1][1] == fa.paged_decode_vmem_bytes(heads, d, itemsize=2)


def test_fused_kernel_refuses_the_first_width_that_cannot_fit():
    """Heads of 128, float32 pool: 152 of them (19,456 lanes) fit under the
    cap, the 153rd is refused with the sizes named, whatever the span."""
    from accelerate_tpu.ops.flash_attention import (
        PAGED_DECODE_VMEM_CAP,
        check_paged_decode_fits,
    )

    assert check_paged_decode_fits(152, 128) <= PAGED_DECODE_VMEM_CAP
    with pytest.raises(ValueError, match=r"153 kv heads x head_dim 128 = 19584 lanes needs 113 MiB"):
        check_paged_decode_fits(153, 128)
    with pytest.raises(ValueError, match=r"chunks of 128 positions.*The attended span is no term of it"):
        check_paged_decode_fits(256, 128)


def test_engine_refuses_a_fused_kernel_that_cannot_fit():
    """`paged_attention="fused"` must never quietly become `gather`, and must
    not wait for the first decode step to fail."""
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.serving import ServingEngine

    module = GPT2LMHead(GPT2Config(
        vocab_size=64, n_positions=64, n_embd=160 * 128, n_layer=1, n_head=160))
    params = jax.eval_shape(lambda: module.init_params(jax.random.key(0)))
    with pytest.raises(ValueError, match="20480 lanes needs 1.. MiB.*paged_attention='gather'"):
        ServingEngine(module, params, max_concurrency=2, paged_kv=True, paged_attention="fused")


def test_engine_takes_a_span_the_span_wide_kernel_refused():
    """16 heads of 64 over 16,384 positions asked the kernel before for 136
    MiB of VMEM and failed at construction; the span is no term now."""
    from accelerate_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from accelerate_tpu.serving import PagedKVConfig, ServingEngine

    module = GPT2LMHead(GPT2Config(
        vocab_size=64, n_positions=16384, n_embd=1024, n_layer=1, n_head=16,
        dtype=jnp.bfloat16))
    params = module.init_params(jax.random.key(0))
    engine = ServingEngine(module, params, max_concurrency=2, prompt_buckets=(16,),
                           paged_kv=PagedKVConfig(num_blocks=1024),
                           paged_attention="fused")
    assert engine.paged_attention == "fused" and engine._blocks_per_slot == 1024


