"""Test configuration: force an 8-device CPU mesh so every sharding/collective path
runs without TPU hardware (the reference's "multi-node without a cluster" tier —
SURVEY.md §4 tier 3 — realized natively via XLA host-platform device multiplexing).

The one audited CPU-forcing defense lives in accelerate_tpu.test_utils.platform;
it must run before any JAX backend initialization, hence module level.
"""

from accelerate_tpu.test_utils.platform import force_cpu_platform

force_cpu_platform(8)

import jax  # noqa: E402
import pytest  # noqa: E402

from accelerate_tpu.utils.environment import configure_compile_cache  # noqa: E402

# Persistent XLA compilation cache: tier-1 wall time is dominated by CPU
# compiles of tiny test graphs, and the same programs recompile on every
# pytest invocation. The one helper places the cache (JAX_COMPILATION_CACHE_DIR
# if set, else <checkout>/.jax_cache); the threshold makes tiny graphs count.
configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.25)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "fault: deterministic fault-injection tests (reliability layer; "
        "seeded, so stable under tier-1's -p no:randomly)",
    )
    config.addinivalue_line(
        "markers",
        "serving: continuous-batching serving engine tests — the standalone "
        "serving suite is `pytest -m serving`",
    )
    config.addinivalue_line(
        "markers",
        "prefix_cache: prefix KV-cache reuse tests (serving/prefix_cache.py) "
        "— run standalone with `pytest -m prefix_cache`",
    )
    config.addinivalue_line(
        "markers",
        "sharded: mesh-sharded serving tests (engine ``mesh=``; need >= 4 "
        "host devices, provided by the force_cpu_platform(8) above — run "
        "standalone with `pytest -m sharded`",
    )
    config.addinivalue_line(
        "markers",
        "recovery: serving crash-recovery tests (request journal, engine "
        "snapshot/resume, preemption drain — docs/reliability.md \"Serving "
        "recovery\") — run standalone with `pytest -m recovery`",
    )
    config.addinivalue_line(
        "markers",
        "trace: request-level tracing, Perfetto export, and SLO-goodput "
        "tests (serving/trace.py — docs/observability.md) — run standalone "
        "with `pytest -m trace`",
    )
    config.addinivalue_line(
        "markers",
        "telemetry: continuous telemetry / memory-capacity accounting tests "
        "(serving/telemetry.py, engine memory_stats/capacity_headroom — "
        "docs/observability.md \"Continuous telemetry\") — run standalone "
        "with `pytest -m telemetry`",
    )
    config.addinivalue_line(
        "markers",
        "paged: paged block-table KV serving tests (engine ``paged_kv=``, "
        "models/kv_cache.py BlockAllocator — docs/serving.md \"Paged KV\") — "
        "run standalone with `pytest -m paged`",
    )
    config.addinivalue_line(
        "markers",
        "supervisor: self-healing serving tests (engine supervisor restart "
        "ladder, overload brownout, journal auto-compaction — "
        "docs/reliability.md \"Self-healing\") — run standalone with "
        "`pytest -m supervisor`",
    )
    config.addinivalue_line(
        "markers",
        "speculation: speculative-decoding tests (drafters, batched verify, "
        "block-table rollback — docs/serving.md \"Speculative decoding\") — "
        "run standalone with `pytest -m speculation`",
    )
    config.addinivalue_line(
        "markers",
        "cluster: multi-replica serving cluster tests (prefix/health-aware "
        "routing, journal-backed migration — docs/serving.md \"Multi-replica "
        "serving\") — run standalone with `pytest -m cluster`",
    )
    config.addinivalue_line(
        "markers",
        "tier: host-RAM KV tier tests (engine ``kv_tier=``, block spill / "
        "request hibernation / wake cost model — docs/serving.md \"KV "
        "tiering & hibernation\") — run standalone with `pytest -m tier`",
    )
    config.addinivalue_line(
        "markers",
        "autoscaler: elastic fleet tests (serving/autoscaler.py scale-up / "
        "drain-and-retire / dead-replica replacement / thrash hysteresis — "
        "docs/reliability.md \"Elastic fleet\") — run standalone with "
        "`pytest -m autoscaler`",
    )
    config.addinivalue_line(
        "markers",
        "quant: quantized serving tests (int8 paged KV pools with sibling "
        "scale planes, engine ``weight_quant=`` int8/nf4 packed weights, "
        "per-mode parity oracles — docs/serving.md \"Quantized serving\") — "
        "run standalone with `pytest -m quant`",
    )


@pytest.fixture
def fault_injection():
    """Seeded fault-injection activator for `pytest.mark.fault` tests.

    Yields a factory: ``activate(*specs, seed=...)`` builds a
    `reliability.FaultInjector` over the given `FaultSpec`s and activates it
    for the rest of the test (deactivated on teardown, nesting preserved).
    The fixed default seed keeps every probabilistic spec deterministic under
    tier-1's ``-p no:randomly``.
    """
    from accelerate_tpu.reliability import FaultInjector, faults

    active = []

    def activate(*specs, seed=1234):
        injector = FaultInjector(seed=seed, specs=specs)
        cm = faults.inject(injector)
        cm.__enter__()
        active.append(cm)
        return injector

    yield activate
    while active:
        active.pop().__exit__(None, None, None)


@pytest.fixture(autouse=True)
def reset_singletons():
    """Reset state singletons between tests (reference `AccelerateTestCase.tearDown`
    → `AcceleratorState._reset_state()`, `test_utils/testing.py:479-490`)."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    GradientState._reset_state()
    PartialState._reset_state()
