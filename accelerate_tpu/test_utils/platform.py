"""Host-CPU platform forcing — the "multi-node without a cluster" vehicle.

The reference runs its distributed tests anywhere via a 2-process gloo fork
(``debug_launcher``, reference ``src/accelerate/launchers.py:269-302``). The
TPU-native equivalent multiplexes the host platform into N virtual XLA devices
so every sharding/collective path runs without hardware.

``jax.config`` beats the ``JAX_PLATFORMS`` env var, so the platform is forced
through the config, before any backend initializes. The one audited place that
does it is here; tests/conftest.py, ``__graft_entry__``, ``chip_smoke.py
--rehearsal`` and the ``BENCH_FORCE_CPU=1`` bench paths all call it.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_platform(n_devices: int | None = None) -> None:
    """Redirect this process's JAX backend to host CPU, optionally with
    ``n_devices`` virtual devices, initializing the backend eagerly.

    Must run before any JAX backend initialization; XLA_FLAGS is restored
    afterwards so child processes don't inherit the forced topology. Safe to
    call again once forced (no-op if the CPU backend already exposes enough
    devices); raises if another platform's backend already initialized.
    """
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        devs = jax.devices()
        if devs[0].platform == "cpu" and (n_devices is None or len(devs) >= n_devices):
            return
        raise RuntimeError(
            f"jax backend already initialized as {devs[0].platform} with "
            f"{len(devs)} devices; cannot re-force cpu"
            + (f" x{n_devices}" if n_devices else "")
        )

    old_flags = os.environ.get("XLA_FLAGS")
    if n_devices is not None:
        flags = old_flags or ""
        if _COUNT_FLAG in flags:
            flags = re.sub(rf"{_COUNT_FLAG}=\d+", f"{_COUNT_FLAG}={n_devices}", flags)
        else:
            flags = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
        os.environ["XLA_FLAGS"] = flags
    try:
        jax.config.update("jax_platforms", "cpu")
        platform = jax.devices()[0].platform  # initializes the CPU client
    finally:
        if n_devices is not None:
            if old_flags is None:
                os.environ.pop("XLA_FLAGS", None)
            else:
                os.environ["XLA_FLAGS"] = old_flags
    if platform != "cpu":
        raise RuntimeError(f"expected forced cpu platform, got {platform!r}")
    if n_devices is not None and len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"host platform exposes {len(jax.devices())} devices, need {n_devices}"
        )
