"""Environment-variable parsing and host introspection.

Capability parity: reference `src/accelerate/utils/environment.py` (str_to_bool,
parse_flag_from_env, CPU topology probing). TPU-native: the launcher <-> library
contract uses ``ACCELERATE_TPU_*`` variables plus JAX's own coordinator variables
(``JAX_COORDINATOR_ADDRESS``/``JAX_PROCESS_ID``/``JAX_NUM_PROCESSES``) instead of
torch.distributed's ``RANK``/``WORLD_SIZE``/``MASTER_ADDR`` rendezvous contract.
"""

from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Any


def on_tpu_platform() -> bool:
    """True when the default JAX backend is a TPU — THE platform probe (kernels
    pick compiled-vs-interpret and dispatchers pick flash-vs-xla off this)."""
    import jax

    return jax.devices()[0].platform == "tpu"


def device_description() -> dict[str, Any]:
    """The default backend as JAX reports it — what every measured result is
    stamped with: ``{"platform", "kind", "count"}``."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_tpu(what: str, rehearse: str = "") -> dict[str, Any]:
    """Measured entry points (`chip_smoke.py`, `bench.py`, the serving bench,
    `tools/profile_step.py`) call this once at start, so a run that found no
    chip exits non-zero instead of timing the CPU backend or the Pallas
    interpreter. ``rehearse`` says how that script's CPU run is asked for by
    name. Returns `device_description()`."""
    device = device_description()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"{what} measures the TPU; JAX found platform={device['platform']!r} "
            f"({device['count']} x {device['kind']!r}). A run on another backend "
            f"is a rehearsal and must be asked for by name{rehearse and ': ' + rehearse}."
        )
    return device


def device_memory_stats(device: Any) -> dict[str, int] | None:
    """``device.memory_stats()``, or None on a backend that keeps none (the
    CPU). A TPU always reports its HBM; one that does not is an error here,
    not an 8 GiB guess or a silently missing gauge."""
    stats = device.memory_stats()
    if device.platform == "tpu" and (not stats or "bytes_limit" not in stats):
        raise RuntimeError(
            f"{device} reports no bytes_limit in memory_stats() ({stats!r}); "
            "placement and headroom cannot be computed for it"
        )
    return stats or None


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, nothing is
    touched. Unset: ``<checkout>/.jax_cache``, resolved from this file (the
    directory is part of the cache key, so it must not move between runs; a
    chip machine's copy of the repo is not a git checkout). This is the only
    place the repo sets ``jax_compilation_cache_dir``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    cache_dir = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def str_to_bool(value: str) -> int:
    """Convert a truthy/falsy string to 1/0 (raises on anything else)."""
    value = value.lower()
    if value in ("y", "yes", "t", "true", "on", "1"):
        return 1
    if value in ("n", "no", "f", "false", "off", "0"):
        return 0
    raise ValueError(f"invalid truth value {value!r}")


def parse_flag_from_env(key: str, default: bool = False) -> bool:
    value = os.environ.get(key, None)
    if value is None:
        return default
    try:
        return bool(str_to_bool(value))
    except ValueError:
        raise ValueError(f"If set, {key} must be yes or no, got {value!r}.")


def parse_choice_from_env(key: str, default: str = "no") -> str:
    return os.environ.get(key, str(default))


def parse_int_from_env(key: str, default: int) -> int:
    """Integer env knob; empty/whitespace values fall back to the default
    (kernel block sizes, sweep knobs)."""
    raw = os.environ.get(key, "").strip()
    return int(raw) if raw else default


def get_int_from_env(keys: list[str], default: int) -> int:
    """Return the first set integer among ``keys`` (reference: same helper for PMI/OMPI)."""
    for key in keys:
        value = os.environ.get(key, None)
        if value is not None:
            return int(value)
    return default


@contextlib.contextmanager
def patch_environment(**kwargs: Any):
    """Temporarily set environment variables inside the context, restoring after.

    Mirrors reference `utils/other.py:patch_environment`. Keys are upper-cased.
    """
    existing: dict[str, str] = {}
    for key, value in kwargs.items():
        key = key.upper()
        if key in os.environ:
            existing[key] = os.environ[key]
        os.environ[key] = str(value)
    try:
        yield
    finally:
        for key in kwargs:
            key = key.upper()
            if key in existing:
                os.environ[key] = existing[key]
            else:
                os.environ.pop(key, None)


@contextlib.contextmanager
def clear_environment():
    """Temporarily empty os.environ inside the context (reference `utils/other.py:clear_environment`)."""
    saved = dict(os.environ)
    os.environ.clear()
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(saved)


def are_we_under_multihost_env() -> bool:
    """True when launcher-provided multi-host coordinates are present."""
    return "JAX_COORDINATOR_ADDRESS" in os.environ or "ACCELERATE_TPU_NUM_PROCESSES" in os.environ
