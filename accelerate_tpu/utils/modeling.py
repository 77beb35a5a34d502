"""Model introspection: sizes, memory budgets, tied weights, flattening.

Capability parity: reference `src/accelerate/utils/modeling.py` (1907 LoC) — the
pieces that aren't torch-specific: `compute_module_sizes`, `calculate_maximum_sizes`
(estimate-memory backend), `find_tied_parameters`, `get_max_memory`, and the
flat <-> nested param-tree converters the offload/dispatch stack uses.
"""

from __future__ import annotations

import math
import re
from typing import Any

import jax
import numpy as np

from .environment import device_memory_stats


def dtype_byte_size(dtype: Any) -> float:
    if hasattr(dtype, "itemsize"):
        return dtype.itemsize
    return np.dtype(dtype).itemsize


def flatten_params(params: Any, prefix: str = "", sep: str = "/") -> dict[str, Any]:
    """Nested pytree -> {'a/b/c': leaf} flat dict."""
    flat: dict[str, Any] = {}
    if isinstance(params, dict):
        for k, v in params.items():
            flat.update(flatten_params(v, f"{prefix}{k}{sep}", sep))
    else:
        flat[prefix[: -len(sep)]] = params
    return flat


def unflatten_params(flat: dict[str, Any], sep: str = "/") -> Any:
    nested: dict[str, Any] = {}
    for key, value in flat.items():
        parts = key.split(sep)
        node = nested
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return nested


def named_module_tensors(params: Any) -> list[tuple[str, Any]]:
    return sorted(flatten_params(params).items())


def compute_module_sizes(params: Any, dtype: Any | None = None) -> dict[str, int]:
    """Bytes per module path, aggregated up the tree (reference
    `compute_module_sizes`). Key "" is the total."""
    sizes: dict[str, int] = {}
    for name, leaf in named_module_tensors(params):
        nbytes = int(math.prod(getattr(leaf, "shape", ()) or (1,))) * int(
            dtype_byte_size(dtype or leaf.dtype)
        )
        parts = name.split("/")
        for i in range(len(parts) + 1):
            sizes["/".join(parts[:i])] = sizes.get("/".join(parts[:i]), 0) + nbytes
    return sizes


def calculate_maximum_sizes(params: Any) -> tuple[int, tuple[int, str]]:
    """(total bytes, (largest leaf bytes, its name)) — reference
    `calculate_maximum_sizes` used by estimate-memory."""
    total = 0
    largest = (0, "")
    for name, leaf in named_module_tensors(params):
        nbytes = int(math.prod(getattr(leaf, "shape", ()) or (1,))) * int(dtype_byte_size(leaf.dtype))
        total += nbytes
        if nbytes > largest[0]:
            largest = (nbytes, name)
    return total, largest


def find_tied_parameters(params: Any) -> list[list[str]]:
    """Groups of parameter names sharing the same underlying buffer (reference
    `find_tied_parameters`, `modeling.py:605`). In JAX pytrees ties show up as
    identical array objects (same id) appearing at several paths."""
    by_id: dict[int, list[str]] = {}
    for name, leaf in named_module_tensors(params):
        if hasattr(leaf, "shape"):
            by_id.setdefault(id(leaf), []).append(name)
    return [names for names in by_id.values() if len(names) > 1]


def get_max_memory(max_memory: dict | None = None) -> dict[str, int]:
    """Memory budget per tier: each accelerator device's free HBM, host RAM, disk
    (reference `get_max_memory`, `modeling.py:797`)."""
    if max_memory is not None:
        return dict(max_memory)
    out: dict[str, int] = {}
    for i, dev in enumerate(jax.local_devices()):
        stats = device_memory_stats(dev)
        # a backend without stats (host CPU devices) gets a nominal 8 GiB
        free = stats["bytes_limit"] - stats["bytes_in_use"] if stats else 8 * 1024**3
        out[f"device:{i}"] = int(free * 0.9)
    try:
        with open("/proc/meminfo") as f:
            meminfo = f.read()
        avail_kb = int(re.search(r"MemAvailable:\s+(\d+)", meminfo).group(1))
        out["cpu"] = avail_kb * 1024 // 2
    except Exception:
        out["cpu"] = 8 * 1024**3
    out["disk"] = 1 << 62
    return out


def get_balanced_memory(
    params: Any,
    num_devices: int | None = None,
    no_split_module_classes: Any = None,
    low_zero: bool = False,
) -> dict[str, int]:
    """Per-device budgets that spread the model evenly (reference
    `get_balanced_memory`, `modeling.py:951`): each device gets at least the
    largest indivisible block (else the fit degenerates to first-fill), and
    ``low_zero`` reserves device 0 for activations/generation by halving its
    share, as the reference does for generate-heavy workloads."""
    total, (largest_leaf, _) = calculate_maximum_sizes(params)
    sizes = compute_module_sizes(params)
    top_blocks = [v for k, v in sizes.items() if k and "/" not in k]
    largest_block = max(top_blocks, default=largest_leaf)
    n = num_devices or len(jax.local_devices())
    per = max(-(-total // n), largest_block)
    per = int(per * 1.1)  # fit slack, as in the reference's buffer margin
    budget = {f"device:{i}": per for i in range(n)}
    if low_zero and n > 1:
        budget["device:0"] = per // 2
    budget["cpu"] = get_max_memory()["cpu"]
    budget["disk"] = 1 << 62
    return budget
