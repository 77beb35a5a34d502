"""Reduction of a JAX profiler trace (`.xplane.pb`) to device numbers.

Reads with `jax.profiler.ProfileData` alone. A device plane is one whose name
starts with `/device:`; on it the line named `XLA Ops` holds one event per
executed HLO operation. Busy time is the union of those events' intervals."""

from __future__ import annotations

import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"


def load(trace_dir: str):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def device_events(profile, ops_line: str = OPS_LINE) -> dict[str, list[tuple[str, float, float]]]:
    """{device plane name: [(event name, start_ns, duration_ns)]} from the
    operations line of every device plane."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == ops_line:
                out[plane.name] = [(e.name, float(e.start_ns), float(e.duration_ns))
                                   for e in line.events]
    return out


def busy_union_ns(events) -> float:
    """Length of the union of [start, start + duration) intervals."""
    total, end = 0.0, None
    for start, stop in sorted((s, s + d) for _, s, d in events):
        if end is None or start > end:
            total += stop - start
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def sums_by_name(events) -> dict[str, float]:
    """Total device nanoseconds per event name."""
    sums: dict[str, float] = defaultdict(float)
    for name, _, dur in events:
        sums[name] += dur
    return dict(sums)


def reduce(per_device: dict, window_ns: float | None = None) -> dict:
    """Busy seconds (mean over devices), the window, per-name sums (mean over
    devices). The window is the span from the first event's start to the last
    event's end over all devices unless given: a steady slice's own extent."""
    if not per_device or not any(per_device.values()):
        return {"busy_s": 0.0, "window_s": 0.0, "by_name_s": {}}
    if window_ns is None:
        starts = [s for ev in per_device.values() for _, s, _ in ev]
        stops = [s + d for ev in per_device.values() for _, s, d in ev]
        window_ns = max(stops) - min(starts)
    n = len(per_device)
    busy = sum(busy_union_ns(ev) for ev in per_device.values()) / n
    by_name: dict[str, float] = defaultdict(float)
    for ev in per_device.values():
        for name, ns in sums_by_name(ev).items():
            by_name[name] += ns / n
    return {"busy_s": busy / 1e9, "window_s": window_ns / 1e9,
            "by_name_s": {k: v / 1e9 for k, v in by_name.items()}}


def idle_gaps(events, top: int = 10) -> list[tuple[float, float]]:
    """The longest gaps between device operations: [(start_ns, length_ns)]."""
    gaps, end = [], None
    for start, stop in sorted((s, s + d) for _, s, d in events):
        if end is not None and start > end:
            gaps.append((end, start - end))
        end = stop if end is None else max(end, stop)
    return sorted(gaps, key=lambda g: -g[1])[:top]


def idle_share_percent(trace: dict | None) -> float | None:
    """Share of the traced slice in which no operation ran on the device, in
    percent; None where there is no device trace to read."""
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
