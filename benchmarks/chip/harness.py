"""What every driver shares: the cell's files, the device check, the trace,
the per-layer readers, the memory peak and the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def overlay(data: dict, rehearsal: bool) -> dict:
    """The file's `rehearsal` sizes laid over it: the CPU walk-through only."""
    data = dict(data)
    extra = data.pop("rehearsal", {})
    if rehearsal:
        data.update(extra)
    return data


class Cell:
    """One entry of BENCHMARK.json's `workloads` with its three data files
    and the names of the metrics it reports."""

    def __init__(self, name: str, rehearsal: bool = False):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
        self.name, self.chips, self.rehearsal = name, int(entry["chips"]), rehearsal
        self.spec = overlay(load_json("workloads", f"{name}.json"), rehearsal)
        self.config = overlay(load_json("configs", f"{entry['config']}.json"), rehearsal)
        self.traffic = overlay(load_json("traffic", f"{entry['traffic']}.json"), rehearsal)

        def listed(metric):
            return name in metric.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if listed(m)]
        self.per_layer = [m for m in bench["per_layer"] if listed(m)]


def require_chips(cell: Cell) -> dict:
    """The device as JAX reports it; exits non-zero, printing no result,
    unless it is a TPU with the chips the cell asks for."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if cell.rehearsal:
        return device
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{device['count']} x {device['platform']} ({device['kind']})", file=sys.stderr)
        raise SystemExit(3)
    return device


def configure_cache() -> str:
    """The program's own helper places the cache (`<checkout>/.jax_cache`, or
    where JAX_COMPILATION_CACHE_DIR says); here only the thresholds, so that
    every program, however small, is found again by the next run."""
    import jax

    from accelerate_tpu.utils.environment import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest chip, where the backend keeps it."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(max(int(stats.get("peak_bytes_in_use", 0)), int(stats.get("bytes_in_use", 0))))
    return max(peaks) if peaks and max(peaks) > 0 else None


class CompileLog:
    """Every backend compile JAX makes in this process, with its time, so that
    a run can say how many fell inside its window (a program the warm-up
    missed; a load from the persistent cache is no compile and is not seen)."""

    def __init__(self):
        from jax import monitoring

        self.events: list[tuple[float, float]] = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_):
        if event.endswith("backend_compile_duration"):
            self.events.append((time.perf_counter(), seconds))

    def inside(self, start: float, stop: float) -> str:
        hits = [(t - start, s) for t, s in self.events if start <= t <= stop]
        where = "".join(f" [{s:.2f}s ending at +{t:.1f}s]" for t, s in hits[:8])
        return f"jax compiles inside the window {len(hits)} of {len(self.events)}{where}"


class Trace:
    """A profiler trace of a slice of the window, written under the checkout
    and removed once reduced."""

    def __init__(self, cell: Cell):
        self.dir = os.path.join(ROOT, ".bench_trace", cell.name)
        self.on = False
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.on, self.t_start = True, time.perf_counter()

    def stop(self) -> None:
        import jax

        if self.on:
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.on = False

    def reduce(self) -> dict:
        """Busy and window seconds, per-name sums, the breakdown's lists."""
        import xplane

        per_device = xplane.device_events(xplane.load(self.dir))
        out = xplane.reduce(per_device)
        out["per_device"] = per_device
        groups: dict[str, float] = {}
        for name, seconds in out["by_name_s"].items():
            groups[short_name(name)] = groups.get(short_name(name), 0.0) + seconds
        top = sorted(groups.items(), key=lambda kv: -kv[1])[:10]
        gaps = []
        for events in list(per_device.values())[:1]:
            first = min(s for _, s, _ in events)
            gaps = [[f"gap_at_{(s - first) / 1e6:.3f}ms", ns / 1e9]
                    for s, ns in xplane.idle_gaps(events)]
        out["breakdown"] = {"device_ops": [[k, v] for k, v in top], "idle_gaps": gaps}
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def short_name(name: str) -> str:
    """An operation's trace name is its whole HLO line; keep the result's
    name without its number, the opcode and, for a kernel, the custom call's
    target, so that the hundreds of `%copy.N` of one program add up."""
    import re

    head, _, rest = name.partition(" = ")
    head = re.sub(r"\.\d+$", "", head.lstrip("%"))
    op = re.search(r"[\}\)\]] ([a-z][\w\-]*)\(", rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return " ".join(x for x in (head, op.group(1) if op else "", target.group(1) if target else "") if x)


def read_layer_metrics(cell: Cell, run: dict) -> dict:
    """Each per-layer metric's own reader, found by the metric's name; a
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for metric in cell.per_layer:
        path = os.path.join(HERE, "layer_metrics", f"{metric['name']}.py")
        spec = importlib.util.spec_from_file_location("layer_metric", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        value = module.read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def judge(compared: dict) -> tuple[bool, dict]:
    """{name: (number, limit)} -> (every number at or under its limit, the
    same as {name: {"value", "limit"}}). A number that is not finite fails."""
    import math

    shown, ok = {}, True
    for name, (value, limit) in compared.items():
        value = float(value)
        shown[name] = {"value": value, "limit": limit}
        if limit is not None and not (math.isfinite(value) and value <= limit):
            ok = False
    return ok, shown


def finish(cell: Cell, device: dict, *, trace: bool, correct: bool, attempted: int, failed: int,
           end_to_end: dict, per_layer: dict, compared: dict, peak: int | None,
           trace_out: dict | None) -> None:
    """Print the compared numbers on standard error, then the result line."""
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    if trace:
        metrics = per_layer
    else:
        metrics = {k: {"value": float(v), "unit": units[k]} for k, v in end_to_end.items()
                   if k in units}
    device = dict(device, memory_peak_bytes=peak)
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if cell.rehearsal:
        # a CPU walk-through of the script: no device number under any name
        line["rehearsal"] = True
        line["metrics"] = {k: None for k in metrics}
        line["device"]["memory_peak_bytes"] = None
    elif trace and trace_out is not None:
        device["busy_s"], device["window_s"] = trace_out["busy_s"], trace_out["window_s"]
        line["breakdown"] = trace_out["breakdown"]
    line["compared"] = compared
    for name, pair in compared.items():
        print(f"compared {name} value={pair['value']!r} limit={pair['limit']!r}", file=sys.stderr)
    print(f"correct={bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
