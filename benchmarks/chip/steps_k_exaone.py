"""What the K-EXAONE per-layer readers share: the decode step's device time
from the trace, the events that read the window rings, the program's counters
per step.

A decode step of this model runs the fused paged kernel once, on the one
full-attention layer (`%attn.N`, `program_spans.is_decode_kernel`), so that
kernel's events, in order, mark the steps, as in `steps_qwen3_next`: the
device's busy time from one kernel's start to the next one's is one whole
step, plus an admit program where one ran between the two; a period counts
as a plain step when it lies within a quarter of the shortest one, and the
step's device time is the median of those; what the other periods hold
beyond one step is the admits' (`periods`). The sliding layers read their
rings, `[slots, window, kv_heads * head_dim]` in the compute dtype; the events
whose HLO line names that shape, and no admit group's ring, are the ones that
read or write them (found by shape, as every reader here finds its events).
The counters' reading is imported: `per_step` the picks held and the experts
touched a step, `live_tokens` the full layer's keys and values alive a decode
dispatch; `window_rows` is the ring rows attended a step, summed over the
sliding layers and the live slots.

Against a program without the counters or the kernel every function returns
None."""

from __future__ import annotations

import statistics

import flops_k_exaone as flops
import program_spans
from steps_kimi_k2 import live_tokens  # noqa: F401  (the readers take it here)
from steps_qwen3_next import FEWEST_STEPS, per_step  # noqa: F401


def _events(run: dict):
    trace = run.get("trace")
    if run["cell"].rehearsal or not trace or not trace.get("per_device"):
        return None
    return next(iter(trace["per_device"].values()))


def ring_shape(cfg: dict, rows: int) -> str:
    return f"[{rows},{int(cfg['sliding_window'])},{flops.kv_width(cfg)}]"


def full_kernels(run: dict):
    """The fused kernel's events on the full layer's pool, or None."""
    events = _events(run)
    if events is None:
        return None
    ring = ring_shape(run["cell"].config, int(run["cell"].spec["engine"]["max_concurrency"]))
    return [e for e in events if program_spans.is_decode_kernel(e[0]) and ring not in e[0]]


def ring_events(run: dict):
    """The decode steps' events that read or write the rings, or None."""
    events = _events(run)
    if events is None:
        return None
    cfg, engine = run["cell"].config, run["cell"].spec["engine"]
    ring = ring_shape(cfg, int(engine["max_concurrency"]))
    admits = [ring_shape(cfg, 1 << i) for i in range(int(engine.get("admit_batch", 4)).bit_length())]
    return [e for e in events if ring in e[0] and not any(a in e[0] for a in admits)]


def step_device_ns(run: dict) -> float | None:
    """Median device busy time of one decode step in the traced slice."""
    split = periods(run)
    return None if split is None else split["step_ns"]


def periods(run: dict) -> dict | None:
    """The traced slice's busy time between successive decode kernels:
    {"step_ns": the plain periods' median, "all_ns": every period's busy time
    summed, "admit_ns": what the periods holding an admit program spend beyond
    one step each}, or None."""
    if "_k_exaone_periods" not in run:
        run["_k_exaone_periods"] = _periods(run)
    return run["_k_exaone_periods"]


def _periods(run: dict) -> dict | None:
    kernels = full_kernels(run)
    if kernels is None:
        return None
    starts = sorted(e[1] for e in kernels)
    if len(starts) < FEWEST_STEPS + 1:
        print(f"k-exaone steps: {len(starts)} decode kernels in the trace, too few", flush=True)
        return None
    busy = program_spans.Busy(_events(run))
    spans = [busy.between(a, b) for a, b in zip(starts, starts[1:])]
    plain = [p for p in spans if p <= 1.25 * min(spans)]
    if len(plain) < FEWEST_STEPS:
        print(f"k-exaone steps: {len(plain)} of {len(spans)} periods within a quarter of the "
              f"shortest ({min(spans) / 1e6:.3f} ms), too few", flush=True)
        return None
    step = statistics.median(plain)
    admits = [p - step for p in spans if p > 1.25 * min(spans)]
    print(f"k-exaone steps: {len(spans)} periods between decode kernels, {len(plain)} plain: "
          f"median {step / 1e6:.3f} ms (shortest {min(spans) / 1e6:.3f}, longest plain "
          f"{max(plain) / 1e6:.3f}); the other {len(admits)} hold "
          f"{(sum(spans) - sum(plain)) / 1e6:.1f} ms", flush=True)
    return {"step_ns": step, "all_ns": sum(spans), "admit_ns": sum(admits)}


def window_rows(run: dict, part: str = "traced") -> float | None:
    """Ring rows attended a decode step, summed over the sliding layers and
    the live slots: the program's `window_rows` over `part` of the run."""
    bounds = run.get(part)
    if not bounds or "window_rows" not in (bounds.get("counters1") or {}):
        return None
    a, b = bounds.get("counters0") or {}, bounds["counters1"]
    steps = b.get("steps", 0) - a.get("steps", 0)
    if steps <= 0:
        return None
    return (b["window_rows"] - a.get("window_rows", 0)) / steps
