"""What the Qwen3-Next per-layer readers share: the decode step's device time
from the trace, the program's step counters per step, the live keys and values.

A decode step of this model runs the fused paged kernel once (one full-
attention layer in the period of four), so the kernel's events, in order, mark
the steps (`program_spans.is_decode_kernel`: the readers of the GPT-2 cell find
theirs the same way). The device's busy time from one kernel's start to the
next one's is one whole step, plus an admit program where one ran between the
two. An admit program reads every weight once, so it adds at least as much as
a step takes; a period counts as a plain step when it lies within a quarter of
the shortest one, and the step's device time is the median of those.

Against a program without the counters (the parent of the PR that brought the
model cannot run the cell at all) every function returns None."""

from __future__ import annotations

import statistics

import program_spans

FEWEST_STEPS = 8


def step_device_ns(run: dict) -> float | None:
    """Median device busy time of one decode step in the traced slice."""
    if "_qwen3_next_step_ns" not in run:
        run["_qwen3_next_step_ns"] = _step_device_ns(run)
    return run["_qwen3_next_step_ns"]


def _step_device_ns(run: dict) -> float | None:
    trace = run.get("trace")
    if run["cell"].rehearsal or not trace or not trace.get("per_device"):
        return None
    events = next(iter(trace["per_device"].values()))
    starts = sorted(e[1] for e in events if program_spans.is_decode_kernel(e[0]))
    if len(starts) < FEWEST_STEPS + 1:
        print(f"qwen3-next steps: {len(starts)} decode kernels in the trace, too few", flush=True)
        return None
    busy = program_spans.Busy(events)
    periods = [busy.between(a, b) for a, b in zip(starts, starts[1:])]
    plain = [p for p in periods if p <= 1.25 * min(periods)]
    if len(plain) < FEWEST_STEPS:
        print(f"qwen3-next steps: {len(plain)} of {len(periods)} periods within a quarter of "
              f"the shortest ({min(periods) / 1e6:.3f} ms), too few", flush=True)
        return None
    step = statistics.median(plain)
    print(f"qwen3-next steps: {len(periods)} periods between decode kernels, {len(plain)} plain: "
          f"median {step / 1e6:.3f} ms (shortest {min(periods) / 1e6:.3f}, longest plain "
          f"{max(plain) / 1e6:.3f}); the other {len(periods) - len(plain)} hold "
          f"{(sum(periods) - sum(plain)) / 1e6:.1f} ms", flush=True)
    return step


def per_step(run: dict, part: str = "traced") -> dict | None:
    """The program's step counters over `part` of the run ("traced" or
    "window"): {"steps", "picks_held", "experts_touched"}, the two counts a
    decode step (all layers); None where the program counted nothing."""
    bounds = run.get(part)
    if not bounds or not bounds.get("counters1"):
        return None
    a, b = bounds.get("counters0") or {}, bounds["counters1"]
    steps = b.get("steps", 0) - a.get("steps", 0)
    if steps <= 0:
        return None
    return {"steps": steps,
            "picks_held": (b["moe_picks_held"] - a.get("moe_picks_held", 0)) / steps,
            "experts_touched": (b["moe_experts_touched"] - a.get("moe_experts_touched", 0)) / steps}


def live_tokens(run: dict) -> float | None:
    """Keys and values alive in a decode step, summed over the slots: not
    reported by the program, so taken as `paged_decode_roofline.serve` takes
    them, from the requests that finished in the window, each weighing in for
    as many steps as it decoded at its mean context, times the slots, which a
    closed loop keeps full."""
    window = run.get("window")
    if not window or not window.get("done"):
        return None
    weights = [len(out.tokens) for _, out in window["done"]]
    contexts = [len(item["prompt"]) + len(out.tokens) / 2.0 for item, out in window["done"]]
    rows = int(run["cell"].spec["engine"]["max_concurrency"])
    return rows * sum(w * c for w, c in zip(weights, contexts)) / sum(weights)
