"""What the Ling 3.0 flash per-layer readers share: the decode step's device
time from the trace, the program's counters per step.

A decode step of this model runs the fused paged kernel once for each latent
layer here (one in a period of six), each under its own name (`%attn.N`), so
`program_spans.decode_cycles` cuts the kernel's events into steps. The
device's busy time from one step's first kernel to the next step's first
kernel is one whole step, plus an admit program where one ran between the two;
an admit program reads every weight once over thousands of tokens, so a period
counts as a plain step when it lies within a quarter of the shortest one, and
the step's device time is the median of those (the way `steps_qwen3_next` and
`steps_kimi_k2` read theirs; the counters' reading is imported from them:
`per_step` the picks held and the experts touched a step, `live_tokens` the
latent rows alive a decode dispatch, `decode_kernels` the kernel's events).

Against a program without the counters every function returns None."""

from __future__ import annotations

import statistics

import flops_ling3 as flops
import program_spans
from steps_kimi_k2 import decode_kernels, live_tokens  # noqa: F401  (the readers take them here)
from steps_qwen3_next import FEWEST_STEPS, per_step  # noqa: F401


def step_device_ns(run: dict) -> float | None:
    """Median device busy time of one decode step in the traced slice."""
    if "_ling3_step_ns" not in run:
        run["_ling3_step_ns"] = _step_device_ns(run)
    return run["_ling3_step_ns"]


def _step_device_ns(run: dict) -> float | None:
    if decode_kernels(run) is None:
        return None
    events = next(iter(run["trace"]["per_device"].values()))
    cycles = program_spans.decode_cycles(events, flops.kinds(run["cell"].config).count("latent"))
    if isinstance(cycles, str) or len(cycles) < FEWEST_STEPS + 1:
        print(f"ling3 steps: {cycles if isinstance(cycles, str) else len(cycles)} whole steps "
              f"in the trace, too few", flush=True)
        return None
    busy = program_spans.Busy(events)
    periods = [busy.between(a[0], b[0]) for a, b in zip(cycles, cycles[1:])]
    plain = [p for p in periods if p <= 1.25 * min(periods)]
    if len(plain) < FEWEST_STEPS:
        print(f"ling3 steps: {len(plain)} of {len(periods)} periods within a quarter of the "
              f"shortest ({min(periods) / 1e6:.3f} ms), too few", flush=True)
        return None
    step = statistics.median(plain)
    print(f"ling3 steps: {len(periods)} periods between steps, {len(plain)} plain: median "
          f"{step / 1e6:.3f} ms (shortest {min(periods) / 1e6:.3f}, longest plain "
          f"{max(plain) / 1e6:.3f}); the other {len(periods) - len(plain)} hold "
          f"{(sum(periods) - sum(plain)) / 1e6:.1f} ms", flush=True)
    return step


def rows_routed_here(run: dict, part: str = "traced") -> float | None:
    """Rows a step with at least one pick on a held expert, an expert layer:
    the program's `moe_rows_routed_here` over `part` of the run."""
    bounds = run.get(part)
    if not bounds or "moe_rows_routed_here" not in (bounds.get("counters1") or {}):
        return None
    a, b = bounds.get("counters0") or {}, bounds["counters1"]
    steps = b.get("steps", 0) - a.get("steps", 0)
    if steps <= 0:
        return None
    return (b["moe_rows_routed_here"] - a.get("moe_rows_routed_here", 0)) / steps \
        / flops.expert_layers(run["cell"].config)
