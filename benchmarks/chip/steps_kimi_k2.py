"""What the Kimi K2 per-layer readers share: the decode step's device time
from the trace, the program's counters per step.

A decode step of this model runs the fused paged kernel once a layer, each
under its own name (`%attn.N`), so `program_spans.decode_cycles` cuts the
kernel's events into steps as it does for the GPT-2 cell (it is imported; the
GPT-2 split itself reads `n_layer` and the ring's dispatches and is left
alone). The device's busy time from one step's first kernel to the next
step's first kernel is one whole step, plus an admit program where one ran
between the two; an admit program reads every weight once over thousands of
tokens, so a period counts as a plain step when it lies within a quarter of
the shortest one, and the step's device time is the median of those (the way
`steps_qwen3_next` reads its model's; its `per_step` is imported).

Live latent rows a step are the program's own count: `serving/paged_decode/
live_tokens` summed at every decode dispatch, over the dispatches. Against a
program without the counters every function returns None."""

from __future__ import annotations

import statistics

import program_spans
from steps_qwen3_next import FEWEST_STEPS, per_step  # noqa: F401  (per_step: the readers take it here)


def decode_kernels(run: dict):
    """The decode kernel's events of the traced slice, or None."""
    trace = run.get("trace")
    if run["cell"].rehearsal or not trace or not trace.get("per_device"):
        return None
    events = next(iter(trace["per_device"].values()))
    return [e for e in events if program_spans.is_decode_kernel(e[0])]


def step_device_ns(run: dict) -> float | None:
    """Median device busy time of one decode step in the traced slice."""
    if "_kimi_k2_step_ns" not in run:
        run["_kimi_k2_step_ns"] = _step_device_ns(run)
    return run["_kimi_k2_step_ns"]


def _step_device_ns(run: dict) -> float | None:
    if decode_kernels(run) is None:
        return None
    events = next(iter(run["trace"]["per_device"].values()))
    cycles = program_spans.decode_cycles(events, int(run["cell"].config["num_hidden_layers"]))
    if isinstance(cycles, str) or len(cycles) < FEWEST_STEPS + 1:
        print(f"kimi-k2 steps: {cycles if isinstance(cycles, str) else len(cycles)} whole steps "
              f"in the trace, too few", flush=True)
        return None
    busy = program_spans.Busy(events)
    periods = [busy.between(a[0], b[0]) for a, b in zip(cycles, cycles[1:])]
    plain = [p for p in periods if p <= 1.25 * min(periods)]
    if len(plain) < FEWEST_STEPS:
        print(f"kimi-k2 steps: {len(plain)} of {len(periods)} periods within a quarter of the "
              f"shortest ({min(periods) / 1e6:.3f} ms), too few", flush=True)
        return None
    step = statistics.median(plain)
    print(f"kimi-k2 steps: {len(periods)} periods between steps, {len(plain)} plain: median "
          f"{step / 1e6:.3f} ms (shortest {min(periods) / 1e6:.3f}, longest plain "
          f"{max(plain) / 1e6:.3f}); the other {len(periods) - len(plain)} hold "
          f"{(sum(periods) - sum(plain)) / 1e6:.1f} ms", flush=True)
    return step


def live_tokens(run: dict, part: str = "traced") -> float | None:
    """Latent rows alive in a decode step, summed over the slots: the
    program's `live_tokens` count over `part` of the run, a dispatch."""
    bounds = run.get(part)
    if not bounds or "live_tokens" not in (bounds.get("counters1") or {}):
        return None
    a, b = bounds.get("counters0") or {}, bounds["counters1"]
    # a decode dispatch is a counted step: both are read at the same edges
    steps = b.get("steps", 0) - a.get("steps", 0)
    if steps <= 0:
        return None
    return (b["live_tokens"] - a.get("live_tokens", 0)) / steps
