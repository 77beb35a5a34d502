"""What the MiMo-V2 per-layer readers share: the decode step's device time
from the trace, the events of the two attention kernels this model changed,
the program's counters per step.

A decode step of this model runs the fused paged kernel on each of its two
full-attention layers (`%attn.N`, `program_spans.is_decode_kernel`, whose
line names no ring) and on each sliding layer's rings (`%window_attn.N`,
whose line names them). The events of ONE of the two full-layer kernels, the
one whose HLO name sorts first, mark the steps: the device's busy time from
one such event's start to the next one's is one whole step, plus an admit
program where one ran between the two, split as `steps_k_exaone.periods`
splits K-EXAONE's. The rings are `[slots, window, 8 x 192]` (keys) and
`[slots, window, 8 x 128]` (values) in the compute dtype; the events whose
line names either, and no admit row's ring, read or write them. An admit runs
the band flash kernel with the sinks on each sliding layer: a
`%window_attn.N` custom call of an admit program, whose line names no ring
and names its queries `[rows, 64, bucket, 192]`. The counters' reading is
imported (`live_tokens`, `per_step`, `window_rows`).

Against a program without the counters or the kernels every function returns
None."""

from __future__ import annotations

import re
import statistics

import program_spans
from reference.mimo_v2 import heads
from steps_k_exaone import FEWEST_STEPS, live_tokens, per_step, window_rows  # noqa: F401  (the readers take them here)


def _events(run: dict):
    trace = run.get("trace")
    if run["cell"].rehearsal or not trace or not trace.get("per_device"):
        return None
    return next(iter(trace["per_device"].values()))


def ring_shapes(cfg: dict, rows: int) -> tuple[str, str]:
    """The key ring's and the value ring's shapes as an HLO line names them."""
    _, hkv, d, dv = heads(cfg, True)
    w = int(cfg["sliding_window"])
    return f"[{rows},{w},{hkv * d}]", f"[{rows},{w},{hkv * dv}]"


def _rows(run: dict) -> int:
    return int(run["cell"].spec["engine"]["max_concurrency"])


def _admit_rings(run: dict) -> list[str]:
    cfg, engine = run["cell"].config, run["cell"].spec["engine"]
    return [s for i in range(int(engine.get("admit_batch", 4)).bit_length())
            for s in ring_shapes(cfg, 1 << i)]


def full_kernels(run: dict):
    """The fused kernel's events on the full layers' pool, or None."""
    events = _events(run)
    if events is None:
        return None
    rings = ring_shapes(run["cell"].config, _rows(run))
    return [e for e in events if program_spans.is_decode_kernel(e[0]) and not any(r in e[0] for r in rings)]


def step_marks(run: dict):
    """The events of one full layer's kernel, one a decode step, or None."""
    kernels = full_kernels(run)
    if not kernels:
        return None
    first = min(e[0].split(" = ")[0] for e in kernels)
    return [e for e in kernels if e[0].split(" = ")[0] == first]


def ring_events(run: dict):
    """The decode steps' events that read or write the rings, or None."""
    events = _events(run)
    if events is None:
        return None
    rings, admits = ring_shapes(run["cell"].config, _rows(run)), _admit_rings(run)
    return [e for e in events if any(r in e[0] for r in rings) and not any(a in e[0] for a in admits)]


def band_events(run: dict):
    """[(event, admit rows, bucket)] of the band kernel in the admits, or None."""
    events = _events(run)
    if events is None:
        return None
    cfg = run["cell"].config
    hq, _, d, _ = heads(cfg, True)
    rings = ring_shapes(cfg, _rows(run))
    query = re.compile(rf"\[(\d+),{hq},(\d+),{d}\]")
    out = []
    for e in events:
        if not e[0].startswith("%window_attn") or "tpu_custom_call" not in e[0] or any(r in e[0] for r in rings):
            continue
        found = query.search(e[0])
        if found:
            out.append((e, int(found.group(1)), int(found.group(2))))
    return out


def step_device_ns(run: dict) -> float | None:
    """Median device busy time of one decode step in the traced slice."""
    split = periods(run)
    return None if split is None else split["step_ns"]


def periods(run: dict) -> dict | None:
    """The traced slice's busy time between successive step marks:
    {"step_ns": the plain periods' median, "all_ns": every period's busy time
    summed, "admit_ns": what the periods holding an admit program spend beyond
    one step each}, or None."""
    if "_mimo_v2_periods" not in run:
        run["_mimo_v2_periods"] = _periods(run)
    return run["_mimo_v2_periods"]


def _periods(run: dict) -> dict | None:
    marks = step_marks(run)
    if marks is None:
        return None
    starts = sorted(e[1] for e in marks)
    if len(starts) < FEWEST_STEPS + 1:
        print(f"mimo-v2 steps: {len(starts)} step marks in the trace, too few", flush=True)
        return None
    busy = program_spans.Busy(_events(run))
    spans = [busy.between(a, b) for a, b in zip(starts, starts[1:])]
    plain = [p for p in spans if p <= 1.25 * min(spans)]
    if len(plain) < FEWEST_STEPS:
        print(f"mimo-v2 steps: {len(plain)} of {len(spans)} periods within a quarter of the "
              f"shortest ({min(spans) / 1e6:.3f} ms), too few", flush=True)
        return None
    step = statistics.median(plain)
    admits = [p - step for p in spans if p > 1.25 * min(spans)]
    print(f"mimo-v2 steps: {len(spans)} periods between step marks, {len(plain)} plain: "
          f"median {step / 1e6:.3f} ms (shortest {min(spans) / 1e6:.3f}, longest plain "
          f"{max(plain) / 1e6:.3f}); the other {len(admits)} hold "
          f"{(sum(spans) - sum(plain)) / 1e6:.1f} ms", flush=True)
    return {"step_ns": step, "all_ns": sum(spans), "admit_ns": sum(admits)}
