"""The profile's host plane: the program's own spans on the device trace's clock.

Every span of `accelerate_tpu/utils/spans.py` opens a `TraceAnnotation` of its
name, so a capture's `/host:` plane holds the engine's `serve.*`, the train
loop's `train.*` and the collector's `host.gc` on the clock of the device
plane's `XLA Ops` line: both count from the start of the profiler session.
The annotations carry `step=` / `seq=` as metadata, which pairs them with the
ring's spans, and `profile_offset_ns` turns that pairing into the constant
that lays the ring's `perf_counter` stamps on the profile (`serve.queued`,
which opens no annotation, included).

`harness.Trace.reduce` drops the host plane before the readers run, so no
metric of `BENCHMARK.json` reads this module: `trace_host.py` runs a cell with
the plane kept and prints what these functions find (PERF.md section 7).

Against a program whose spans carry no metadata every function returns None
or an empty answer and raises nothing."""

from __future__ import annotations

import bisect
import statistics
from collections import defaultdict

import xplane

PREFIXES = ("serve.", "train.", "host.")
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside"  # idle time no span of the program's line covers: the caller's code


def host_spans(profile) -> list[tuple]:
    """`(name, start_ns, duration_ns, line, metadata)` of every event on a
    `/host:` plane whose name starts with `serve.`, `train.` or `host.`, in the
    order of their start; `line` is the thread's line name and `metadata` the
    annotation's keyword metadata (`{"step": 7}`)."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append((e.name, float(e.start_ns), float(e.duration_ns), line.name,
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def program_runs(profile) -> list[tuple]:
    """`(module name, start_ns, duration_ns)` of every program the first
    device plane ran: its `XLA Modules` line, empty if it has none."""
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    return [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
            return []
    return []


def program_line(spans: list[tuple]) -> list[tuple]:
    """The spans of the thread that ran the steps: the line holding the most
    `serve.step` spans, else the most `train.*` spans."""
    counts: dict[str, list[int]] = defaultdict(lambda: [0, 0])
    for name, _, _, line, _ in spans:
        counts[line][0] += name == "serve.step"
        counts[line][1] += name.startswith("train.")
    if not counts:
        return []
    best = max(counts, key=lambda line: counts[line])
    return [s for s in spans if s[3] == best]


def profile_offset_ns(ring: list[tuple], spans: list[tuple]) -> tuple[float, float, int] | None:
    """`(offset, spread, pairs)`: the nanoseconds that carry a ring stamp onto
    the profile (`profile_ns = perf_counter_s * 1e9 + offset`), the range of
    the per-step estimates, and how many `serve.step` spans of the ring met
    their annotation by `step`. The annotation opens just before the ring's
    stamp and closes just after it, so a step's estimate is the mean of the
    two ends' differences. None where none pair."""
    marks = {s[4]["step"]: s for s in spans if s[0] == "serve.step" and "step" in s[4]}
    each = []
    for name, start, end, _, attrs in ring:
        ann = marks.get(attrs.get("step")) if name == "serve.step" else None
        if ann is not None:
            each.append(((ann[1] - start * 1e9) + (ann[1] + ann[2] - end * 1e9)) / 2)
    if not each:
        return None
    return statistics.median(each), max(each) - min(each), len(each)


def innermost(spans: list[tuple]) -> list[tuple[float, float, str]]:
    """One thread's nested spans flattened to `(start_ns, end_ns, name)`
    stretches, each named by the innermost span open over it; a span that
    outlasts its parent is cut at the parent's end. Time under no span is
    left out."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []  # (end, name) of the open spans, innermost last
    at = None

    def emit(t0, t1, name):
        if t1 > t0:
            out.append((t0, t1, name))

    for name, start, dur, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:  # close what ended before this one starts
            end, inner = stack.pop()
            emit(at, end, inner)
            at = end
        if stack:
            emit(at, start, stack[-1][1])
        end = start + dur if not stack else min(start + dur, stack[-1][0])
        stack.append((end, name))
        at = start
    while stack:
        end, inner = stack.pop()
        emit(at, end, inner)
        at = end
    return out


def split_by_class(intervals, stretches) -> dict[str, float]:
    """Nanoseconds of `intervals` under each stretch's name, `outside` for
    the rest; both lists ordered and without overlaps."""
    out: dict[str, float] = defaultdict(float)
    starts = [s[0] for s in stretches]
    for t0, t1 in intervals:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, t0) - 1)
        while i < len(stretches) and stretches[i][0] < t1:
            s0, s1, name = stretches[i]
            ns = min(s1, t1) - max(s0, t0)
            if ns > 0:
                out[name] += ns
                covered += ns
            i += 1
        if t1 - t0 > covered:
            out[OUTSIDE] += t1 - t0 - covered
    return dict(out)


def gap_class(gap: tuple[float, float], stretches) -> str:
    """The innermost span that covers most of a gap `(start_ns, length_ns)`;
    `outside` where time under no span covers most of it."""
    split = split_by_class([(gap[0], gap[0] + gap[1])], stretches)
    return max(split, key=split.get)


def label_gaps(gaps, first_ns: float, spans: list[tuple]) -> list[list]:
    """`xplane.idle_gaps`' `[(start_ns, length_ns)]` as the breakdown's
    `[["gap_at_<ms>ms:<class>", seconds]]`, `<ms>` counted from `first_ns`."""
    stretches = innermost(program_line(spans))
    return [[f"gap_at_{(s - first_ns) / 1e6:.3f}ms:{gap_class((s, ns), stretches)}", ns / 1e9]
            for s, ns in gaps]


def host_exposed(events, spans: list[tuple]) -> dict | None:
    """The device's idle time inside the host's `serve.step` spans, from the
    first to the last step that lies whole inside the device's slice, over
    those steps: `{"ms_per_step", "steps", "by_class_ms" (the idle time under
    each innermost span), "outside_ms" (idle time between steps)}`; None
    without steps."""
    line = program_line(spans)
    steps = [s for s in line if s[0] == "serve.step"]
    if not events or not steps:
        return None
    first = min(s for _, s, _ in events)
    last = max(s + d for _, s, d in events)
    whole = [s for s in steps if first <= s[1] and s[1] + s[2] <= last]
    if not whole:
        return None
    t0, t1 = whole[0][1], whole[-1][1] + whole[-1][2]
    idle = sorted((max(a, t0), min(a + ns, t1)) for a, ns in xplane.idle_gaps(events, len(events))
                  if a + ns > t0 and a < t1)
    split = split_by_class(idle, innermost(line))
    outside = split.pop(OUTSIDE, 0.0)
    return {"ms_per_step": sum(split.values()) / 1e6 / len(whole), "steps": len(whole),
            "by_class_ms": {k: v / 1e6 for k, v in sorted(split.items(), key=lambda kv: -kv[1])},
            "outside_ms": outside / 1e6}
