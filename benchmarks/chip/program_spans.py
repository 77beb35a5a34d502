"""What the per-layer readers share to read the program's own host spans
(`accelerate_tpu/utils/spans.py`: one bounded ring, always on) and to lay the
serving engine's dispatches on the device's time.

The device trace reaches a reader as `run["trace"]["per_device"]`: the `XLA
Ops` line's `(HLO line, start_ns, duration_ns)`, with `start_ns` counted from
the start of the profiler session. That origin is recorded nowhere a reader
sees (PERF.md section 7), so nothing here joins by clock. Idle gaps do not
bound a program either: one decode step runs as two stretches 5.5 us apart,
an admit program as three or four, 1.3 to 5.8 us apart, and programs follow
each other at 1.3 to 8.8 us (traced run, PR 26). The split of device time
joins by ORDER and by what a stretch holds: the decode kernels of one step,
one a layer, mark where each `step` dispatch ran, and the device time between
two steps beyond what two steps always leave there is the admit programs',
which must be there where the ring says an admit was dispatched between those
two steps, and nowhere else.

Against a program without the ring (the parent of the PR that brought it)
every function returns None and raises nothing."""

from __future__ import annotations

import bisect
import re
import statistics

# Between two decode steps that no admit was dispatched between, the device
# runs the end of one step, the start of the next and the host's helper
# programs (a seed, a stack, a table scatter: 0.5 to 2.5 us each); whatever it
# ran beyond the usual there has to stay under this, and an admit program has
# to take more (the shortest in the cell takes 40 ms).
ADMIT_NS = 1_000_000.0


def ring_spans(name: str | None = None):
    """The program's spans `(name, start, end, parent, attrs)`, oldest first;
    None where the program keeps none, and where its ring has dropped spans:
    what it still holds may then lack part of the window."""
    try:
        from accelerate_tpu.utils import spans
    except ImportError:
        return None
    if spans.RING.dropped:
        print(f"program spans: the ring dropped {spans.RING.dropped} spans, nothing is read",
              flush=True)
        return None
    return spans.RING.snapshot(name)


def steps_of(run: dict, spans: list, part: str = "traced") -> dict[int, tuple]:
    """{span id: `serve.step` span} of the engine steps made while the trace
    was on (`part` "traced") or in the whole window ("window"), picked by step
    number: the driver read `step_total_s.count` on either side of both, and a
    step span carries the count it made."""
    bounds = run.get(part)
    if not bounds:
        return {}
    first, last = bounds["phases0"]["steps"], bounds["phases1"]["steps"]
    return {s[4]["id"]: s for s in spans
            if s[0] == "serve.step" and first < s[4].get("step", -1) <= last}


def is_decode_kernel(name: str) -> bool:
    """The fused paged-decode kernel's events, as `paged_decode_roofline.serve`
    finds them: a Pallas custom call named after the flax scope `attn`."""
    return name.startswith("%attn") and "tpu_custom_call" in name


class Busy:
    """The union of the device's operation intervals, for `between(t0, t1)`:
    the nanoseconds in which some operation ran inside [t0, t1]."""

    def __init__(self, events):
        self.starts, self.ends, self.before = [], [], [0.0]
        for _, start, dur in sorted(events, key=lambda e: e[1]):
            stop = start + dur
            if self.ends and start <= self.ends[-1]:
                if stop > self.ends[-1]:
                    self.before[-1] += stop - self.ends[-1]
                    self.ends[-1] = stop
            else:
                self.starts.append(start)
                self.ends.append(stop)
                self.before.append(self.before[-1] + stop - start)

    def until(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i] - max(0.0, self.ends[i - 1] - t)

    def between(self, t0: float, t1: float) -> float:
        return self.until(t1) - self.until(t0)


def decode_cycles(events, n_layer: int):
    """The decode kernel's events cut into steps: [(first kernel's start, last
    kernel's end)] for every step the trace holds whole, in order, or a string
    saying what does not fit. A step runs the kernel once a layer, each layer
    under its own name (`%attn.N`, N rising with the layer)."""
    kernels = sorted((e for e in events if is_decode_kernel(e[0])), key=lambda e: e[1])
    number = {name: int(re.match(r"%attn\.(\d+)", name).group(1))
              for name in {k[0] for k in kernels} if re.match(r"%attn\.\d+", name)}
    names = sorted(number, key=number.get)
    if len(names) != n_layer or len(number) != len({k[0] for k in kernels}):
        return f"{len(names)} decode kernels by name, a step holds {n_layer}"
    groups: list[list] = []
    for k in kernels:
        if k[0] == names[0]:
            groups.append([])
        if groups:  # kernels before the first layer's first run end a step caught half
            groups[-1].append(k)
    if groups and len(groups[-1]) < n_layer:
        groups.pop()  # the step the trace's end cut
    for g in groups:
        if [k[0] for k in g] != names:
            return f"a step of {len(g)} decode kernels out of layer order among {len(groups)}"
    return [(g[0][1], g[-1][1] + g[-1][2]) for g in groups]


def dispatch_kind(kind: str) -> str | None:
    """`serve.dispatch`'s program name -> `step` or `admit`; None for a
    program this split does not know (a KV-tier restore)."""
    if kind == "step":
        return "step"
    return "admit" if kind in ("admit", "cached_admit") else None


def serve_split(run: dict) -> dict | None:
    """Device time of the traced slice by program. Returns {admit_ns, step_ns,
    helper_ns, step_runs_ns} or None, saying why on standard output. Computed
    once a run."""
    if "_serve_split" not in run:
        run["_serve_split"] = _serve_split(run)
    return run["_serve_split"]


def _serve_split(run: dict) -> dict | None:
    cell, trace = run["cell"], run.get("trace")
    spans = ring_spans()
    if cell.rehearsal or not trace or not trace.get("per_device") or not spans:
        return None
    steps = steps_of(run, spans)
    kinds = [dispatch_kind(s[4]["kind"]) for s in sorted(
        (s for s in spans if s[0] == "serve.dispatch" and s[3] in steps),
        key=lambda s: s[4]["seq"])]
    # admits dispatched after each `step` dispatch and before the next
    admits_after: list[int] = []
    for kind in kinds:
        if kind == "step":
            admits_after.append(0)
        elif admits_after:
            admits_after[-1] += 1
    events = next(iter(trace["per_device"].values()))
    cycles = decode_cycles(events, int(cell.config["n_layer"]))
    said = (f"{len(kinds)} dispatches of {len(steps)} steps ({kinds.count('step')} step, "
            f"{kinds.count('admit')} admit, {kinds.count(None)} other)")
    if isinstance(cycles, str) or None in kinds:
        print(f"serve split: no pairing: {said}; {cycles if isinstance(cycles, str) else ''}",
              flush=True)
        return None
    # the k-th whole step on the device is the slice's k-th `step` dispatch: the
    # program in flight when the trace began is caught half and dropped, and the
    # trace ends before the slice's last dispatches do
    if not 3 <= len(cycles) <= len(admits_after):
        print(f"serve split: no pairing: {said} against {len(cycles)} whole steps on the device",
              flush=True)
        return None
    busy = Busy(events)
    cores = [busy.between(t0, t1) for t0, t1 in cycles]
    between = [busy.between(a[1], b[0]) for a, b in zip(cycles, cycles[1:])]
    dispatched = admits_after[: len(between)]
    plain = [ns for ns, n in zip(between, dispatched) if n == 0]
    if len(plain) < 3:
        print(f"serve split: no pairing: {said}; {len(plain)} pairs of steps with no admit "
              f"between them", flush=True)
        return None
    edges = statistics.median(plain)  # one step's end and the next one's start
    extra = [ns - edges for ns in between]
    wrong = [(i, n, round(ns / 1e6, 3)) for i, (ns, n) in enumerate(zip(extra, dispatched))
             if (n > 0) != (ns >= ADMIT_NS)]
    if wrong:
        print(f"serve split: no pairing: {said}; after whole step i, n admits dispatched but "
              f"x ms of device time beyond the steps' own: (i, n, x) = {wrong[:8]}", flush=True)
        return None
    out = {"admit_ns": sum(ns for ns, n in zip(extra, dispatched) if n),
           "helper_ns": sum(ns for ns, n in zip(extra, dispatched) if not n),
           "step_runs_ns": [core + edges for core in cores]}
    # between the first step's first kernel and the last one's last: every
    # step's kernels and all but one step's edges
    out["step_ns"] = sum(cores) + edges * len(between)
    total = sum(cores) + sum(between)
    print(f"serve split: {len(cycles)} whole steps on the device for {said}: step "
          f"{out['step_ns'] / 1e6:.3f} ms (median run "
          f"{statistics.median(out['step_runs_ns']) / 1e6:.3f}), admit {out['admit_ns'] / 1e6:.3f} "
          f"ms after {sum(1 for n in dispatched if n)} steps, helpers {out['helper_ns'] / 1e6:.3f} "
          f"ms: step and admit hold {100.0 * (out['admit_ns'] + out['step_ns']) / total:.2f}% of "
          f"{total / 1e6:.3f} ms busy", flush=True)
    return out
