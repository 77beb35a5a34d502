"""Run one serving cell of BENCHMARK.json once, as run.py does, and read the
profile's host plane, which `harness.Trace.reduce` drops before the readers run.

  python benchmarks/chip/trace_host.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints run.py's lines, the engine steps the window made (a traced window
against an untraced one is what tracing costs), how long the profiler took to
stop, every full collection (`host.gc`) the ring holds, placed against the
window, and the window's longest engine steps with the spans inside them.
With `--trace 1` also, on lines that start `host plane:`:

- the clock check: each engine program the device ran (its `XLA Modules`
  line; in a GPT-2 cell also each step's decode kernels) starts after the
  `serve.dispatch` annotation that sent it starts, and ends before the
  `serve.fetch` that waited for it ends; the count of violations and the
  smallest margins;
- `host_plane.profile_offset_ns`: the constant that lays the span ring on the
  profile, and its spread;
- the device's idle time inside the engine's steps by the innermost host span
  it sat in (`host_plane.host_exposed`), and the idle time between steps;
- every idle gap of `GAP_MS` or more, named by that span, with the ring's
  spans over it (laid on the profile by the offset)."""

from __future__ import annotations

import importlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.dirname(os.path.dirname(HERE))):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402
import host_plane  # noqa: E402
import program_spans  # noqa: E402
import run as run_py  # noqa: E402
import xplane  # noqa: E402

GAP_MS = 10.0
PROGRAMS = (("step", "step_fn"), ("admit", "admit_fn"))  # dispatch kind, module name holds


def say(text: str) -> None:
    print(f"host plane: {text}", flush=True)


def causality(name: str, runs, seqs, marks) -> None:
    """`runs` [(start_ns, end_ns)] of one program in order, `seqs` its
    dispatches' sequence numbers in order, `marks` {seq: (dispatch, fetch)}
    annotations: the first run that starts after the first dispatch starts is
    that dispatch's, and the rest follow in order."""
    first = marks[seqs[0]][0][1] if seqs else None
    runs = [r for r in runs if first is not None and r[0] >= first]
    pairs = list(zip(seqs, runs))
    if not pairs:
        say(f"clock check {name}: nothing to pair ({len(seqs)} dispatches, {len(runs)} runs)")
        return
    after = [r[0] - marks[q][0][1] for q, r in pairs]  # run start - dispatch start
    before = [marks[q][1][1] + marks[q][1][2] - r[1] for q, r in pairs]  # fetch end - run end
    bad = sum(1 for a, b in zip(after, before) if a < 0 or b < 0)
    say(f"clock check {name}: {len(pairs)} runs paired, {bad} violations; smallest margin "
        f"dispatch->first op {min(after) / 1e3:.3f} us, last op->fetch end {min(before) / 1e3:.3f} us "
        f"(medians {sorted(after)[len(after) // 2] / 1e3:.3f}, {sorted(before)[len(before) // 2] / 1e3:.3f})")


def report(run: dict, kept: dict) -> None:
    from accelerate_tpu.utils import spans as program

    cell, spans = run["cell"], kept.get("spans") or []
    events = next(iter(run["trace"]["per_device"].values()), [])
    ring = program.RING.snapshot()
    say(f"{len(spans)} annotations; the ring holds {len(ring)} spans of {program.RING.maxlen}, "
        f"dropped {program.RING.dropped}")
    if not spans or not events:
        return
    # the clock: each program between its dispatch and its fetch
    kinds = {s[4]["seq"]: program_spans.dispatch_kind(s[4]["kind"])
             for s in ring if s[0] == "serve.dispatch"}
    by_seq = {}
    for s in spans:
        if s[0] in ("serve.dispatch", "serve.fetch") and "seq" in s[4]:
            by_seq.setdefault(s[4]["seq"], {})[s[0]] = s
    marks = {q: (m["serve.dispatch"], m["serve.fetch"]) for q, m in by_seq.items() if len(m) == 2}
    modules = kept.get("runs") or []
    names = sorted({m[0] for m in modules})
    say(f"{len(modules)} program runs on the device, {len(names)} modules: {names[:12]}")
    for kind, part in PROGRAMS:
        seqs = sorted(q for q in marks if kinds.get(q) == kind)
        runs = sorted((s, s + d) for n, s, d in modules if part in n)
        causality(f"{kind} programs", runs, seqs, marks)
    if "n_layer" in cell.config:
        cycles = program_spans.decode_cycles(events, int(cell.config["n_layer"]))
        if not isinstance(cycles, str):
            seqs = sorted(q for q in marks if kinds.get(q) == "step")
            causality("decode kernels of a step", cycles, seqs, marks)
    offset = host_plane.profile_offset_ns(ring, spans)
    if offset is None:
        say("no serve.step of the ring met its annotation")
        return
    say(f"ring offset {offset[0]:.0f} ns, spread {offset[1] / 1e3:.3f} us over {offset[2]} steps")
    exposed = host_plane.host_exposed(events, spans)
    if exposed:
        split = ", ".join(f"{k} {v:.3f}" for k, v in exposed["by_class_ms"].items())
        say(f"host exposed {exposed['ms_per_step']:.4f} ms a step over {exposed['steps']} whole "
            f"steps; idle ms inside steps by innermost span: {split}; between steps "
            f"{exposed['outside_ms']:.3f} ms")
    first = min(s for _, s, _ in events)
    stretches = host_plane.innermost(host_plane.program_line(spans))
    laid = [(s[0], s[1] * 1e9 + offset[0], s[2] * 1e9 + offset[0], s[4]) for s in ring]
    long = sorted(g for g in xplane.idle_gaps(events, len(events)) if g[1] >= GAP_MS * 1e6)
    say(f"{len(long)} idle gaps of {GAP_MS} ms or more")
    for start, ns in long:
        over = [f"{n} {(e - s) / 1e6:.3f}ms{' ' + str(a) if n in ('host.gc', 'serve.step') else ''}"
                for n, s, e, a in laid if s < start + ns and start < e
                and n in ("host.gc", "serve.step", "serve.admit", "serve.fetch")]
        say(f"gap_at_{(start - first) / 1e6:.3f}ms {ns / 1e6:.3f} ms: "
            f"{host_plane.gap_class((start, ns), stretches)}; ring over it: {over[:6]}")
    labelled = host_plane.label_gaps(xplane.idle_gaps(events), first, spans)
    say(f"breakdown idle gaps: {[[n, round(s * 1e3, 3)] for n, s in labelled]}")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    cell = harness.Cell(argv[argv.index("--workload") + 1])
    driver = importlib.import_module(f"drivers.{cell.spec['driver']}")
    drive, reduce, read = driver.drive, harness.Trace.reduce, harness.read_layer_metrics
    stop = harness.Trace.stop
    kept: dict = {}

    def drive_counting(*args, **kwargs):
        from accelerate_tpu.utils import spans as program

        served = drive(*args, **kwargs)
        steps = served["phases1"]["steps"] - served["phases0"]["steps"]
        print(f"window: {steps} engine steps in {served['elapsed']:.3f} s", flush=True)
        pauses = [(round(s[1] - served["start"], 3), round(1e3 * (s[2] - s[1]), 3), s[3] != 0)
                  for s in program.RING.snapshot("host.gc")]
        print(f"full collections (s from the window's start, ms, inside a step): {pauses}",
              flush=True)
        ring = program.RING.snapshot()
        steps = [s for s in ring if s[0] == "serve.step"
                 and served["start"] <= s[1] and s[2] <= served["stop"]]
        inside: dict = {}  # step id -> the spans over 1 ms it holds
        for s in ring:
            if s[3] and s[0] not in ("serve.step", "serve.queued") and s[2] - s[1] > 1e-3:
                inside.setdefault(s[3], []).append((s[0], round(1e3 * (s[2] - s[1]), 3)))
        between = [(b[1] - a[2], a[2]) for a, b in zip(steps, steps[1:])]
        longest = sorted(steps, key=lambda s: s[1] - s[2])[:5]
        print("longest steps (s from the window's start, ms, the spans over 1 ms inside): "
              f"{[(round(s[1] - served['start'], 3), round(1e3 * (s[2] - s[1]), 3), inside.get(s[4]['id'], [])) for s in longest]}; "
              "longest time between steps: "
              f"{[(round(t - served['start'], 3), round(1e3 * ns, 3)) for ns, t in sorted(between, reverse=True)[:5]]}",
              flush=True)
        return served

    def stop_timed(self):
        import time

        t = time.perf_counter()
        stop(self)
        print(f"the profiler took {time.perf_counter() - t:.3f} s to stop", flush=True)

    def reduce_keeping_host(self):
        profile = xplane.load(self.dir)
        kept["spans"] = host_plane.host_spans(profile)
        kept["runs"] = host_plane.program_runs(profile)
        return reduce(self)

    def read_and_report(cell, run):
        report(run, kept)
        return read(cell, run)

    driver.drive = drive_counting
    harness.Trace.stop = stop_timed
    harness.Trace.reduce = reduce_keeping_host
    harness.read_layer_metrics = read_and_report
    return run_py.main(argv)


if __name__ == "__main__":
    sys.exit(main())
