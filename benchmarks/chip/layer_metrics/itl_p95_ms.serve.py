"""Time between two tokens of one reply as its caller sees it: nearest-rank
p95, in ms, of the gaps between consecutive `RequestOutput.token_times` of
every request that finished in the window, both stamps inside the window's
engine steps (picked by step number from the program's span ring: no clock is
joined and the trace is not read). Every caller's gap in a turn is that turn,
so some 350 turns stand behind the 10,000 gaps. Left out are the two turns in
which the harness started and stopped the profiler: a gap that holds the start
of the traced slice's first step or the end of its last. `tpot_p95_ms` is a
p95 of per-request means; this is the p95 of the turns themselves, so a turn
that held an admit program shows whole."""

import program_spans
import stats

FEWEST_GAPS = 1000  # 50 beyond the p95, of some 17 turns


def read(run):
    window, spans = run.get("window"), program_spans.ring_spans("serve.step")
    if run["cell"].rehearsal or not window or not window["done"] or not spans:
        return None
    steps = program_spans.steps_of(run, spans, "window").values()
    traced = program_spans.steps_of(run, spans, "traced").values()
    if not steps:
        return None
    first, last = min(s[1] for s in steps), max(s[2] for s in steps)
    profiler = [min(s[1] for s in traced), max(s[2] for s in traced)] if traced else []
    gaps, left_out = [], 0
    for _, out in window["done"]:
        times = getattr(out, "token_times", ())  # the parent's RequestOutput has none
        for a, b in zip(times, times[1:]):
            if first <= a and b <= last:  # a restored token's nan stamp compares false
                if any(a < t <= b for t in profiler):
                    left_out += 1
                else:
                    gaps.append(1e3 * (b - a))
    if len(gaps) < FEWEST_GAPS:
        return None
    print(f"itl: {len(gaps)} gaps in {len(steps)} steps ({left_out} left out around the "
          f"profiler's start and stop), p50 {stats.percentile(gaps, 50):.3f} p95 "
          f"{stats.percentile(gaps, 95):.3f} p99 {stats.percentile(gaps, 99):.3f} max "
          f"{max(gaps):.3f} ms", flush=True)
    return stats.percentile(gaps, 95)
