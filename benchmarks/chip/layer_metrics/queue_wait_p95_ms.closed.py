"""Time a request waited in the scheduler's queue before a dispatch took it:
nearest-rank p95, in ms, of the window's `serve.queued` spans
(`accelerate_tpu/utils/spans.py`: from the scheduler's enqueue stamp to the
start of the admit program's dispatch), picked by the engine step that
recorded them. With `ttft_p95_ms.closed` it splits the time to the first token
into waiting for a slot and waiting for the admit program. Prints the five
longest with their bucket and whether a full collection (`host.gc`) or a step
over 100 ms overlapped them. A program that records no such span: None."""

import program_spans
import stats

FEWEST = 20  # as `ttft_p95_ms.closed`
LONG_STEP_S = 0.1


def read(run):
    window = run.get("window")
    spans = program_spans.ring_spans()
    if run["cell"].rehearsal or not window or not spans:
        return None
    steps = program_spans.steps_of(run, spans, "window")
    waits = [s for s in spans if s[0] == "serve.queued" and s[3] in steps]
    if len(waits) < FEWEST:
        return None
    stalls = [s for s in spans if s[0] == "host.gc"
              or (s[0] == "serve.step" and s[2] - s[1] > LONG_STEP_S)]

    def beside(w):
        return ",".join(sorted({s[0] for s in stalls if s[1] < w[2] and w[1] < s[2]})) or "-"

    ms = [1e3 * (s[2] - s[1]) for s in waits]
    longest = sorted(waits, key=lambda s: s[1] - s[2])[:5]
    print(f"queue wait: {len(waits)} admissions in {len(steps)} steps, p50 "
          f"{stats.percentile(ms, 50):.3f} p95 {stats.percentile(ms, 95):.3f} max {max(ms):.3f} ms; "
          "longest (ms, bucket, overlapped): "
          + "; ".join(f"{1e3 * (w[2] - w[1]):.3f} {w[4]['bucket']} {beside(w)}" for w in longest),
          flush=True)
    return stats.percentile(ms, 95)
