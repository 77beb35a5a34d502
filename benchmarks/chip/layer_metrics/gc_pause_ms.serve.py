"""Full collections inside the engine's turns: the seconds of the
generation-2 `host.gc` spans (`accelerate_tpu/utils/spans.py`: a `gc.callbacks`
hook) whose parent is one of the window's engine steps, picked by step number,
over those steps, in ms. Prints their count and the longest, and the young
generations' totals since the process began (`spans.GC`: counted, never in the
ring). A program without the hook has neither: None."""

import program_spans


def read(run):
    window = run.get("window")
    if run["cell"].rehearsal or not window:
        return None
    try:
        from accelerate_tpu.utils.spans import GC
    except ImportError:
        return None
    spans = program_spans.ring_spans()
    steps = program_spans.steps_of(run, spans, "window") if spans else {}
    if not steps:
        return None
    pauses = [s[2] - s[1] for s in spans if s[0] == "host.gc" and s[3] in steps]
    young = ", ".join(f"generation {g} {GC.collections[g]} in {GC.seconds[g]:.3f} s"
                      for g in (0, 1))
    print(f"gc: {len(pauses)} full collections in {len(steps)} steps, longest "
          f"{1e3 * max(pauses, default=0.0):.3f} ms; since start {young}, generation 2 "
          f"{GC.collections[2]} in {GC.seconds[2]:.3f} s", flush=True)
    return 1e3 * sum(pauses) / len(steps)
