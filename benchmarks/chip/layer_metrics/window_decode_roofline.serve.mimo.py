"""The window rings' share of their roofline in MiMo-V2's decode step: the
least time the chip could take to read the ring rows a step attends
(`flops_mimo_v2.window_decode_cost`: the program's `window_rows`, summed over
the five sliding layers and the live slots, read once at 8 heads of 192 and
128 lanes; the new rows written; queries in and outputs out), times the steps
in the traced slice (`steps_mimo_v2.step_marks`, one a step), over the summed
device time of the events that read or write the rings
(`steps_mimo_v2.ring_events`: the sink kernel `%window_attn.N` and the rows'
writes, whose lines name the rings' shapes `[slots, 128, 1536]` or
`[slots, 128, 1024]` and no admit row's). This is the decode path's attention
over the window, whatever implements it. In percent."""

import flops_mimo_v2 as flops
import peaks
import steps_mimo_v2 as steps


def read(run):
    cell, rings, rows_read = run["cell"], steps.ring_events(run), steps.window_rows(run)
    marks = steps.step_marks(run)
    if not rings or not marks or rows_read is None:
        return None
    rows = int(cell.spec["engine"]["max_concurrency"])
    cost = flops.window_decode_cost(cell.config, rows_read, rows)
    p = peaks.peaks_for(run["peaks_kind"])
    least = max(cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"])
    seconds = sum(ns for _, _, ns in rings) / 1e9
    print(f"ring events: {len(rings)} over {len(marks)} steps, {seconds / len(marks) * 1e6:.1f} us a step; "
          f"{rows_read:.0f} ring rows a step need {least * 1e6:.1f} us", flush=True)
    return 100.0 * least * len(marks) / seconds
