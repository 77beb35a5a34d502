"""The window rings' share of their roofline in K-EXAONE's decode step: the
least time the chip could take to read the ring rows a step attends
(`flops_k_exaone.window_decode_cost`: the program's `window_rows`, summed over
the four sliding layers and the live slots, read once; the new rows written;
queries in and outputs out), times the steps in the traced slice (the full
layer's kernel events, one a step), over the summed device time of the events
that read or write the rings (`steps_k_exaone.ring_events`: their HLO line
names the rings' shape `[slots, 128, 1024]` and no admit group's). This is the
decode path's attention over the window, whatever implements it. In percent."""

import flops_k_exaone as flops
import peaks
import steps_k_exaone as steps


def read(run):
    cell, rings, rows_read = run["cell"], steps.ring_events(run), steps.window_rows(run)
    kernels = steps.full_kernels(run)
    if not rings or not kernels or rows_read is None:
        return None
    rows = int(cell.spec["engine"]["max_concurrency"])
    cost = flops.window_decode_cost(cell.config, rows_read, rows)
    p = peaks.peaks_for(run["peaks_kind"])
    least = max(cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"])
    seconds = sum(ns for _, _, ns in rings) / 1e9
    print(f"ring events: {len(rings)} over {len(kernels)} steps, {seconds / len(kernels) * 1e6:.1f} us a step; "
          f"{rows_read:.0f} ring rows a step need {least * 1e6:.1f} us", flush=True)
    return 100.0 * least * len(kernels) / seconds
