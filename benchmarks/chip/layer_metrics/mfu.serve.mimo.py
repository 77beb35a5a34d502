"""Whole serving step's share of the chip's bf16 peak for MiMo-V2: forward
FLOPs of the chip's share (`flops_mimo_v2.serve_request_flops`: 8 x 16 / 256
= 0.5 held picks a token under even routing, the dense layer, every layer's
projections at its kind's heads, each query's attention over the keys it
sees (the causal context on the two full layers, at most the window of 128
on the five sliding ones), the sliced head once a token produced) of the
requests finished in the traced run's window, per second of that window, over
chips times peak. In percent. Requests in flight at either edge of the window
stand in for each other. A decode-heavy cell reads a few percent: the step is
bound by bytes (`step_hbm_roofline.serve.mimo`)."""

import flops_mimo_v2 as flops
import peaks


def read(run):
    cell, window = run["cell"], run.get("window")
    if cell.rehearsal or not window or not window["done"]:
        return None
    total = sum(flops.serve_request_flops(cell.config, len(item["prompt"]), len(out.tokens))
                for item, out in window["done"])
    peak = peaks.peaks_for(run["peaks_kind"])["bf16_flops_per_s"]
    return 100.0 * total / window["seconds"] / (run["chips"] * peak)
