"""Whole serving step's share of the chip's bf16 peak for Ling 3.0 flash:
forward FLOPs of the chip's share (`flops_ling3.serve_request_flops`: 8 x 128 /
512 = 2 held picks a token under even routing, the shared expert, the dense
layers, both mixers' projections, the delta rule's products with S, the sliced
head, the prompt's attention in the plain form and each decode step's in the
absorbed form over its live rows on the one latent layer) of the requests
finished in the traced run's window, per second of that window, over chips
times peak. In percent. Requests in flight at either edge of the window stand
in for each other. A decode-bound cell reads a few percent: the step is bound
by bytes (`step_hbm_roofline.serve.ling3`)."""

import flops_ling3 as flops
import peaks


def read(run):
    cell, window = run["cell"], run.get("window")
    if cell.rehearsal or not window or not window["done"]:
        return None
    total = sum(flops.serve_request_flops(cell.config, len(item["prompt"]), len(out.tokens))
                for item, out in window["done"])
    peak = peaks.peaks_for(run["peaks_kind"])["bf16_flops_per_s"]
    return 100.0 * total / window["seconds"] / (run["chips"] * peak)
