"""Flash attention's share of its roofline in the train step: the least time
the chip could take for the causal forward and backward of every layer
(FLOPs and bytes from shapes, `flops.flash_attention_train_cost`), over the
summed device time of the kernels' events in the traced steps. In percent.

The kernels carry no name of their own yet. The trace tells them apart all
the same: they are the operations whose custom-call target is
`tpu_custom_call` (a Pallas kernel) and whose result is named after the flax
scope `attn` that calls them (`%attn.N`); the train step holds no other
Pallas kernel. Where no such event is found the metric is left out."""

import flops
import peaks


def kernel_seconds(trace) -> float:
    return sum(s for name, s in trace["by_name_s"].items()
               if "tpu_custom_call" in name and name.startswith("%attn"))


def read(run):
    cell, trace = run["cell"], run.get("trace")
    if cell.rehearsal or not trace or not run.get("steps"):
        return None
    seconds = kernel_seconds(trace)
    if seconds <= 0:
        return None
    cost = flops.flash_attention_train_cost(
        cell.config, run["global_batch"] // run["chips"], int(cell.traffic["seq"]))
    least = flops.roofline_seconds(cost, peaks.peaks_for(run["peaks_kind"]))
    return 100.0 * least * run["steps"] / seconds
