"""Distinct held experts that one layer's grouped product touches in a decode
step: the program's `moe_experts_touched` counter (summed on the device inside
the step, fetched with the step's tokens) over the window's counted steps and
the layers. Their weights are most of the bytes a step moves; fewer touched is
a cheaper step at the same rows."""

import flops_qwen3_next as flops
import steps_qwen3_next as steps


def read(run):
    counted = steps.per_step(run, "window")
    if counted is None:
        return None
    return counted["experts_touched"] / len(flops.kinds(run["cell"].config))
