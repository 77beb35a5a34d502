"""Whole train step's share of the chip's bf16 peak: the traced slice's
tokens per second times the FLOPs a token needs (forward + backward from
shapes, recompute not counted) over chips times peak. In percent."""

import flops
import peaks


def read(run):
    cell = run["cell"]
    if run.get("tokens_per_s") is None or cell.rehearsal:
        return None
    per_token = flops.train_flops_per_token(cell.config, int(cell.traffic["seq"]))
    peak = peaks.peaks_for(run["peaks_kind"])["bf16_flops_per_s"]
    return 100.0 * run["tokens_per_s"] * per_token / (run["chips"] * peak)
