"""The whole decode step's share of its memory roofline: the least bytes one
step has to move (`flops_qwen3_next.decode_step_bytes`: the routed experts the
step touched, from the program's `moe_experts_touched` counter over the traced
slice; every other weight once; the recurrent state read and written; the live
keys and values) at the chip's HBM bandwidth, over the step's device time
(`steps_qwen3_next.step_device_ns`: the median busy time between two decode
kernels with no admit program between them). Bound by bytes: at 128 rows the
step's FLOPs take a twentieth of that time. In percent."""

import flops_qwen3_next as flops
import peaks
import steps_qwen3_next as steps


def read(run):
    cell = run["cell"]
    step_ns, counted, live = steps.step_device_ns(run), steps.per_step(run), steps.live_tokens(run)
    if step_ns is None or counted is None or live is None:
        return None
    rows = int(cell.spec["engine"]["max_concurrency"])
    layers = len(flops.kinds(cell.config))
    least = flops.decode_step_bytes(cell.config, rows, counted["experts_touched"] / layers, live)
    print("step bytes " + " ".join(f"{k} {v / 1e9:.3f} GB" for k, v in least.items())
          + f" over {counted['steps']} counted steps", flush=True)
    bandwidth = peaks.peaks_for(run["peaks_kind"])["hbm_bytes_per_s"]
    return 100.0 * (least["total"] / bandwidth) / (step_ns / 1e9)
