"""The whole decode step's share of its memory roofline for K-EXAONE: the
least bytes one step has to move (`flops_k_exaone.decode_step_bytes`: the
routed experts the step touched, from the program's `moe_experts_touched`
counter over the traced slice; every other held weight once; the full layer's
live keys and values, from the program's `serving/paged_decode/live_tokens`
count; the rings' live rows, from its `window_rows` counter; the new rows
written) at the chip's HBM bandwidth, over the step's device time
(`steps_k_exaone.step_device_ns`: the median busy time from one step's decode
kernel to the next one's with no admit program between them). In percent."""

import flops_k_exaone as flops
import peaks
import steps_k_exaone as steps


def read(run):
    cell = run["cell"]
    step_ns, counted = steps.step_device_ns(run), steps.per_step(run)
    live, rings = steps.live_tokens(run), steps.window_rows(run)
    if step_ns is None or counted is None or live is None or rings is None:
        return None
    rows = int(cell.spec["engine"]["max_concurrency"])
    layers = flops.expert_layers(cell.config)
    least = flops.decode_step_bytes(cell.config, rows, counted["experts_touched"] / layers, live, rings)
    print("step bytes " + " ".join(f"{k} {v / 1e9:.3f} GB" for k, v in least.items())
          + f" at {live:.0f} live keys, {rings:.0f} ring rows, {counted['picks_held'] / layers:.1f} picks on "
          f"{counted['experts_touched'] / layers:.1f} experts a layer over {counted['steps']} counted steps",
          flush=True)
    bandwidth = peaks.peaks_for(run["peaks_kind"])["hbm_bytes_per_s"]
    return 100.0 * (least["total"] / bandwidth) / (step_ns / 1e9)
