"""The whole decode step's share of its memory roofline for MiMo-V2: the
least bytes one step has to move (`flops_mimo_v2.decode_step_bytes`: the
routed experts the step touched, from the program's `moe_experts_touched`
counter over the traced slice; every other held weight once; the two full
layers' live keys and values at 4 heads of 192 and 128, from the program's
`serving/paged_decode/live_tokens` count; the rings' live rows at 8 heads of
192 and 128, from its `window_rows` counter; the new rows written) at the
chip's HBM bandwidth, over the step's device time
(`steps_mimo_v2.step_device_ns`: the median busy time from one step's mark to
the next one's with no admit program between them). In percent."""

import flops_mimo_v2 as flops
import peaks
import steps_mimo_v2 as steps


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
