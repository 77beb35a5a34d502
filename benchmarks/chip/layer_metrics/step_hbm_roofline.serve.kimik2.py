"""The whole decode step's share of its memory roofline for Kimi K2: the least
bytes one step has to move (`flops_kimi_k2.decode_step_bytes`: the routed
experts the step touched, from the program's `moe_experts_touched` counter
over the traced slice; every other held weight once; the live latent rows,
from the program's `serving/paged_decode/live_tokens` count) at the chip's HBM
bandwidth, over the step's device time (`steps_kimi_k2.step_device_ns`: the
median busy time from one step's first decode kernel to the next one's with no
admit program between them). In percent."""

import flops_kimi_k2 as flops
import peaks
import steps_kimi_k2 as steps


def read(run):
    cell = run["cell"]
    step_ns, counted, live = steps.step_device_ns(run), steps.per_step(run), steps.live_tokens(run)
    if step_ns is None or counted is None or live is None:
        return None
    rows = int(cell.spec["engine"]["max_concurrency"])
    least = flops.decode_step_bytes(cell.config, rows,
                                    counted["experts_touched"] / flops.expert_layers(cell.config), live)
    print("step bytes " + " ".join(f"{k} {v / 1e9:.3f} GB" for k, v in least.items())
          + f" at {live:.0f} live rows over {counted['steps']} counted steps", flush=True)
    bandwidth = peaks.peaks_for(run["peaks_kind"])["hbm_bytes_per_s"]
    return 100.0 * (least["total"] / bandwidth) / (step_ns / 1e9)
