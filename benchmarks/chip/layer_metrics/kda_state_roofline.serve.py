"""The decode step's per-channel delta rule's share of its roofline: the least
time the chip could take to read and write the slots' KDA matrices once
(`flops_ling3.kda_step_cost`: bound by bytes, float32), times the calls (KDA
layers x the decode steps the trace holds, one fused paged kernel for each
latent layer a step), over the summed device time of the events that touch
the state. In percent.

The delta rule is plain XLA: its fusions carry XLA's names and the `kda_step`
scope is in the HLO's metadata, which the trace's lines do not carry. They are
found by what they touch, as `delta_state_roofline.serve` finds its model's: a
line that names the whole per-slot state `f32[slots, heads, key dim, value
dim]` and no state-shaped array of another leading size (an admit program
scatters its group's `f32[group, ...]` into the same buffer)."""

import re

import flops_ling3 as flops
import peaks
import steps_ling3 as steps


def read(run):
    cell, kernels = run["cell"], steps.decode_kernels(run)
    if not kernels:
        return None
    cfg, rows = cell.config, int(cell.spec["engine"]["max_concurrency"])
    tail = f"{cfg['num_attention_heads']},{cfg['head_dim']},{cfg['head_dim']}]"
    shaped = re.compile(rf"f32\[(\d+),{re.escape(tail)}")
    mine = [ns for name, _, ns in next(iter(run["trace"]["per_device"].values()))
            if (sizes := set(shaped.findall(name))) and sizes == {str(rows)}]
    layers = flops.kinds(cfg)
    calls = len(kernels) / max(layers.count("latent"), 1) * layers.count("kda")
    if not mine or not calls:
        return None
    cost = flops.kda_step_cost(cfg, rows)
    p = peaks.peaks_for(run["peaks_kind"])
    least = max(cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"])
    print(f"kda rule: {len(mine)} events over {calls:.0f} layer-steps, "
          f"{sum(mine) / calls / 1e3:.1f} us a layer a step against {least * 1e6:.1f} us", flush=True)
    return 100.0 * least * calls / (sum(mine) / 1e9)
