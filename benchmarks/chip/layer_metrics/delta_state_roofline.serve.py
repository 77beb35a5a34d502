"""The decode step's delta rule's share of its roofline: the least time the
chip could take to read and write the slots' DeltaNet matrices once
(`flops_qwen3_next.delta_step_cost`: bound by bytes, float32), times the calls
(linear layers x the decode steps the trace holds, one fused paged kernel
each), over the summed device time of the events that touch the state. In
percent.

The delta rule is plain XLA: its fusions carry XLA's names
(`%multiply_reduce_fusion.N`), and the `delta_step` scope is in the HLO's
metadata, which the trace's lines do not carry. They are found by what they
touch: a line that names the whole per-slot state `f32[slots, value heads, key
dim, value dim]` and no state-shaped array of another leading size (an admit
program scatters its group's `f32[group, ...]` into the same buffer)."""

import re

import flops_qwen3_next as flops
import peaks
import program_spans


def read(run):
    cell, trace = run["cell"], run.get("trace")
    if cell.rehearsal or not trace or not trace.get("per_device"):
        return None
    cfg, rows = cell.config, int(cell.spec["engine"]["max_concurrency"])
    tail = f"{cfg['linear_num_value_heads']},{cfg['linear_key_head_dim']},{cfg['linear_value_head_dim']}]"
    shaped = re.compile(rf"f32\[(\d+),{re.escape(tail)}")
    events = next(iter(trace["per_device"].values()))
    mine = [ns for name, _, ns in events
            if (sizes := set(shaped.findall(name))) and sizes == {str(rows)}]
    kernels = sum(1 for name, _, _ in events if program_spans.is_decode_kernel(name))
    linear = flops.kinds(cfg).count("linear")
    if not mine or not kernels or not linear:
        return None
    cost = flops.delta_step_cost(cfg, rows)
    p = peaks.peaks_for(run["peaks_kind"])
    least = max(cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"])
    print(f"delta rule: {len(mine)} events over {kernels} steps x {linear} layers, "
          f"{sum(mine) / (kernels * linear) / 1e3:.1f} us a layer a step against {least * 1e6:.1f} us",
          flush=True)
    return 100.0 * least * kernels * linear / (sum(mine) / 1e9)
