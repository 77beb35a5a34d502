"""The fused paged kernel's share of its roofline on MiMo-V2's two
full-attention layers (64 query heads on 4 key/value heads, keys 192 wide and
values 128, read at their own widths): the least time the chip could take for
one call (`flops_mimo_v2.gqa_decode_cost`: the live keys and values read once
and the queries in and outputs out; at 16 FLOP a byte the bytes bound it), at
the live keys a dispatch that the program counted over the traced slice
(`serving/paged_decode/live_tokens`), times the calls, over the summed device
time of the kernel's events on the pool (the `%attn.N` custom calls whose
line names no window ring, two a step). In percent."""

import flops_mimo_v2 as flops
import peaks
import steps_mimo_v2 as steps


def read(run):
    cell, kernels, live = run["cell"], steps.full_kernels(run), steps.live_tokens(run)
    if not kernels or live is None:
        return None
    rows = int(cell.spec["engine"]["max_concurrency"])
    cost = flops.gqa_decode_cost(cell.config, live, rows)
    p = peaks.peaks_for(run["peaks_kind"])
    least = max(cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"])
    seconds = sum(ns for _, _, ns in kernels) / 1e9
    print(f"full-layer kernel: {len(kernels)} events, {seconds / len(kernels) * 1e6:.1f} us each; "
          f"{live:.0f} live keys a call need {least * 1e6:.1f} us", flush=True)
    return 100.0 * least * len(kernels) / seconds
