"""The fused paged kernel's share of its roofline on K-EXAONE's full-attention
layer (grouped-query attention, 64 query heads on 8 key/value heads of 128,
no position): the least time the chip could take for one call
(`flops_k_exaone.gqa_decode_cost`: the live keys and values read once, at 16
FLOP a byte the bytes bound it), at the live keys a dispatch that the program
counted over the traced slice (`serving/paged_decode/live_tokens`), times the
calls, over the summed device time of the kernel's events on the pool (the
`%attn.N` custom calls whose line names no window ring). In percent."""

import flops_k_exaone as flops
import peaks
import steps_k_exaone as steps


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
