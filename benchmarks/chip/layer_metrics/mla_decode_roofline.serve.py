"""The fused paged kernel's share of its roofline on a latent pool (absorbed
latent attention): the least time the chip could take for one call
(`flops_kimi_k2.mla_decode_cost` at the live rows a decode dispatch the
program counted over the traced slice: the larger of the live latent bytes at
the HBM bandwidth and the absorbed form's FLOPs at the bf16 peak; 121 FLOP a
byte puts the kernel between the two roofs), times the calls, over the summed
device time of the kernel's events (`%attn.N` Pallas custom calls, as
`program_spans.is_decode_kernel` finds them: prefill runs the flash kernel or
XLA attention under other names). In percent."""

import flops_kimi_k2 as flops
import peaks
import steps_kimi_k2 as steps


def read(run):
    cell, kernels, live = run["cell"], steps.decode_kernels(run), steps.live_tokens(run)
    if not kernels or live is None:
        return None
    rows = int(cell.spec["engine"]["max_concurrency"])
    cost = flops.mla_decode_cost(cell.config, live, rows)
    p = peaks.peaks_for(run["peaks_kind"])
    least = max(cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"])
    seconds = sum(ns for _, _, ns in kernels) / 1e9
    print(f"latent decode kernel: {len(kernels)} events, {1e6 * seconds / len(kernels):.1f} us each; a "
          f"call over {live:.0f} live rows needs {least * 1e6:.1f} us ({cost['bytes'] / 1e6:.1f} MB, "
          f"{cost['flops'] / 1e9:.2f} GFLOP)", flush=True)
    return 100.0 * least * len(kernels) / seconds
