"""The band flash kernel's share of its roofline in MiMo-V2's admits, where
it runs with the sinks on the five sliding layers: for each of its events
(`steps_mimo_v2.band_events`: a `%window_attn.N` custom call of an admit
program, whose line names its queries `[rows, 64, bucket, 192]`), the least
time the chip could take for that call (`flops_mimo_v2.window_prefill_cost`
over the bucket: the larger of the band's FLOPs, every query against the keys
of its window, over the bf16 peak and the q, k, v and output bytes over the
HBM bandwidth), summed, over the events' summed device time. In percent. The
kernel computes whole 512 x 512 blocks of the band, so it does several times
the window's FLOPs and reads well under 100.

The least work counts every row of the bucket, the prompt's padding
included: the trace names the bucket and not the prompt. A prompt drawn
log-uniform inside a bucket's octave fills 1 / (2 ln 2), about 72%, of it on
average, so the share of the real rows' least work is about 0.72 of the
reading, and a kernel that skips the padded rows reads higher here for work
it no longer does."""

import flops_mimo_v2 as flops
import peaks
import steps_mimo_v2 as steps


def read(run):
    cell, band = run["cell"], steps.band_events(run)
    if not band:
        return None
    p = peaks.peaks_for(run["peaks_kind"])
    least = 0.0
    for _, rows, bucket in band:
        cost = flops.window_prefill_cost(cell.config, bucket)
        least += rows * max(cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"])
    seconds = sum(e[2] for e, _, _ in band) / 1e9
    print(f"band kernel: {len(band)} events over buckets {sorted({b for _, _, b in band})}, "
          f"{seconds / len(band) * 1e6:.1f} us each, {least / len(band) * 1e6:.1f} us least", flush=True)
    return 100.0 * least / seconds
