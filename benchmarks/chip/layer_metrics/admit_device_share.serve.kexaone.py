"""Share of the device's busy time that the admit (prefill) programs take in
the traced slice of the K-EXAONE cell, in percent: over the periods from one
decode step's full-layer kernel to the next (`steps_k_exaone.periods`), what
the periods holding an admit program spend beyond one plain step each, over
the busy time of all periods. Every admit lengthens the turn it runs in for
all 128 callers, so this is the part of `tpot_p95_ms` that a faster decode
step leaves alone. (`admit_device_share.serve` pairs dispatches with GPT-2's
`n_layer` decode kernels a step and cannot read this model.)"""

import steps_k_exaone as steps


def read(run):
    split = steps.periods(run)
    if split is None or split["all_ns"] <= 0:
        return None
    return 100.0 * split["admit_ns"] / split["all_ns"]
