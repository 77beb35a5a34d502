"""Share of the device's busy time that the admit (prefill) programs take in
the traced slice of the MiMo-V2 cell, in percent: K-EXAONE's definition
(`admit_device_share.serve.kexaone`) on this model's step marks, one
full-layer kernel a step (`steps_mimo_v2.periods`): what the periods holding
an admit program spend beyond one plain step each, over the busy time of all
periods. Every admit lengthens the turn it runs in for all 128 callers, so
this is the part of `tpot_p95_ms` that a faster decode step leaves alone."""

import steps_mimo_v2 as steps


def read(run):
    split = steps.periods(run)
    if split is None or split["all_ns"] <= 0:
        return None
    return 100.0 * split["admit_ns"] / split["all_ns"]
