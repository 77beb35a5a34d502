"""Share of the device's busy time that the admit (prefill) programs take in
the traced slice, in percent. The engine's dispatches, in the order the
program's span ring holds them, are paired with the device's program runs
(`program_spans.serve_split`): a run paired with a `step` dispatch must hold
one decode kernel per layer and a run paired with an admit none, or nothing is
reported. Every admit program lengthens the turn it runs in for all callers,
so this is the part of `tpot_p95_ms` that a faster decode step leaves alone."""

import program_spans


def read(run):
    split = program_spans.serve_split(run)
    if split is None:
        return None
    return 100.0 * split["admit_ns"] / (split["admit_ns"] + split["step_ns"] + split["helper_ns"])
