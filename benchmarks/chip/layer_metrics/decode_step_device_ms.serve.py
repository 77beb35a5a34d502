"""Device time of one decode step: the median busy time, in ms, of the program
runs of the traced slice that `program_spans.serve_split` pairs with the
engine's `step` dispatches (each checked to hold one decode kernel per layer).
The turn a caller waits for is this plus the admit programs that ran in it."""

import statistics

import program_spans


def read(run):
    split = program_spans.serve_split(run)
    if split is None or not split["step_runs_ns"]:
        return None
    return statistics.median(split["step_runs_ns"]) / 1e6
