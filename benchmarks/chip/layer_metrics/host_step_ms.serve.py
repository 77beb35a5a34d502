"""Host work of one `ServingEngine.step`: the sums of
`ServingMetrics.step_phase_*_s` over the window, `fetch_blocked` left out
(that is waiting on the device), over the steps made. In milliseconds."""

HOST_PHASES = ("schedule", "draft", "dispatch", "deliver", "journal", "telemetry")


def read(run):
    window = run.get("window")
    if run["cell"].rehearsal or not window:
        return None
    a, b = window["phases0"], window["phases1"]
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    return 1e3 * sum(b[p] - a[p] for p in HOST_PHASES) / steps
