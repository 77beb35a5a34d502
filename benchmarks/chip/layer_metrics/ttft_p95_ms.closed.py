"""Time to the first token in a closed loop at capacity: nearest-rank p95, in
ms, of `first_token_time - arrival_time` over every request of the mix that
got its first token inside the window, finished or not. A caller's new
request waits for the turn in flight and for its admit program, so this reads
the scheduler and the admit programs; at some hundred requests a window it
hops by whole turns from seed to seed, which is why it carries no bound."""

import stats


def read(run):
    window = run.get("window")
    if run["cell"].rehearsal or not window or len(window.get("ttft_ms", ())) < 20:
        return None
    return stats.percentile(window["ttft_ms"], 95)
