"""The fused paged-decode kernel's share of its roofline: the least time the
chip could take to read the live keys and values of one call
(`flops.paged_decode_cost`; the kernel is bound by bytes), times the calls
in the traced slice (layers x engine steps), over the summed device time of
the kernel's events. In percent.

Live tokens per call are not reported by the program: they are taken from
the requests that finished in the run's window, each weighing in for as many
steps as it decoded, at its mean context (prompt + half of its output),
times the slots, which a closed loop keeps full.

The kernel carries no name yet; in the serving programs it is the only
operation whose custom-call target is `tpu_custom_call` and whose result is
named after the flax scope `attn` (`%attn.N`): prefill runs XLA attention."""

import flops
import peaks


def read(run):
    cell, trace, traced, window = run["cell"], run.get("trace"), run.get("traced"), run.get("window")
    if cell.rehearsal or not trace or not traced or not window or not window["done"]:
        return None
    seconds = sum(s for name, s in trace["by_name_s"].items()
                  if "tpu_custom_call" in name and name.startswith("%attn"))
    steps = traced["phases1"]["steps"] - traced["phases0"]["steps"]
    if seconds <= 0 or steps <= 0:
        return None
    weights_ = [len(out.tokens) for _, out in window["done"]]
    contexts = [len(item["prompt"]) + len(out.tokens) / 2.0 for item, out in window["done"]]
    rows = int(cell.spec["engine"]["max_concurrency"])
    live = rows * sum(w * c for w, c in zip(weights_, contexts)) / sum(weights_)
    least = flops.roofline_seconds(flops.paged_decode_cost(cell.config, live, rows),
                                   peaks.peaks_for(run["peaks_kind"]))
    return 100.0 * least * cell.config["n_layer"] * steps / seconds
