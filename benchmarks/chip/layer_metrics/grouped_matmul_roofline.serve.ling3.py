"""The routed experts' grouped products' share of their roofline in Ling 3.0
flash's decode step: the least time the chip could take for one layer's call
(`flops_ling3.expert_matmul_cost` at the picks held and the experts touched a
step that the program counted over the traced slice: bound by the touched
experts' weights, 11.8 MB each), times the calls, over the summed device time
of the grouped products' events. In percent.

Every grouped product on the TPU is the Pallas grouped matmul (megablox
`gmm`), which reaches the device as Mosaic custom calls named `%gmm.N`; the
decode step's are told from an admit program's by their rows (slots times
experts per token, 2,048: an admit's carry its bucket's picks, 4,096 and
more), and a layer's call is two of them: gate and up in one, down in the
other."""

import flops_ling3 as flops
import peaks
import steps_ling3 as steps


def read(run):
    cell, trace, counted = run["cell"], run.get("trace"), steps.per_step(run)
    if cell.rehearsal or not trace or not trace.get("per_device") or counted is None:
        return None
    rows = int(cell.spec["engine"]["max_concurrency"]) * int(cell.config["num_experts_per_tok"])
    head = f" = f32[{rows},"
    mine = [ns for name, _, ns in next(iter(trace["per_device"].values()))
            if name.startswith("%gmm") and head in name[:64]]
    if len(mine) < 2:
        return None
    layers = flops.expert_layers(cell.config)
    cost = flops.expert_matmul_cost(cell.config, counted["picks_held"] / layers,
                                    counted["experts_touched"] / layers)
    p = peaks.peaks_for(run["peaks_kind"])
    least = max(cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"])
    print(f"expert products: {len(mine)} events, {sum(mine) / len(mine) / 1e3:.1f} us each; a call of "
          f"{counted['picks_held'] / layers:.0f} picks on {counted['experts_touched'] / layers:.1f} "
          f"experts needs {least * 1e6:.1f} us", flush=True)
    return 100.0 * least * (len(mine) / 2.0) / (sum(mine) / 1e9)
