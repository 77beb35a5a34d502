"""Readings that the limits of `correct` are set from (builder's tool; the
benchmark's own runs never call it).

  python benchmarks/chip/calibrate.py --workload <name> --seeds 1,2,3 \
      [--control-seeds 1,2,3] [--seconds 8] [--out chiprun_out/cal.jsonl]

For every seed: the program's numbers against the plain reference (the lower
reading). For every control seed besides, the upper readings: the reference
computed in a lower precision put in the program's place, in a serving cell
also the program itself with its own int8 weights and int8 paged pool
switched on, and the planted fault (half of every batch left out; one served
token altered). One process, so that the chip's
set-up is paid once a seed and the compiled programs are shared."""

import argparse
import gc
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


def train_seed(cell, driver, seed, control: bool) -> dict:
    from accelerate_tpu.state import AcceleratorState, GradientState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    built = driver.build(cell, seed)
    got = driver.program_readings(built, cell, seed, driver.feed(built["loader"]),
                                  int(cell.traffic["reference_steps"]))
    hp, batches = built["hp"], built["batches"]
    built.clear()
    gc.collect()
    want = driver.reference_readings(cell, seed, batches, hp)
    row = {"seed": seed, "program": driver.gaps(got, want), "losses": got["losses"],
           "reference_losses": want["losses"]}
    if control:
        row["control_int8"] = driver.gaps(
            driver.reference_readings(cell, seed, batches, hp, quant="int8"), want)
        half = slice(0, batches[0].shape[0] // 2)
        row["fault_half_batch"] = driver.gaps(
            driver.reference_readings(cell, seed, batches, hp, rows=half), want)
    return row


def served_sample(cell, driver, seed, seconds: float):
    """Drive the cell's loop for a short window and take the sample a run
    would compare; the engine is ended and freed before the reference runs."""
    served = driver.drive(cell, seed, seconds)
    sample = driver.pick_sample(served["done"], seed, int(cell.traffic.get("check_requests", 4)))
    driver.close(served)
    served.clear()
    gc.collect()
    return sample


def own_int8(cell):
    """The cell with the program's own lower precision switched on: packed
    int8 weights and an int8 paged pool."""
    import copy

    low = copy.copy(cell)
    low.spec = dict(cell.spec, engine=dict(cell.spec["engine"], weight_quant="int8"),
                    kv_cache_dtype="int8")
    return low


def gap_row(driver, gaps) -> dict:
    """The compared numbers, and every gap above 0 for a look at other statistics."""
    return dict(driver.gap_numbers(gaps), flipped=int((gaps > 0).sum()), tokens=int(gaps.size),
                gaps=sorted(float(g) for g in gaps[gaps > 0]))


def serve_seed(cell, driver, seed, control: bool, seconds: float) -> dict:
    import copy

    sample = served_sample(cell, driver, seed, seconds)
    row = {"seed": seed, "requests": len(sample),
           "program": gap_row(driver, driver.logit_gaps(cell, seed, sample))}
    if control:
        for quant in ("int8", "fp8"):
            row[f"control_{quant}"] = gap_row(driver, driver.logit_gaps(cell, seed, sample, quant=quant))
        altered = copy.deepcopy(sample[:1])  # one token of one answer altered where it is produced
        out = altered[0][1]
        out.tokens[len(out.tokens) // 2] = (out.tokens[len(out.tokens) // 2] + 1) % cell.config["vocab_size"]
        row["fault_altered_token"] = gap_row(driver, driver.logit_gaps(cell, seed, altered + sample[1:]))
    return row


def serve_own_int8(cell, driver, seed, seconds: float) -> dict:
    """The program with its own int8 path switched on, in the program's place."""
    try:
        sample = served_sample(own_int8(cell), driver, seed, seconds)
        return {"seed": seed, "requests": len(sample),
                "control_own_int8": gap_row(driver, driver.logit_gaps(cell, seed, sample))}
    except Exception as err:  # a control that crashes has failed, and sets no upper reading
        return {"seed": seed, "control_own_int8": {"crashed": f"{type(err).__name__}: {err}"[:400]}}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--out", default=None)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args()
    import harness

    cell = harness.Cell(args.workload, args.rehearsal)
    if args.rehearsal:
        from accelerate_tpu.test_utils.platform import force_cpu_platform

        force_cpu_platform(cell.chips)
    harness.require_chips(cell)
    harness.configure_cache()
    driver = importlib.import_module(f"drivers.{cell.spec['driver']}")
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    def note(row, t):
        row["seconds"] = time.perf_counter() - t
        text = json.dumps(row)
        print(text[:1500], flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t = time.perf_counter()
        if cell.spec["driver"] == "train":
            note(train_seed(cell, driver, seed, seed in controls), t)
            continue
        note(serve_seed(cell, driver, seed, seed in controls, args.seconds), t)
        if seed in controls:
            t = time.perf_counter()
            note(serve_own_int8(cell, driver, seed, args.seconds), t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
