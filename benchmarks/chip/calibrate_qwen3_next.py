"""`calibrate.py` for the Qwen3-Next serving cell (builder's tool; the
benchmark's own runs never call it).

  python benchmarks/chip/calibrate_qwen3_next.py --seeds 1,2,3 [--control-seeds 1,2,3] \
      [--controls int8,fp8] [--seconds 8] [--out chiprun_out/cal_qwen3_next.jsonl]

For every seed: the program's numbers against the plain reference (the lower
reading). For every control seed besides, the upper readings: the reference
computed below the stated precision put in the program's place, and the
planted fault (one served token altered). The controls: every product with a
weight matrix in vector-wise int8, or in scaled float8 e4m3 (the precisions
next below the stated bfloat16 compute); the DeltaNet matrix S kept in
bfloat16 between tokens, and the router in bfloat16 (the two places the
configuration states float32). `calibrate.py`'s own serving controls include
the program's int8 path, which this model has not; the sampling and the rows
are its functions, imported."""

import argparse
import copy
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

CELL = "qwen3-next-80b-a3b.serve.closed128"
CONTROLS = ("int8", "fp8", "state_bf16", "router_bf16")


def altered(sample, vocab: int):
    """The sample with one token of its first answer altered where it is produced."""
    out = copy.deepcopy(sample[:1])
    tokens = out[0][1].tokens
    tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % vocab
    return out + sample[1:]


def seed_row(cell, driver, seed: int, controls, seconds: float) -> dict:
    """The program's reading at `seed`, and the reading of each of `controls`
    and of the planted fault where there are any."""
    from calibrate import gap_row, served_sample

    sample = served_sample(cell, driver, seed, seconds)
    gaps = driver.gaps_by_control(cell, seed, sample, (None, *controls))
    row = {"seed": seed, "requests": len(sample), "program": gap_row(driver, gaps[None])}
    for low in controls:
        row[f"control_{low}"] = gap_row(driver, gaps[low])
    if controls:
        row["fault_altered_token"] = gap_row(
            driver, driver.logit_gaps(cell, seed, altered(sample, cell.config["vocab_size"])))
    return row


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default=CELL)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--controls", default=",".join(CONTROLS))
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
    lows = tuple(s for s in args.controls.split(",") if s)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t = time.perf_counter()
        row = seed_row(cell, driver, seed, lows if seed in controls else (), args.seconds)
        row["seconds"] = time.perf_counter() - t
        text = json.dumps(row)
        print(text[:1500], flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
