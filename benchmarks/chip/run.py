"""Run one cell of BENCHMARK.json once, in one process.

  python benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's driver (`drivers/<kind>.py`, named in `workloads/<name>.json`)
builds the system from the configuration's file, warms every shape the window
uses, measures for `--seconds`, checks what the timed path produced against
the plain reference, and prints one JSON object as the last line of standard
output. Without a TPU holding the chips the cell asks for: exit code 3, no
result. `--rehearsal` walks the same code at a tiny size on the CPU and
prints no device number; it is for the builder and the unit tests."""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


def main(argv=None, t0: float = T0) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true",
                        help="tiny sizes on the CPU, interpreted kernels, no device number")
    args = parser.parse_args(argv)

    import harness

    cell = harness.Cell(args.workload, args.rehearsal)
    if args.rehearsal:
        from accelerate_tpu.test_utils.platform import force_cpu_platform

        force_cpu_platform(cell.chips)
    import accelerate_tpu  # noqa: F401  (fails here, before any result, where the program is absent)

    device = harness.require_chips(cell)
    print(f"device {device} compile cache {harness.configure_cache()}", flush=True)
    driver = importlib.import_module(f"drivers.{cell.spec['driver']}")
    driver.run(cell, device, seed=args.seed, seconds=args.seconds, trace=bool(args.trace), t0=t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
