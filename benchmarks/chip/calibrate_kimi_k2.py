"""`calibrate.py` for the Kimi K2 serving cell (builder's tool; the benchmark's
own runs never call it).

  python benchmarks/chip/calibrate_kimi_k2.py --seeds 1,2,3 [--control-seeds 1,2,3] \
      [--controls int8,fp8] [--seconds 8] [--out chiprun_out/cal_kimi_k2.jsonl]

For every seed: the program's numbers against the plain reference (the lower
reading). For every control seed besides, the upper readings: the reference
computed below the stated precision put in the program's place, and the
planted fault (one served token altered). The controls: every product with a
weight matrix in vector-wise int8, or in scaled float8 e4m3 (the precisions
next below the stated bfloat16 compute); the latent rows a cache would hold
rounded through float8 e4m3 (next below the bfloat16 latent pool). The walk is
`calibrate_qwen3_next.py`'s `main`, given this cell and these controls."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import calibrate_qwen3_next as walk  # noqa: E402
from calibrate_qwen3_next import altered  # noqa: E402,F401  (the tests plant the fault with it)

CELL = "kimi-k2.7-code.serve.closed256"
CONTROLS = ("int8", "fp8", "latent_fp8")

if __name__ == "__main__":
    walk.CELL, walk.CONTROLS = CELL, CONTROLS  # `main` reads both as its arguments' defaults
    sys.exit(walk.main())
