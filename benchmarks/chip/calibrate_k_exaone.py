"""`calibrate.py` for the K-EXAONE serving cell (the tool that sets the
cell's limits; the benchmark's own runs never call it).

  python benchmarks/chip/calibrate_k_exaone.py --seeds 1,2,3 [--control-seeds 1,2,3] \
      [--controls int8,no_window] [--seconds 8] [--out cal_k_exaone.jsonl]

For every seed: the program's numbers against the plain reference (the lower
reading). For every control seed besides, the upper readings: the reference
altered put in the program's place, and the planted fault (one served token
altered). The controls: every product with a weight matrix in vector-wise
int8 (the precision next below the stated bfloat16 compute); the window
removed, so that the sliding layers attend the whole causal context; rotary
positions on the full-attention layer, which carries none. The walk is
`calibrate_qwen3_next.py`'s `main`, given this cell and these controls."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import calibrate_qwen3_next as walk  # noqa: E402
from calibrate_qwen3_next import altered  # noqa: E402,F401  (the tests plant the fault with it)

CELL = "k-exaone-236b-a23b.serve.long128"
CONTROLS = ("int8", "no_window", "rope_global")

if __name__ == "__main__":
    walk.CELL, walk.CONTROLS = CELL, CONTROLS  # `main` reads both as its arguments' defaults
    sys.exit(walk.main())
