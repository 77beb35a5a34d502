"""`calibrate.py` for the Ling 3.0 flash serving cell (builder's tool; the
benchmark's own runs never call it).

  python benchmarks/chip/calibrate_ling3.py --seeds 1,2,3 [--control-seeds 1,2,3] \
      [--controls int8,decay_mean] [--seconds 8] [--out chiprun_out/cal_ling3.jsonl]

For every seed: the program's numbers against the plain reference (the lower
reading). For every control seed besides, the upper readings: the reference
altered put in the program's place, and the planted fault (one served token
altered). The controls: every product with a weight matrix in vector-wise
int8 (the precision next below the stated bfloat16 compute); the per-channel
decay replaced by its mean over a head's channels; the router's group limit
dropped (the plain top-8 of 512); the KDA state kept in bfloat16 between
tokens. The walk is `calibrate_qwen3_next.py`'s `main`, given this cell and
these controls."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import calibrate_qwen3_next as walk  # noqa: E402
from calibrate_qwen3_next import altered  # noqa: E402,F401  (the tests plant the fault with it)

CELL = "ling-3.0-flash-vl.serve.closed256"
CONTROLS = ("int8", "decay_mean", "no_group_limit", "state_bf16")

if __name__ == "__main__":
    walk.CELL, walk.CONTROLS = CELL, CONTROLS  # `main` reads both as its arguments' defaults
    sys.exit(walk.main())
