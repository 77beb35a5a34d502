import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for path in (CHIP, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
