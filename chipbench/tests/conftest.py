"""The benchmark's own tests: ``pytest chipbench/tests`` from the repo root.

They run on the CPU (the Pallas kernels in interpret mode) at MobileNet-v1
0.25@96, and import the harness from ``chipbench/`` and the program from
``src/``.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
