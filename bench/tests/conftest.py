"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

(the repository's own test run collects ``tests/`` only).  Cells run here
at tiny sizes, with the Pallas kernels in interpret mode.
"""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
