"""The benchmark's own tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
