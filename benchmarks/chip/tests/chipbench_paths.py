"""Puts the harness and the program on the import path of the benchmark's
own tests, which run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

Every test module here imports it before the harness.  It is a module of
its own name, not a ``conftest.py``, so that these tests can be collected
in one pytest run with the repository's ``tests/``, whose modules import
their own ``conftest`` by that name.
"""
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for _p in (str(HERE.parents[2] / "src"), str(HERE.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
