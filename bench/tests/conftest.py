"""The benchmark's own tests, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH, BENCH / "refs", BENCH / "gen"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
