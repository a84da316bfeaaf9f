"""Tests of the benchmark itself, at sizes a CPU holds:

    python3 -m pytest benchmarks/tests -q

They are not among the repo's tier-1 tests. The environment is set before JAX
is first imported.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
