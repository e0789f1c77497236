"""CPU self-tests of the benchmark's own yardstick, run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of the repository's tier-1 tests (tests/)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
