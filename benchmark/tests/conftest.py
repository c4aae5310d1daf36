import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
# the tests rehearse on the CPU; a run of the benchmark never sets this
os.environ.setdefault("JAX_PLATFORMS", "cpu")
