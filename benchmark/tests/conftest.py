"""The benchmark's own tests: `python3 -m pytest benchmark/tests -q` from the
root of the checkout. Outside tier-1 (`tests/`). They run on the CPU; the
drivers run in processes of their own (see toy.py)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (HERE, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
