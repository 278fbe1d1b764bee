"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
root of a checkout.  They run on the CPU at small sizes; the Pallas
kernels run in interpret mode there."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
