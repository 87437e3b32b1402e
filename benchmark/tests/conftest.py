"""The benchmark's own tests run on the CPU at tiny sizes:
`python -m pytest benchmark/tests -q` from the root of the checkout."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# never write a compile cache into the checkout from a test
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
