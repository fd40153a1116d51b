"""The benchmark's own tests: run by hand with
``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider``
(the repo's tier-1 command collects ``tests/`` only).  No JAX topology call
is made while any of these files is imported."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
