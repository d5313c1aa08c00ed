"""The benchmark's own tests run on the CPU, from the repo root:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
