"""Tests of the benchmark itself: ``JAX_PLATFORMS=cpu python -m pytest
perfbench/tests``. They run the rehearsal sizes of each mix on the CPU;
none of them reports a device number."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
