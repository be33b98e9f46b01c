"""Rehearsal tests of the benchmark, run by hand on the CPU:

    python3 -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 command (that runs ``tests/``).
The CPU backend is pinned here, before anything imports JAX; ``run.py``
itself never sets the platform.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
