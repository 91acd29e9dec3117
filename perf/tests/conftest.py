"""Run by hand: ``python -m pytest perf/tests -q`` from the root of the
checkout (not part of the repo's tier-1 tests)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
