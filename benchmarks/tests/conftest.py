"""The benchmark's own tests (not tier-1): run with
``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("KTPU_PALLAS", "interpret")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
