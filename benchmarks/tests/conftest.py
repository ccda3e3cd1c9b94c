"""pytest benchmarks/tests -q  (CPU; tiny widths; Pallas in interpret mode).

Not part of tier-1 (`tests/`): these check the instrument, not the program."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
