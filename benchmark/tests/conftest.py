"""These tests sit outside ``tests/`` on purpose: they are the
benchmark's own, and the tier-1 count does not move with them.  Run
them from the root of the repo: ``JAX_PLATFORMS=cpu python3 -m pytest
benchmark/tests -q``.  Nothing here touches a chip, and nothing here
is a speed."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
