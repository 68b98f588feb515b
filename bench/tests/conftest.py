"""The benchmark's own CPU tests: ``python -m pytest bench/tests``.

They are not part of the repository's tier-1 suite (``testpaths`` is
``tests``).  They run on the CPU, at small sizes."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
