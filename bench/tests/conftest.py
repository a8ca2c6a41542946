"""The benchmark's own tests run on the CPU: no chip is needed or touched."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.append(str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
