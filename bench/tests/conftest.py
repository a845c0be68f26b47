"""The benchmark's own tests run on the CPU at small sizes:
``python -m pytest bench/tests``."""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
