"""CPU checks of the benchmark's own code: run with
``JAX_PLATFORMS=cpu python3 -m pytest bench/tests -q`` from the checkout."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
