"""Tests of the chip benchmark's own code, run on the CPU:

    python -m pytest benchmarks/chip -q

They put the checkout and its ``src/`` on the import path, as ``run.py``
does, and keep every compile cache out of the checkout."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
