"""Tests of the benchmark itself: ``python -m pytest chipbench/tests``.

They run on the CPU at tiny sizes and never look for a chip."""

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
