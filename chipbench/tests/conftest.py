"""The benchmark's own tests run on the CPU at tiny sizes; none describes
or touches a TPU.  The system under test is imported from the checkout's
``src`` as ``chipbench/run.py`` imports it."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_SRC = str(Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
