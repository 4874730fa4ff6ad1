"""The benchmark's own tests run on the CPU: JAX is held there, and the
Pallas kernels run in interpret mode.  The program under test is imported
from the checkout's ``src``."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "src"))
