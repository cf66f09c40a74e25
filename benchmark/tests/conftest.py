"""Run by hand (``python -m pytest benchmark/tests -q``), on the CPU:
these check the yardstick, not the program, and are outside tier-1."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
