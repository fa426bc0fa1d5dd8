"""Tests of the benchmark's own yardstick. Run by hand:

    python -m pytest benchmark/tests -q

Everything here runs on the CPU backend at tiny sizes; nothing it prints is
a device metric."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
