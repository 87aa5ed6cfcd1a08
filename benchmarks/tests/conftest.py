"""The benchmark's own tests run on the CPU, with four virtual devices
for the data-parallel rehearsal. Not part of the repo's tier-1 tests:
run them with ``python3 -m pytest benchmarks/tests``."""

import os
import sys

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)
