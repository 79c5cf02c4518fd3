"""The benchmark's own tests run on the CPU: ``JAX_PLATFORMS=cpu python -m
pytest benchmarks/chip/tests -q``.  Four virtual CPU devices stand in for
the four-chip host; Pallas kernels run in interpret mode where a test sets
``hist_pallas._INTERPRET``.  No number measured here is a device number."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
