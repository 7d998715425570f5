import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

# four host devices stand in for a four-chip host (a meshed configuration's
# rehearsal; set before anything imports jax, as tests/conftest.py does)
FOUR_DEVICES = "--xla_force_host_platform_device_count=4"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + FOUR_DEVICES).strip()
