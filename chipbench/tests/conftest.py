"""Four virtual CPU devices for the dp4 rehearsal; nothing here touches a TPU
or describes a topology."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flag = "--xla_force_host_platform_device_count=4"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
