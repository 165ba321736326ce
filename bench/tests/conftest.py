"""The benchmark's own tests run on the CPU, with four host devices for the
cells that span chips: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FLAG}=4").strip()
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(_ROOT, "src"), _ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
