import os
import sys

# The suite runs on the CPU, and so do the drivers its subprocesses start
# (they inherit this). Device-count flags are deliberately NOT set here —
# smoke tests run on the single real CPU device; integration tests that need
# a multi-device host platform (elastic scaling) spawn subprocesses whose
# drivers force it themselves (repro.launch.devices.pick_devices).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
