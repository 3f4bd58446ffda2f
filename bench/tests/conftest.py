"""Tests of the chip benchmark's own code, run on the CPU:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests

Four virtual CPU devices stand in for a four-chip host where a test needs
a mesh; the flag must be set before JAX starts.
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT / "bench"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
