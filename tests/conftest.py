"""Test environment: a virtual 8-device CPU mesh, asked for before JAX
is imported.

Mirrors SURVEY.md section 4's prescription: multi-host-simulated
collective tests with one process and 8 XLA CPU devices.  The tests run
on the CPU whatever the machine holds, so they are deterministic and
parallel-safe; ``chip_smoke.py`` is the entry point that runs on the
chip.  JAX reads ``JAX_PLATFORMS`` when it is imported and ``XLA_FLAGS``
when its backend starts, so both are set here, ahead of every import
that pulls jax in.
"""
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if not f.startswith("--xla_force_host_platform_device_count")]
    + ["--xla_force_host_platform_device_count=8"])

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the always-on flight recorder (obs/flight.py) dumps forensic bundles
# on quarantine/chaos triggers many tests exercise on purpose; keep the
# bundles out of the repo checkout (tests that assert on them point the
# recorder at their own tmp_path)
if "LIGHTGBM_TPU_FLIGHT_DIR" not in os.environ:
    os.environ["LIGHTGBM_TPU_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="lgbt-flight-test-")
