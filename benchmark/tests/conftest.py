"""The benchmark's own tests run on the CPU: ``pytest benchmark/tests``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, so the four-chip twin finds its mesh
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
