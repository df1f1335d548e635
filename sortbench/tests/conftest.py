"""sortbench's own tests run on the CPU, on a 4-device virtual mesh:
``JAX_PLATFORMS=cpu python -m pytest sortbench/tests -q``.  They are not
among the repo's tier-1 tests."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from mpitest_tpu.utils.platform import ensure_virtual_cpu_devices  # noqa: E402

ensure_virtual_cpu_devices(4)
