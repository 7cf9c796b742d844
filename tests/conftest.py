"""Shared fixtures. NOTE: no XLA_FLAGS here — unit tests run on the 1
real CPU device (the dry-run sets its own 512-device flag in its own
process; SPMD tests spawn subprocesses with 8 devices)."""
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SRC = ROOT / "src"
for path in (SRC, ROOT):       # the library; chip_smoke.py and benchmarks/
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))


@pytest.fixture(scope="session")
def spmd_env():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(SRC)
    return env
