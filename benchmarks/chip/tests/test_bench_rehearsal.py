"""The harness end to end on the CPU, at the rehearsal sizes
(``rehearsal/``: a configuration and a cell added as files only), on 1
and 4 virtual devices: sound runs come out correct, the control and
every planted fault come out not correct, and the measuring command
refuses to run without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import faults

TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parents[2]


def _env(devices, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return env


def _runs(out: str) -> dict:
    """``{wrap: result}`` from rehearse.py's output."""
    runs, name = {}, None
    for line in out.splitlines():
        if line.startswith("# wrap "):
            name = line.split()[-1]
        elif line.startswith("{"):
            runs[name] = json.loads(line)
    return runs


CELLS = {1: ("e3sm_g_tiny_node.tam_cycle", faults.ONE_CHIP),
         4: ("e3sm_g_tiny_2x2.tam_cycle", faults.FOUR_CHIPS)}


@pytest.mark.parametrize("chips", [1, 4])
def test_sound_control_and_faults(chips, tmp_path):
    cell, planted = CELLS[chips]
    proc = subprocess.run(
        [sys.executable, str(TESTS / "rehearse.py"), "--workload", cell,
         "--seed", str(2**31 + 17), "--seconds", "0.3", "--wrap", "none",
         "control", *planted],
        env=_env(chips, tmp_path), cwd=ROOT, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    runs = _runs(proc.stdout)
    sound = runs.pop("none")
    assert sound["correct"] and sound["failed"] == 0
    assert sound["attempted"] >= 2 and sound["attempted"] % 2 == 0
    assert set(sound["metrics"]) == {"write_bw", "read_bw", "setup_s"}
    assert sound["device"] == {"platform": "cpu", "kind": "cpu",
                               "count": chips, "memory_peak_bytes": None}
    assert list(sound)[-1] == "checks"
    assert sound["checks"] == {"file_bytes_wrong": {"value": 0, "limit": 0},
                               "read_bytes_wrong": {"value": 0, "limit": 0}}
    assert set(runs) == {"control", *planted}
    for name, r in runs.items():
        assert not r["correct"], name
        assert r["failed"] > 0, name


def test_traced_run_reports_per_layer_metrics(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(TESTS / "rehearse.py"), "--workload",
         "e3sm_g_tiny_node.tam_cycle", "--seed", "7", "--seconds", "0.2",
         "--trace", "1"],
        env=_env(1, tmp_path), cwd=ROOT, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (r,) = _runs(proc.stdout).values()
    assert r["correct"]
    # the CPU has no device plane: only the host-clock metrics remain
    assert set(r["metrics"]) == {"plan_s", "compile_s"}
    assert r["device"]["window_s"] > 0.2
    assert "breakdown" not in r


def test_no_tpu_no_work(tmp_path):
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "e3sm_g_node.tam_cycle", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=_env(1, tmp_path), cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert not (tmp_path / "cache").exists()
