"""``chip_smoke.py`` on the CPU: the tiny-size rehearsal passes every check
on one and on four virtual devices, the default run refuses to start
without a TPU, and the compile cache lands only where
``repro.launch.cache`` says. Each run is its own process (its own device
count, its own cache)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(args, env, cwd=ROOT):
    return subprocess.run([sys.executable, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=600)


def _cpu_env(devices=1, cache_dir=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    return env


def _records(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(ln) for ln in proc.stdout.splitlines()]


@pytest.mark.parametrize("chips", [1, 4])
def test_smoke_tiny_passes_every_check(tmp_path, chips):
    args = ["chip_smoke.py", "--size", "tiny", "--chips", str(chips)]
    env = _cpu_env(chips, tmp_path / "cache")
    recs = _records(_run(args, env))
    by_phase = {}
    for r in recs[:-1]:
        by_phase.setdefault(r["phase"], []).append(r)
    writes = by_phase["1/write"]
    assert {(w["method"], w["depth"]) for w in writes} == {
        (m, d) for m in ("twophase", "tam") for d in (1, 2)}
    assert all(w["bytes_match_write_reference"] and w["dropped_requests"] == 0
               and w["dropped_elems"] == 0 for w in writes)
    assert [r["payloads_match"] for r in by_phase["1/read"]] == [True, True]
    assert by_phase["1/setup"][0]["input_shard_devices"] == chips
    if chips == 1:
        (ck,) = by_phase["2/checkpoint"]
        assert ck["leaves_bitwise_equal"] == ck["leaves"] == 5
    else:
        assert "2/checkpoint" not in by_phase
    assert recs[-1] == {"ok": True, "device": {"platform": "cpu",
                                               "kind": "cpu",
                                               "count": chips}}
    # a second run with the same cache compiles nothing
    (cold,) = by_phase["compile_cache"]
    (warm,) = [r for r in _records(_run(args, env))
               if r.get("phase") == "compile_cache"]
    assert cold["misses"] > 0 and cold["hits"] == 0
    assert (warm["hits"], warm["misses"]) == (cold["misses"], 0)


def test_smoke_refuses_without_tpu(tmp_path):
    proc = _run(["chip_smoke.py"], _cpu_env(cache_dir=tmp_path / "cache"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert not (tmp_path / "cache").exists()


CACHE_PROBE = r"""
import sys
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compilation_cache
print(enable_compilation_cache(sys.argv[1]))
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_lands_in_one_place(tmp_path, env_dir):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the entries go there alone;
    unset, they go to ``<root>/.jax_cache/`` alone; a second process with
    the same setting reuses them."""
    root, named = tmp_path / "checkout", tmp_path / "named"
    root.mkdir()
    env = _cpu_env(cache_dir=named if env_dir else None)
    env["PYTHONPATH"] = str(ROOT / "src")
    where = named if env_dir else root / ".jax_cache"
    first = _run(["-c", CACHE_PROBE, str(root)], env, cwd=tmp_path)
    assert first.returncode == 0, first.stderr[-3000:]
    assert first.stdout.strip() == str(where)
    entries = sorted(p.name for p in where.iterdir())
    assert entries
    assert not (named if not env_dir else root / ".jax_cache").exists()
    second = _run(["-c", CACHE_PROBE, str(root)], env, cwd=tmp_path)
    assert second.returncode == 0, second.stderr[-3000:]
    assert sorted(p.name for p in where.iterdir()) == entries
