"""Pipeline parallelism vs sequential reference (subprocess, 4 devices)."""
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.models.pipeline import pipeline_apply

mesh = make_mesh((4,), ("pipe",))
S, M, mb, d = 4, 8, 2, 16
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (S, d, d)) * 0.3

def stage_fn(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.PRNGKey(1), (M * mb, d))
ref = x
for s in range(S):
    ref = jnp.tanh(ref @ W[s])

fn = pipeline_apply(stage_fn, mesh, microbatches=M)
out = jax.jit(fn)(W, x)
assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5), \
    np.abs(np.asarray(out) - np.asarray(ref)).max()
print("pipeline OK")
"""


@pytest.mark.timeout(600)
def test_pipeline_matches_sequential(spmd_env):
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=spmd_env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "pipeline OK" in proc.stdout
