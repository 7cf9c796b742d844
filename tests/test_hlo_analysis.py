"""The roofline rests on the loop-aware HLO parser — test it directly
(subprocess with 4 virtual devices)."""
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.launch.hlo_analysis import HloCostModel
from repro.launch.mesh import make_mesh
from jax.sharding import NamedSharding, PartitionSpec as P

# 1. while-loop flops multiplied by trip count (XLA counts body once)
def body(c, w):
    return c @ w, ()
W = jnp.ones((10, 128, 128), jnp.float32)
x = jnp.ones((128, 128), jnp.float32)
c = jax.jit(lambda x, W: jax.lax.scan(body, x, W)[0]).lower(x, W).compile()
t = HloCostModel(c.as_text()).total()
expected = 10 * 2 * 128**3
assert abs(t.flops - expected) / expected < 0.01, (t.flops, expected)

# 2. nested scans multiply
def outer(c, w):
    c2, _ = jax.lax.scan(lambda a, _: (a @ w, ()), c, None, length=5)
    return c2, ()
c2 = jax.jit(lambda x, W: jax.lax.scan(outer, x, W)[0]).lower(x, W).compile()
t2 = HloCostModel(c2.as_text()).total()
assert abs(t2.flops - 5 * expected) / (5 * expected) < 0.01

# 3. collective wire bytes: psum of 4KB over a 4-ring = 2*(3/4)*4KB
mesh = make_mesh((4,), ("d",))
f = jax.jit(lambda x: x.sum(0, keepdims=True),
            in_shardings=NamedSharding(mesh, P("d", None)),
            out_shardings=NamedSharding(mesh, P(None, None)))
c3 = f.lower(jax.ShapeDtypeStruct((4, 1024), jnp.float32)).compile()
t3 = HloCostModel(c3.as_text()).total()
ar = t3.coll_bytes.get("all-reduce", 0)
assert abs(ar - 2 * 0.75 * 4096) < 1, ar

# 4. fusion-internal bytes are NOT counted as HBM traffic
def g(x):
    return jnp.sin(x) * 2 + jnp.cos(x)   # one fused kernel
c4 = jax.jit(g).lower(jnp.ones((1024, 1024), jnp.float32)).compile()
t4 = HloCostModel(c4.as_text()).total()
assert t4.bytes <= 3 * 4 * 1024 * 1024, t4.bytes  # ~in+out only

print("hlo_analysis OK")
"""


@pytest.mark.timeout(600)
def test_hlo_analyzer(spmd_env):
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=spmd_env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2500:]
    assert "hlo_analysis OK" in proc.stdout
