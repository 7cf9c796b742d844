"""Device-path evidence: slow-axis collective bytes, TAM vs two-phase.

Lowers both SPMD collective-write schedules for an 8-device
(2 nodes x 2 lagg x 2 lmem) mesh and parses the compiled HLO for
wire bytes per collective kind. derived = TAM/two-phase byte ratio on
the slow ('node') axis proxy (all_to_all + node-axis gathers).

The counts come from the CPU backend's HLO on 8 virtual devices, so the
child runs with ``JAX_PLATFORMS=cpu`` (it never reaches for a chip the
parent may hold) and every row is labelled ``spmd_bytes/cpu_hlo/...``.
They are byte counts, not device measurements.
"""
from __future__ import annotations

import json
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core import IOConfig, contiguous_layout, make_tam_write, make_twophase_write
from repro.launch.hlo_analysis import HloCostModel
from repro.launch.mesh import make_io_mesh

mesh = make_io_mesh(2, 2, 2)
layout = contiguous_layout(4096, 2)
# the paper's regime: request METADATA dominates payload (E3SM-F: 1.4e9
# tiny requests for 14 GiB). 256 adjacent 1-element requests per rank
# coalesce to ~1 run at the local aggregator, so TAM's inter-node
# metadata capacity is 16 pairs vs two-phase's 256.
cfg_tam = IOConfig(req_cap=256, data_cap=64, coalesce_cap=16)
cfg_2ph = IOConfig(req_cap=256, data_cap=64, coalesce_cap=256)

O = np.full((8, 256), 2**31 - 1, np.int32)
L = np.ones((8, 256), np.int32)
C = np.full(8, 256, np.int32)
D = np.ones((8, 64), np.int32)
for p in range(8):
    O[p] = np.arange(256, dtype=np.int32) + p * 256
    L[p] = 1

out = {}
for name, mk, cfg in (("tam", make_tam_write, cfg_tam),
                      ("twophase", make_twophase_write, cfg_2ph)):
    c = jax.jit(mk(mesh, layout, cfg)).lower(O, L, C, D).compile()
    t = HloCostModel(c.as_text()).total()
    out[name] = {k: v for k, v in t.coll_bytes.items()}
print(json.dumps(out))
"""


def collective_bytes():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        return [("spmd_bytes/ERROR", 0.0, proc.stderr.strip()[-120:])]
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    rows = []
    for name, kinds in data.items():
        for kind, v in sorted(kinds.items()):
            rows.append((f"spmd_bytes/cpu_hlo/{name}/{kind}", 0.0, int(v)))
    tot_tam = sum(data["tam"].values())
    tot_2ph = sum(data["twophase"].values())
    rows.append(("spmd_bytes/cpu_hlo/tam_over_twophase_total", 0.0,
                 round(tot_tam / max(tot_2ph, 1), 3)))
    return rows
