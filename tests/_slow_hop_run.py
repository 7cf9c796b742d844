"""Both collective writes on a small interleaved pattern, printing their
slow-hop counters (run as a script, one process per device count):

    python tests/_slow_hop_run.py <1|4>

On 1 device the mesh is (1, 1, 1); on 4 it is (2, 1, 2), two nodes of
two ranks, as on a v5e 2x2. Each rank writes ``REQS`` requests of
``UNIT`` elements, round-robin over the ranks, so requests straddle
the 5 windows of each domain and are split. Prints one JSON list, a
row per (method, depth): the stats, the requested elements, and
whether the file equals ``write_reference``.
"""
import json
import os
import sys

N_DEVICES = int(sys.argv[1])
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEVICES}")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import IOConfig, contiguous_layout  # noqa: E402
from repro.core.tam import make_tam_write  # noqa: E402
from repro.core.twophase import (make_twophase_write,  # noqa: E402
                                 write_reference)
from repro.launch.mesh import make_io_mesh  # noqa: E402

MESHES = {1: (1, 1, 1), 4: (2, 1, 2)}
REQS, UNIT, ROUNDS = 16, 5, 5


def main():
    mesh = make_io_mesh(*MESHES[N_DEVICES])
    n_ranks, n_nodes = mesh.size, mesh.shape["node"]
    file_len = REQS * n_ranks * UNIT
    layout = contiguous_layout(file_len, n_nodes)
    cb = file_len // n_nodes // ROUNDS
    slot = np.arange(REQS)[None, :] * n_ranks + np.arange(n_ranks)[:, None]
    offsets = (slot * UNIT).astype(np.int32)
    lengths = np.full_like(offsets, UNIT)
    counts = np.full((n_ranks,), REQS, np.int32)
    data = (np.arange(n_ranks * REQS * UNIT, dtype=np.int32)
            .reshape(n_ranks, -1) % 251 + 1)
    ref = write_reference(layout, offsets, lengths, counts, data)
    rows = []
    for method, make in (("twophase", make_twophase_write),
                         ("tam", make_tam_write)):
        for depth in (1, 2):
            cfg = IOConfig(req_cap=REQS, data_cap=REQS * UNIT,
                           cb_buffer_size=cb, pipeline=depth > 1,
                           pipeline_depth=depth)
            file, stats = jax.jit(make(mesh, layout, cfg))(
                offsets, lengths, counts, data)
            rows.append({
                "method": method, "depth": depth,
                "stats": {k: np.asarray(v).tolist()
                          for k, v in stats.items()},
                "requested_elems": int(lengths.sum()),
                "identical": bool(np.array_equal(
                    np.asarray(file).reshape(-1), ref))})
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
