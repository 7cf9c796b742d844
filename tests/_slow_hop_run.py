"""Both collective writes, and the planned read of each file, on small
interleaved request lists (run as a script, one process per device
count and case):

    python tests/_slow_hop_run.py <1|4> [case]

On 1 device the mesh is (1, 1, 1); on 4 it is (2, 1, 2), two nodes of
two ranks, as on a v5e 2x2. The case (of :func:`cases`, by default
``slow_hop``) is written by ``make_twophase_write`` and
``make_tam_write`` at depths 1 and 2, and each file is read back by
``make_twophase_read`` at the same depth. Prints one JSON list, a row
per (method, depth): the stats, the requested elements, whether the
file equals ``write_reference``, and whether every rank read its
payload.

Importing this module only defines the cases (numpy); the tests compute
their expected counts from them.
"""
import json
import os
import sys

import numpy as np

from repro.io_patterns import e3sm_g_pattern

MESHES = {1: (1, 1, 1), 4: (2, 1, 2)}
REQS, UNIT, ROUNDS = 16, 5, 5   # the slow_hop case
RUN_SLOTS = 64                  # slots in one run of a node's requests


def e3sm(n_ranks: int, reqs: int, unit: int, merged: int = 1,
         interleave: int = 0, shift: int = 0):
    """Element offsets and lengths ``[n_ranks, reqs]`` of
    ``e3sm_g_pattern`` over ``interleave`` source ranks (default
    ``n_ranks * merged``; its byte units read as elements): rank ``p``
    holds the first ``reqs`` requests of the ``merged`` source ranks
    ``p * merged ...`` merged in file order, as a node's aggregator
    holds them, and the slots of source ranks that no rank holds are
    holes. ``shift`` moves every request right by that many elements."""
    src = e3sm_g_pattern(interleave or n_ranks * merged,
                         -(-reqs // merged), unit)
    offsets = np.stack([
        np.sort(np.concatenate([src[q][0] for q in
                                range(p * merged, (p + 1) * merged)]))[:reqs]
        for p in range(n_ranks)]) + shift
    return offsets.astype(np.int32), np.full(offsets.shape, unit, np.int32)


def cases(n_devices: int) -> dict:
    """``{name: (offsets, lengths, cb, file_len)}`` in elements.

    * ``slow_hop``: ``REQS`` requests of ``UNIT`` elements per rank,
      round-robin over the ranks, so requests straddle the ``ROUNDS``
      windows of each domain and are split.
    * ``runs_unit2``: E3SM-F-shaped, two-element requests in 64-slot
      runs with a hole of the same size after each rank's run; the last
      run partial.
    * ``runs_unit1``: one-element requests, as above.
    * ``straddle``: two-element requests moved one element right, with
      a window that is not a multiple of the run, so requests straddle
      window boundaries and are split.
    * ``window_at_cap``: one-element requests without holes, so a rank
      holds exactly ``cb`` live requests in some round: the stage-1
      request cap ``min(split.capacity, cb)``. On four ranks each rank
      holds half of every other window of both domains.
    """
    n_ranks, n_nodes = n_devices, MESHES[n_devices][0]
    file_len = REQS * n_ranks * UNIT
    o, ln = e3sm(n_ranks, REQS, UNIT)
    out = {"slow_hop": (o, ln, file_len // n_nodes // ROUNDS, file_len)}
    q = 2 * n_ranks * RUN_SLOTS      # holes: as many slots as the ranks'
    for name, unit, reqs, cb, shift in (
            ("runs_unit2", 2, 4 * RUN_SLOTS + 20, 512, 0),
            ("runs_unit1", 1, 4 * RUN_SLOTS + 20, 256, 0),
            ("straddle", 2, 4 * RUN_SLOTS + 20, 192, 1)):
        o, ln = e3sm(n_ranks, reqs, unit, RUN_SLOTS, q, shift)
        unit_len = n_nodes * cb
        file_len = -(-int((o + ln).max()) // unit_len) * unit_len
        out[name] = (o, ln, cb, file_len)
    cb = 128
    if n_ranks == 1:
        o, ln = e3sm(1, 3 * cb, 1, 3 * cb)
        out["window_at_cap"] = (o, ln, cb, 4 * cb)
    else:
        # rows of 2 cb: ranks 0, 1 fill the even windows, 2, 3 the odd;
        # 4 rows make 4 rounds per domain, so in every even round ranks
        # 0 and 1 hold half of window t in both domains: cb requests
        o, ln = e3sm(4, 4 * cb // 2, 1, cb // 2, 2 * cb)
        out["window_at_cap"] = (o, ln, cb, 8 * cb)
    return out


def main():
    n_devices = int(sys.argv[1])
    case = sys.argv[2] if len(sys.argv) > 2 else "slow_hop"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    import jax

    from repro.core import IOConfig, contiguous_layout
    from repro.core.tam import make_tam_write
    from repro.core.twophase import (make_twophase_read,
                                     make_twophase_write, write_reference)
    from repro.launch.mesh import make_io_mesh

    mesh = make_io_mesh(*MESHES[n_devices])
    offsets, lengths, cb, file_len = cases(n_devices)[case]
    n_ranks, reqs = offsets.shape
    layout = contiguous_layout(file_len, mesh.shape["node"])
    counts = np.full((n_ranks,), reqs, np.int32)
    data_cap = int(lengths.sum(axis=1).max())
    data = (np.arange(n_ranks * data_cap, dtype=np.int32)
            .reshape(n_ranks, -1) % 251 + 1)
    ref = write_reference(layout, offsets, lengths, counts, data)
    rows = []
    for depth in (1, 2):
        cfg = IOConfig(req_cap=reqs, data_cap=data_cap, cb_buffer_size=cb,
                       pipeline=depth > 1, pipeline_depth=depth)
        read = jax.jit(make_twophase_read(mesh, layout, cfg))
        for method, make in (("twophase", make_twophase_write),
                             ("tam", make_tam_write)):
            file, stats = jax.jit(make(mesh, layout, cfg))(
                offsets, lengths, counts, data)
            got = np.asarray(read(offsets, lengths, counts, file))
            rows.append({
                "method": method, "depth": depth,
                "stats": {k: np.asarray(v).tolist()
                          for k, v in stats.items()},
                "requested_elems": int(lengths.sum()),
                "identical": bool(np.array_equal(
                    np.asarray(file).reshape(-1), ref)),
                "read_exact": bool(np.array_equal(got, data))})
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
