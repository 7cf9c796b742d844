"""The planned collective read on both of its scatter paths, printing
one row per case (run as a script, with 8 virtual devices):

    python tests/_read_slices_run.py

Each case reads the ``write_reference`` file of a pattern of
``repro.testing.rounds_checks`` twice on the (2, 2, 2) mesh, through
one compiled program: once as given (every rank's list sorted and
disjoint: the sliced path, where ``read_slices_pay``), and once with
one rank's first request cut in two halves that share one element,
which keeps that list sorted but makes its own requests overlap, so
every rank takes the full pass. A row holds the ``io.scatter`` paths
the compiled program holds, each run's per-rank ``read_window_runs``
predicate, whether every rank got its payload (zeros behind it),
whether the two runs returned the same bytes, and whether some
window's slice start was clamped at ``data_cap - min(cb, data_cap)``.
"""
import json
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import IOConfig, contiguous_layout  # noqa: E402
from repro.core.coalesce import request_starts  # noqa: E402
from repro.core.requests import RequestList, mask_invalid  # noqa: E402
from repro.core.rounds import (RoundScheduler, read_slices_pay,  # noqa: E402
                               read_window_runs)
from repro.core.twophase import (make_twophase_read,  # noqa: E402
                                 write_reference)
from repro.launch.mesh import make_io_mesh  # noqa: E402
from repro.testing import rounds_checks as rc  # noqa: E402

SWAP = (1, 0)


def _empty_rank(rng):
    """The mixed pattern with rank 5's list emptied (count 0)."""
    O, L, C, D = rc.mixed_pattern(rng)
    O[5], L[5], C[5], D[5] = 2**31 - 1, 0, 0, 0
    return O, L, C, D


# case: (pattern, cb, depth, data_cap, extra IOConfig fields). With
# two aggregators the slices pay where 2 * min(cb, data_cap) < data_cap;
# the last two cases build the full pass alone.
CASES = {
    "strided_depth1": (rc.strided_pattern, 16, 1, 64, {}),
    "strided_depth2": (rc.strided_pattern, 16, 2, 64, {}),
    "mixed_depth4": (rc.mixed_pattern, 16, 4, 64, {}),
    "spanning_depth2": (rc.spanning_pattern, 16, 2, 64, {}),
    "random_depth2": (rc.random_pattern, 20, 2, 64, {}),
    "swapped_placement": (rc.mixed_pattern, 16, 2, 64,
                          {"placement": SWAP}),
    "rle_codec": (rc.spanning_pattern, 16, 2, 64,
                  {"slow_hop_codec": "rle"}),
    "clamped_last_slice": (rc.strided_pattern, 16, 2, 40, {}),
    "empty_rank": (_empty_rank, 20, 2, 64, {}),
    "data_cap_below_cb": (rc.mixed_pattern, 160, 1, 64, {}),
    "one_window_per_domain": (rc.strided_pattern, 32, 2, 64, {}),
}


def overlapping_twin(O, L, C, D):
    """The same bytes, with the busiest rank's first request cut in two
    halves that share one element, so the rank's own requests overlap;
    returns the twin and the rank's payload map (twin = payload[perm])."""
    q = int(np.argmax(L.sum(axis=1)))
    n, (o, ln) = int(C[q]), (int(O[q, 0]), int(L[q, 0]))
    h = ln // 2
    O2, L2, D2 = O.copy(), L.copy(), D.copy()
    O2[q, :n + 1] = np.concatenate([[o, o + h], O[q, 1:n]])
    L2[q, :n + 1] = np.concatenate([[h + 1, ln - h], L[q, 1:n]])
    perm = np.concatenate([np.arange(h + 1), np.arange(h, D.shape[1] - 1)])
    D2[q] = D[q, perm]
    C2 = C.copy()
    C2[q] = n + 1
    return (O2, L2, C2, D2), q, perm


def widen(O, L):
    """One more (empty) slot in every request list."""
    return (np.pad(O, ((0, 0), (0, 1)), constant_values=2**31 - 1),
            np.pad(L, ((0, 0), (0, 1))))


def expected(L, C, D):
    out = np.zeros_like(D)
    for p in range(D.shape[0]):
        n = int(L[p, :C[p]].sum())
        out[p, :n] = D[p, :n]
    return out


def predicate(sched, O, L, C, data_cap):
    """Per rank: ``read_window_runs``'s predicate, and whether a window
    with live elements starts past ``data_cap - min(cb, data_cap)``."""
    span = min(sched.cb, data_cap)
    preds, clamped = [], False
    for p in range(O.shape[0]):
        r = mask_invalid(RequestList(jnp.asarray(O[p]), jnp.asarray(L[p]),
                                     jnp.int32(C[p])))
        runs = read_window_runs(r, request_starts(r), sched, data_cap)
        preds.append(bool(runs.sliceable))
        first = np.asarray(runs.first)
        clamped |= bool(np.any((first[1:] > first[:-1])
                               & (first[:-1] > data_cap - span)))
    return preds, clamped


def main():
    mesh = make_io_mesh(2, 2, 2)
    layout = contiguous_layout(rc.FILE_LEN, 2)
    rows = []
    for i, (name, (pattern, cb, depth, data_cap, extra)) in enumerate(
            CASES.items()):
        O, L, C, D = pattern(np.random.default_rng(i))
        file = jnp.asarray(write_reference(layout, O, L, C, D)).reshape(2, -1)
        O, L = widen(O, L)
        (O2, L2, C2, D2), q, perm = overlapping_twin(O, L, C, D)
        D, D2 = D[:, :data_cap], D2[:, :data_cap]
        cfg = IOConfig(req_cap=O.shape[1], data_cap=data_cap,
                       cb_buffer_size=cb, pipeline=depth > 1,
                       pipeline_depth=depth, **extra)
        read = jax.jit(make_twophase_read(mesh, layout, cfg)).lower(
            O, L, C, file).compile()
        text = read.as_text()
        sched = RoundScheduler(layout, 2, cb)
        got = np.asarray(read(O, L, C, file))
        got2 = np.asarray(read(O2, L2, C2, file))
        twin_of_got = got.copy()
        twin_of_got[q] = got[q, perm[:data_cap]]
        sliceable, clamped = predicate(sched, O, L, C, data_cap)
        sliceable2, _ = predicate(sched, O2, L2, C2, data_cap)
        rows.append({
            "case": name,
            "paths": [p for p in ("sliced", "full_pass")
                      if f"io.scatter/{p}/" in text],
            "slices_pay": read_slices_pay(sched, data_cap),
            "sliceable_sorted": sliceable,
            "sliceable_overlap": sliceable2,
            "payload_sorted": bool(np.array_equal(got, expected(L, C, D))),
            "payload_overlap": bool(np.array_equal(
                got2, expected(L2, C2, D2))),
            "same_bytes": bool(np.array_equal(got2, twin_of_got)),
            "clamped": clamped,
        })
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
