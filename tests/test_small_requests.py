"""Both collective writes and the planned read in the E3SM-F regime:
requests of one or two elements in 64-slot runs with holes, many
requests per window, and a window whose live requests reach the stage-1
cap ``min(split.capacity, cb)``.

The device runs are made by ``tests/_slow_hop_run.py``, one
subprocess per case and device count (1 device, and 4 virtual devices
as the (2, 1, 2) mesh, two nodes of two ranks). Each file must equal
``write_reference``, each read must return every rank's payload,
nothing may be dropped, and the requests counted before and after
TAM's coalescing and at each global aggregator must equal the counts
taken here on the host from the same lists.
"""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import _slow_hop_run as sr

RUN = Path(sr.__file__)
CASES = ("runs_unit2", "runs_unit1", "straddle", "window_at_cap")


@pytest.fixture(scope="module")
def rows(spmd_env):
    """``rows(n_devices, case)``: the script's rows; every (device
    count, case) runs in a process of its own, all started at once."""
    procs = {(n, c): subprocess.Popen(
        [sys.executable, str(RUN), str(n), c], env=spmd_env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for n in sr.MESHES for c in CASES}
    runs: dict = {}

    def get(n_devices, case):
        key = (n_devices, case)
        if key not in runs:
            out, err = procs[key].communicate(timeout=300)
            assert procs[key].returncode == 0, err[-3000:]
            runs[key] = json.loads(out.strip().splitlines()[-1])
        return runs[key]

    yield get
    for p in procs.values():
        if p.poll() is None:
            p.kill()
            p.communicate()


def split_pieces(offsets, lengths, cb):
    """Every request split at the ``cb`` window boundaries (at most one
    here): ``(rank, offset, length)`` of each piece."""
    rank = np.broadcast_to(np.arange(offsets.shape[0])[:, None],
                           offsets.shape).reshape(-1)
    o, ln = offsets.reshape(-1).astype(np.int64), lengths.reshape(-1)
    b = (o // cb + 1) * cb
    cut = o + ln > b
    assert not np.any(o + ln > b + cb), "a request spans three windows"
    return (np.concatenate([rank, rank[cut]]),
            np.concatenate([o, b[cut]]),
            np.concatenate([np.where(cut, b - o, ln), (o + ln - b)[cut]]))


def host_counts(offsets, lengths, cb, file_len, mesh):
    """What the writes must count, from the lists alone: requests into
    TAM's coalescing (the split pieces), the coalesced runs out of it
    (per local aggregator and round, re-split at domain boundaries),
    each domain's requests at its global aggregator under either write,
    and the most live pieces one rank holds in one round."""
    n_nodes, _, n_lmem = mesh
    dl = file_len // n_nodes
    rank, off, ln = split_pieces(offsets, lengths, cb)
    win, group = (off % dl) // cb, rank // n_lmem
    per_rank_round = np.zeros((offsets.shape[0], dl // cb), int)
    np.add.at(per_rank_round, (rank, win), 1)
    order = np.lexsort((off, win, group))
    g, w, o = group[order], win[order], off[order]
    end = o + ln[order]
    new = np.ones(len(o), bool)
    new[1:] = (g[1:] != g[:-1]) | (w[1:] != w[:-1]) | (o[1:] != end[:-1])
    starts = np.flatnonzero(new)
    run_lo = o[starts]
    run_hi = np.maximum.reduceat(end, starts)
    ga_tam = np.zeros(n_nodes, int)
    for lo, hi in zip(run_lo, run_hi):
        for d in range(lo // dl, (hi - 1) // dl + 1):
            ga_tam[d] += 1
    return {"before": len(off), "after": int(ga_tam.sum()),
            "at_ga": {"tam": ga_tam.tolist(),
                      "twophase": np.bincount(off // dl,
                                              minlength=n_nodes).tolist()},
            "most_live": int(per_rank_round.max())}


def test_host_counts_of_the_f_shape():
    """The host count on one node's runs: 64 two-element requests to a
    run, two runs to a window, nothing split."""
    o, ln, cb, file_len = sr.cases(1)["runs_unit2"]
    c = host_counts(o, ln, cb, file_len, sr.MESHES[1])
    assert c["before"] == o.size == 276
    assert c["after"] == -(-276 // sr.RUN_SLOTS) == 5
    assert c["most_live"] == 2 * sr.RUN_SLOTS


@pytest.mark.parametrize("n_devices", sorted(sr.MESHES))
def test_window_at_cap_reaches_the_stage1_cap(n_devices):
    o, ln, cb, file_len = sr.cases(n_devices)["window_at_cap"]
    c = host_counts(o, ln, cb, file_len, sr.MESHES[n_devices])
    assert c["most_live"] == cb


@pytest.mark.parametrize("n_devices", sorted(sr.MESHES))
def test_straddle_splits_requests(n_devices):
    o, ln, cb, file_len = sr.cases(n_devices)["straddle"]
    c = host_counts(o, ln, cb, file_len, sr.MESHES[n_devices])
    assert c["before"] > o.size


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("method", ["twophase", "tam"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("n_devices", sorted(sr.MESHES))
def test_small_requests_write_and_read(rows, n_devices, case, method,
                                       depth):
    (row,) = [r for r in rows(n_devices, case)
              if (r["method"], r["depth"]) == (method, depth)]
    st = row["stats"]
    assert row["identical"] and row["read_exact"]
    assert st["dropped_requests"] == st["dropped_elems"] == 0
    want = host_counts(*sr.cases(n_devices)[case], sr.MESHES[n_devices])
    assert st["requests_at_ga"] == want["at_ga"][method]
    if method == "tam":
        assert st["requests_before_coalesce"] == want["before"]
        assert st["requests_after_coalesce"] == want["after"]
