"""The collective read's sliced scatter (``core/rounds.py``).

``read_window_runs`` decides, from one rank's request list, whether
each (domain, window) of the read fills one contiguous run of the
rank's output, and where: checked here against a numpy walk of the
output's file positions on the request patterns of
``repro.testing.rounds_checks``, and shown false for lists the sliced
path cannot take. ``read_slices_pay`` decides from the shapes alone
whether the sliced path is built at all. The read itself runs on 8
virtual devices in a subprocess (``tests/_read_slices_run.py``): on the
sliced path and on the full pass over the same bytes, each must return
every rank's payload exactly, and where the slices do not pay the
program holds the full pass alone.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coalesce import request_starts
from repro.core.domains import contiguous_layout
from repro.core.requests import RequestList, mask_invalid
from repro.core.rounds import (RoundScheduler, read_slices_pay,
                               read_window_runs)

_xla_flags = os.environ.get("XLA_FLAGS")
from repro.testing import rounds_checks as rc  # noqa: E402

# the checks module sets a device count for its own process; the test
# process keeps its own
if _xla_flags is None:
    os.environ.pop("XLA_FLAGS", None)
else:
    os.environ["XLA_FLAGS"] = _xla_flags

SCHED = RoundScheduler(contiguous_layout(rc.FILE_LEN, 2), 2, 32)


def _runs(offsets, lengths, count, data_cap=rc.DATA_CAP, sched=SCHED):
    r = mask_invalid(RequestList(jnp.asarray(offsets, jnp.int32),
                                 jnp.asarray(lengths, jnp.int32),
                                 jnp.int32(count)))
    return read_window_runs(r, request_starts(r), sched, data_cap)


def _numpy_first(offsets, lengths, count, data_cap, sched):
    """``first`` from the file position of every output element, walked
    in numpy."""
    fpos = np.concatenate(
        [np.arange(o, o + ln) for o, ln in zip(offsets[:count],
                                               lengths[:count])]
        + [np.zeros(0, np.int64)])[:data_cap]
    n_windows = sched.n_aggregators * sched.n_rounds
    return np.searchsorted(fpos // sched.cb, np.arange(n_windows + 1))


PATTERNS = {"strided": rc.strided_pattern, "mixed": rc.mixed_pattern,
            "spanning": rc.spanning_pattern,
            **{f"random{s}": rc.random_pattern for s in range(4)}}


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_sorted_patterns_are_sliceable(name):
    seed = int(name[6:]) if name.startswith("random") else 0
    O, L, C, _ = PATTERNS[name](np.random.default_rng(seed))
    for p in range(rc.P_RANKS):
        runs = _runs(O[p], L[p], C[p])
        assert bool(runs.sliceable), p
        first = _numpy_first(O[p], L[p], int(C[p]), rc.DATA_CAP, SCHED)
        np.testing.assert_array_equal(np.asarray(runs.first), first)
        # a window's elements are distinct positions: one slice holds them
        assert int(np.diff(first).max(initial=0)) <= min(SCHED.cb,
                                                         rc.DATA_CAP)


def _unsliceable(kind):
    O, L, C, _ = rc.strided_pattern(np.random.default_rng(0))
    o, ln = O[0].copy(), L[0].copy()
    if kind == "shuffled":
        perm = np.random.default_rng(1).permutation(len(o))
        return o[perm], ln[perm], C[0]
    if kind == "own_overlap":       # the second request reaches into the third
        ln[1] = o[2] - o[1] + 1
        return o, ln, C[0]
    if kind == "past_file_end":
        o[-1] = rc.FILE_LEN - 2
        return o, ln, C[0]
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["shuffled", "own_overlap",
                                  "past_file_end"])
def test_unsliceable_lists(kind):
    assert not bool(_runs(*_unsliceable(kind)).sliceable)


@pytest.mark.parametrize("data_cap", [24, 40, 64])
def test_runs_clip_at_data_cap(data_cap):
    """A payload longer than the output: windows past ``data_cap`` hold
    nothing, and a run that reaches it stops there."""
    O, L, C, _ = rc.strided_pattern(np.random.default_rng(0))
    runs = _runs(O[0], L[0], C[0], data_cap=data_cap)
    first = _numpy_first(O[0], L[0], int(C[0]), data_cap, SCHED)
    np.testing.assert_array_equal(np.asarray(runs.first), first)
    assert int(first[-1] - first[0]) == min(int(L[0].sum()), data_cap)


def test_empty_rank_has_no_runs():
    runs = _runs(np.full(8, 2**31 - 1), np.zeros(8), 0)
    assert bool(runs.sliceable)
    assert not np.asarray(runs.first).any()


# (aggregators, cb, data_cap) -> whether n_dest slices of
# min(cb, data_cap) touch fewer elements than data_cap
PAY = {(1, 32, 64): True, (2, 16, 64): True, (2, 32, 64): False,
       (2, 160, 64): False, (1, 160, 64): False, (4, 8, 40): True}


@pytest.mark.parametrize("shape", sorted(PAY))
def test_slices_pay(shape):
    n_dest, cb, data_cap = shape
    sched = RoundScheduler(contiguous_layout(320 * n_dest, n_dest),
                           n_dest, cb)
    assert read_slices_pay(sched, data_cap) is PAY[shape]


# ---------------------------------------------------------------------------
# the read on both paths (subprocess, 8 virtual devices)
# ---------------------------------------------------------------------------

READ_RUN = Path(__file__).with_name("_read_slices_run.py")
SLICED = ("strided_depth1", "strided_depth2", "mixed_depth4",
          "spanning_depth2", "random_depth2", "swapped_placement",
          "rle_codec", "clamped_last_slice", "empty_rank")
# one window per domain and round, or less: the slices do not pay
FULL_ONLY = ("data_cap_below_cb", "one_window_per_domain")


@pytest.fixture(scope="module")
def read_rows(spmd_env):
    proc = subprocess.run([sys.executable, str(READ_RUN)], env=spmd_env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    return {row["case"]: row for row in rows}


@pytest.mark.parametrize("case", SLICED + FULL_ONLY)
def test_read_paths_byte_identical(read_rows, case):
    row = read_rows[case]
    if case in SLICED:
        assert row["slices_pay"]
        assert row["paths"] == ["sliced", "full_pass"]
        assert all(row["sliceable_sorted"])       # the sliced path ran
        assert not all(row["sliceable_overlap"])  # the full pass ran
    else:
        assert not row["slices_pay"]
        assert row["paths"] == ["full_pass"]      # built alone, no cond
    assert row["payload_sorted"]
    assert row["payload_overlap"]
    assert row["same_bytes"]
    if case == "clamped_last_slice":
        assert row["clamped"]
