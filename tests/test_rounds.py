"""Round-based bounded-buffer exchange engine: scheduler math, peak
buffering, host-path round timing, cost-model wiring, and the SPMD
byte-identity property (subprocess with 8 virtual devices) — including
the pipelined (double-buffered) round loop and the domain-spanning
request patterns — and the slow-hop counters of both writes on 1 and
4 virtual devices. The pipelined overlap accounting and the optimal_cb
autotuner live in tests/test_pipeline_model.py."""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.checkpoint.host_io import HostCollectiveIO
from repro.core.cost_model import (Workload, e3sm_g, rounds_for_cb,
                                   twophase_cost, with_measured_rounds)
from repro.core.domains import FileLayout, contiguous_layout
from repro.core.rounds import RoundScheduler, peak_aggregator_buffer_elems
from repro.io_patterns import btio_pattern, e3sm_g_pattern


# ---------------------------------------------------------------------------
# scheduler math
# ---------------------------------------------------------------------------

def test_scheduler_partition():
    s = RoundScheduler(contiguous_layout(320, 2), 2, 32)
    assert s.domain_len == 160 and s.cb == 32 and s.n_rounds == 5
    # None == single shot: one round covering the whole domain
    s1 = RoundScheduler(contiguous_layout(320, 2), 2, None)
    assert s1.n_rounds == 1 and s1.cb == 160


def test_scheduler_window_of():
    s = RoundScheduler(contiguous_layout(320, 2), 2, 40)
    offs = np.array([0, 39, 40, 159, 160, 199, 319])
    # windows are domain-local: offset 160 starts domain 1's window 0
    assert list(np.asarray(s.window_of(offs))) == [0, 0, 1, 3, 0, 0, 3]


def test_scheduler_validation():
    with pytest.raises(ValueError):
        RoundScheduler(contiguous_layout(320, 2), 2, 33)   # 160 % 33 != 0
    with pytest.raises(ValueError):
        RoundScheduler(contiguous_layout(321, 2), 2, 32)   # uneven domains
    with pytest.raises(ValueError):
        # windows must align with stripes
        RoundScheduler(FileLayout(stripe_size=24, stripe_count=2,
                                  file_len=320), 2, 40)


def test_scheduler_max_spans_bounds_split():
    s = RoundScheduler(contiguous_layout(320, 2), 2, 32)
    # a request of length <= data_cap can straddle at most this many windows
    assert s.max_spans(64) == 4
    assert s.max_spans(16) == 2


# ---------------------------------------------------------------------------
# acceptance criterion: aggregator buffering independent of rank count
# ---------------------------------------------------------------------------

def test_peak_buffer_independent_of_rank_count():
    peaks = [peak_aggregator_buffer_elems(
        data_cap=4096, n_nodes=8, ranks_per_node=rpn,
        domain_len=1 << 20, cb_buffer_size=8192)
        for rpn in (1, 16, 256)]
    rounds = {p["rounds"] for p in peaks}
    single = [p["single_shot"] for p in peaks]
    assert len(rounds) == 1              # O(cb): flat in rank count
    assert single[0] < single[1] < single[2]   # O(P * data_cap): grows
    assert peaks[-1]["rounds"] < peaks[-1]["single_shot"]


# ---------------------------------------------------------------------------
# host-level round timing (literal reproduction)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pattern,method", [
    ("e3sm", "tam"), ("e3sm", "twophase"),
    ("btio", "tam"), ("btio", "twophase"),
])
def test_host_rounds_byte_identical(pattern, method, tmp_path):
    P = 16
    reqs = (e3sm_g_pattern(P) if pattern == "e3sm"
            else btio_pattern(P, n=32))
    io = HostCollectiveIO(n_ranks=P, n_nodes=4, stripe_size=1024,
                          stripe_count=3)
    la = 8 if method == "tam" else None
    t0 = io.write(reqs, str(tmp_path / "ss"), method=method,
                  local_aggregators=la)
    file_len = int(max(o[-1] + l[-1] for o, l, _ in reqs if o.size))
    ref = io.read_file(str(tmp_path / "ss"), file_len)
    assert t0.rounds_executed == 1
    prev_rounds = None
    for cb in (1024, 4096, 16384):
        t = io.write(reqs, str(tmp_path / f"cb{cb}"), method=method,
                     local_aggregators=la, cb_bytes=cb)
        assert np.array_equal(io.read_file(str(tmp_path / f"cb{cb}"),
                                           file_len), ref)
        assert t.rounds_executed >= 1
        if prev_rounds is not None:      # bigger buffer, fewer rounds
            assert t.rounds_executed <= prev_rounds
        prev_rounds = t.rounds_executed
        # rounds serialize the exchange: latency >= the single shot's
        assert t.inter_comm >= t0.inter_comm * 0.99
        # per-round incast at one GA never exceeds the all-at-once storm
        assert t.messages_at_ga <= t0.messages_at_ga


def test_host_rounds_requires_stripe_alignment(tmp_path):
    io = HostCollectiveIO(n_ranks=4, n_nodes=2, stripe_size=1024,
                          stripe_count=2)
    with pytest.raises(ValueError):
        io.write(e3sm_g_pattern(4), str(tmp_path / "x"),
                 method="twophase", cb_bytes=1000)


# ---------------------------------------------------------------------------
# cost-model wiring
# ---------------------------------------------------------------------------

def test_rounds_override_replaces_assumption():
    w = e3sm_g(4096, 64)
    assert w.rounds == w.total_bytes / (w.stripe_size * w.P_G)
    w2 = with_measured_rounds(w, 7)
    assert w2.rounds == 7.0
    # more rounds -> more incast latency paid, total strictly grows
    lo = twophase_cost(with_measured_rounds(w, 1)).total
    hi = twophase_cost(with_measured_rounds(w, 64)).total
    assert hi > lo


def test_rounds_for_cb():
    w = Workload(P=64, nodes=8, P_G=4, k=8, total_bytes=1 << 20)
    assert rounds_for_cb(w, 1 << 18) == 1    # 256 KiB domains fit
    assert rounds_for_cb(w, 1 << 16) == 4
    assert rounds_for_cb(w, 1 << 30) == 1    # never below one round


# ---------------------------------------------------------------------------
# SPMD byte-identity property (8 virtual devices, subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(480)
def test_rounds_spmd_checks(spmd_env):
    # timeout stays under the CI job's 10-minute cap so a hang surfaces
    # this test's captured output, not a generic runner cancellation
    proc = subprocess.run(
        [sys.executable, "-m", "repro.testing.rounds_checks"],
        env=spmd_env, capture_output=True, text=True, timeout=480)
    print(proc.stdout)
    if proc.returncode != 0:
        print(proc.stderr[-3000:])
    assert proc.returncode == 0, "FAIL lines:\n" + "\n".join(
        ln for ln in proc.stdout.splitlines() if ln.startswith("FAIL"))
    # the pipelined byte-identity, spanning-pattern, and depth-k ring
    # checks must have actually executed (guards against silent skips)
    assert "pipelined_vs_serial" in proc.stdout
    assert "spanning/" in proc.stdout
    assert "read_pipelined" in proc.stdout
    assert "depth3_rounds5_vs_ref" in proc.stdout
    assert "depth4_rounds1_vs_ref" in proc.stdout   # the depth clamp
    assert "tam/depth4_rounds5_vs_ref" in proc.stdout
    assert "read_depth4_rounds5" in proc.stdout
    # placement + cross-executor fuzz must have actually executed
    assert "placement_swap_rounds5_vs_ref" in proc.stdout
    assert "read_placement_swap_rounds5" in proc.stdout
    assert "fuzz3/twophase/pl1_rle_k2_vs_ref" in proc.stdout
    assert "fuzz3/host/swap_rle_k2_vs_spmd" in proc.stdout
    assert "fuzz3/host/tam_swap_rle_k2_vs_spmd" in proc.stdout


# ---------------------------------------------------------------------------
# slow-hop counters (subprocess per device count, tests/_slow_hop_run.py)
# ---------------------------------------------------------------------------

SLOW_HOP_RUN = Path(__file__).with_name("_slow_hop_run.py")


@pytest.fixture(scope="module")
def slow_hop_rows(spmd_env):
    """``rows(n_devices)``: the script's rows, one process per device
    count, run on first use."""
    runs: dict = {}

    def rows(n_devices):
        if n_devices not in runs:
            proc = subprocess.run(
                [sys.executable, str(SLOW_HOP_RUN), str(n_devices)],
                env=spmd_env, capture_output=True, text=True, timeout=300)
            assert proc.returncode == 0, proc.stderr[-3000:]
            runs[n_devices] = json.loads(
                proc.stdout.strip().splitlines()[-1])
        return runs[n_devices]

    return rows


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("method", ["twophase", "tam"])
@pytest.mark.parametrize("n_devices", [1, 4])
def test_slow_hop_counters(slow_hop_rows, n_devices, method, depth):
    (row,) = [r for r in slow_hop_rows(n_devices)
              if (r["method"], r["depth"]) == (method, depth)]
    st = row["stats"]
    assert row["identical"]
    assert st["dropped_requests"] == st["dropped_elems"] == 0
    live, shipped = st["slow_hop_live_elems"], st["slow_hop_shipped_elems"]
    # nothing dropped: every requested element crosses the slow hop once
    assert live == row["requested_elems"]
    assert live <= shipped
    # rounds x ranks x destination buckets x bucket elements, where a
    # bucket holds one window (cb = domain / 5) or the rank's payload
    # (TAM: the lmem group's gathered window payload), whichever is less
    ranks, nodes, lmem = {1: (1, 1, 1), 4: (4, 2, 2)}[n_devices]
    reqs, unit, rounds = 16, 5, 5
    cb = reqs * ranks * unit // nodes // rounds
    bucket = min(reqs * unit, cb)
    if method == "tam":
        bucket = min(lmem * bucket, cb)
    assert shipped == rounds * ranks * nodes * bucket
