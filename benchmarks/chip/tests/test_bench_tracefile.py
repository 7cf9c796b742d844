"""The trace reduction, on hand-made events and on a small trace
recorded on one v5e chip (``data/v5e_probe.xplane.pb``, 37 KB: a jitted
``shard_map`` loop of an all-to-all over the one device, a gather and a
scatter, called twice under the benchmark's spans ``window`` >
``write`` > ``dispatch``/``wait``, then ``keep``, the host copy of the
result, with a 10 ms host sleep after each call)."""
from pathlib import Path

import pytest

import tracefile as tf

DATA = Path(__file__).resolve().parent / "data"


def test_merge_and_overlap():
    assert tf.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tf.overlap([(0, 3), (5, 8)], [(2, 6)]) == 2
    assert tf.overlap([], [(0, 1)]) == 0


def test_self_time_of_nested_ops():
    evs = [(0, 10, "while"), (1, 3, "gather"), (4, 9, "fusion"),
           (5, 6, "scatter"), (12, 14, "gather")]
    assert tf.self_ns(evs) == {"while": 3, "gather": 4, "fusion": 4,
                               "scatter": 1}


def _trace():
    t = tf.Trace()
    t.ops = {"/device:TPU:0": [(10, 40, "fusion.1"), (30, 50, "all-to-all.3"),
                               (70, 90, "all-reduce-start.1")],
             "/device:TPU:1": [(10, 90, "fusion.1")]}
    t.modules = {"/device:TPU:0": [(10, 55, "jit_w"), (70, 90, "jit_r")],
                 "/device:TPU:1": [(10, 50, "jit_w"), (55, 90, "jit_r")]}
    t.spans = [(0, 100, "window"), (5, 60, "write"), (5, 8, "dispatch"),
               (8, 60, "wait"), (60, 95, "keep"), (62, 94, "read")]
    return t


def test_call_windows_follow_program_executions():
    t = _trace()
    assert tf.call_windows(t, "read") == {"/device:TPU:0": [(70, 90)],
                                          "/device:TPU:1": [(55, 90)]}
    assert tf.busy_ns(t, tf.call_windows(t, "write")) == {
        "/device:TPU:0": 40, "/device:TPU:1": 40}
    t.modules["/device:TPU:1"].append((95, 99, "jit_x"))
    with pytest.raises(ValueError, match="ran 3 programs"):
        tf.call_windows(t, "write")
    del t.modules["/device:TPU:1"]
    with pytest.raises(ValueError, match="program executions"):
        tf.call_windows(t, "write")


def test_busy_collectives_and_gaps():
    t = _trace()
    assert t.span_intervals("write") == [(5, 60)]
    assert tf.busy_ns(t, [(5, 60)]) == {"/device:TPU:0": 40,
                                        "/device:TPU:1": 50}
    both = {"/device:TPU:0": [(0, 100)], "/device:TPU:1": [(0, 100)]}
    assert tf.collective_ns(t, both) == {"/device:TPU:0": 40,
                                         "/device:TPU:1": 0}
    assert tf.busy_ns(t, both) == {"/device:TPU:0": 60,
                                   "/device:TPU:1": 80}
    t.spans = t.spans[:-1]
    gaps = tf.idle_gaps(t, (0, 100))
    # each gap is labelled by the innermost span over its middle
    assert gaps == [["keep", 20e-9], ["dispatch", 10e-9], ["keep", 10e-9],
                    ["dispatch", 10e-9], ["keep", 10e-9]]
    assert tf.idle_gaps(t, (0, 120))[0] == ["none", 30e-9]


def test_collective_names():
    for name in ("all-to-all.2", "all-reduce-start", "all-gather-done.1",
                 "collective-permute.4", "%reduce-scatter.1"):
        assert tf.COLLECTIVE.match(name), name
    for name in ("fusion.12", "gather.3", "scatter-add", "while.1"):
        assert not tf.COLLECTIVE.match(name), name


@pytest.fixture(scope="module")
def probe():
    return tf.load(DATA / "v5e_probe.xplane.pb")


def test_recorded_trace_planes_and_spans(probe):
    assert list(probe.ops) == ["/device:TPU:0"]
    assert [n for _, _, n in probe.spans] == [
        "window"] + ["write", "dispatch", "wait", "keep"] * 2
    # op names are the HLO instruction without layouts
    names = {n for _, _, n in probe.ops["/device:TPU:0"]}
    assert any(n.startswith("%while.") for n in names)
    assert not any("{" in n or "calls=" in n for n in names)
    # the while loop's body ops nest inside it: its self time is small
    per_op = tf.self_ns(probe.ops["/device:TPU:0"])
    (loop,) = [n for n in per_op if n.startswith("%while.")]
    total = sum(e - s for s, e, n in probe.ops["/device:TPU:0"]
                if n == loop)
    assert 0 <= per_op[loop] < 0.5 * total


def test_recorded_trace_device_ops_lie_in_the_calls(probe):
    execs = tf.call_windows(probe, "write")["/device:TPU:0"]
    assert len(execs) == 2
    busy = tf.busy_ns(probe, {"/device:TPU:0": execs})["/device:TPU:0"]
    ops = tf.merge((s, e) for s, e, _ in probe.ops["/device:TPU:0"])
    # every op of the trace runs inside one of the two executions, which
    # the device keeps busy throughout
    assert busy == sum(e - s for s, e in ops)
    assert busy == pytest.approx(sum(e - s for s, e in execs), rel=1e-3)
    # the device's clock runs ahead of the host spans by about a ms
    skew = [lo - s for (lo, _), (s, _) in
            zip(probe.span_intervals("write"), execs)]
    assert all(0.5e6 < x < 2e6 for x in skew)
    (window,) = probe.span_intervals("window")
    gaps = tf.idle_gaps(probe, window)
    assert gaps and all(g[1] > 0 for g in gaps)
    # the 10 ms host sleep after the first call, covered by no span
    # but the window, is the longest gap
    assert gaps[0][0] == "none" and gaps[0][1] > 0.01
