"""Per-stage device time and the slow-hop counters (``stages.py``): the
stage rules on hand-made program text, the reduction on a trace recorded
on one v5e chip together with the text of the programs that made it
(``data/tiny_node.*.gz``: one TAM write and one read of the rehearsal's
tiny node cell, made with ``stages.py --record``; the trace without its
``/host:metadata`` plane, the programs' serialized HLO that the texts
stand for), and the counters on the CPU at the rehearsal's tiny node
size."""
import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import stages
import tracefile as tf

TESTS = Path(__file__).resolve().parent
DATA = TESTS / "data"
ROOT = TESTS.parents[2]

HLO = """\
HloModule jit_w, entry_computation_layout={(s32[8]{0})->s32[8]{0}}

%fused_computation.1 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.3 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(w)/while/body/io.select/jit(compact)/add"}
}

%body.2 (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  %p = (s32[], s32[8]{0}) parameter(0)
  %gte.1 = s32[8]{0} get-tuple-element(%p), index=1
  %reduce-window.4 = s32[8]{0} reduce-window(%gte.1, %c), window={size=8 pad=7_0}, to_apply=%region
  %fusion.5 = s32[8]{0} fusion(%reduce-window.4), kind=kLoop, calls=%fused_computation.1
  %copy.6 = s32[8]{0} copy(%fusion.5)
  %all-to-all.7 = s32[8]{0} all-to-all(%copy.6), dimensions={0}, metadata={op_name="jit(w)/while/body/io.exchange/all_to_all"}
  %fusion.8 = s32[8]{0} fusion(%all-to-all.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(w)/while/body/io.drain/io.merge/pmax"}
  ROOT %tuple.9 = (s32[], s32[8]{0}) tuple(%gte.1, %fusion.8)
}

ENTRY %main.10 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %while.11 = (s32[], s32[8]{0}) while(%t), condition=%cond, body=%body.2, metadata={op_name="jit(w)/while"}
  %constant.12 = s32[] constant(0)
  ROOT %gte.13 = s32[8]{0} get-tuple-element(%while.11), index=1
}
"""


def test_scope_stage_is_innermost():
    assert stages.scope_stage("jit(w)/while/body/io.select/add") == "select"
    assert stages.scope_stage("jit(w)/io.drain/x/io.merge/pmax") == "merge"
    assert stages.scope_stage("reduce_window_sum") is None


def test_stage_rules_on_program_text():
    rules = stages.stage_rules(HLO)
    assert rules["add.3"] == ("select", "scope")
    assert rules["all-to-all.7"] == ("exchange", "scope")
    assert rules["fusion.8"] == ("merge", "scope")
    # a fusion with no op_name takes its fused computation's stage
    assert rules["fusion.5"] == ("select", "fused")
    # the compiler's scan and copy, with no scope, take their nearest
    # user's stage
    assert rules["reduce-window.4"] == ("select", "dataflow")
    assert rules["copy.6"] == ("exchange", "dataflow")
    # the loop itself stays other: its self time is its own
    assert rules["while.11"] == ("other", "other")
    assert rules["constant.12"] == ("other", "other")
    assert stages.stage_map(HLO)["fusion.5"] == "select"
    assert stages.instruction("%fusion.5 = s32[8] fusion(s32[8] %x)") == \
        "fusion.5"


def _trace():
    t = tf.Trace()
    t.ops = {"/device:TPU:0": [(10, 100, "%while.11 = (s32[]) while()"),
                               (12, 30, "%fusion.5 = s32[8] fusion()"),
                               (30, 34, "%copy.6 = s32[8] copy()"),
                               (40, 60, "%all-to-all.7 = s32[8] all-to-all()"),
                               (60, 90, "%fusion.8 = s32[8] fusion()"),
                               (120, 130, "%fusion.5 = s32[8] fusion()")]}
    t.modules = {"/device:TPU:0": [(10, 100, "jit_w"), (110, 140, "jit_w")]}
    t.spans = [(0, 150, "window"), (8, 102, "write"), (108, 145, "write")]
    return t


def test_stage_time_and_idle_inside_programs():
    t = _trace()
    stage_of = stages.stage_map(HLO)
    windows = tf.call_windows(t, "write", kinds=("write",))
    per_dev = stages.stage_ns(t, windows, stage_of)
    assert per_dev == {"/device:TPU:0": {
        "other": 90 - 18 - 4 - 20 - 30, "select": 28, "exchange": 24,
        "merge": 30}}
    secs = stages.stage_seconds(t, windows, stage_of, "write")
    assert list(secs) == list(stages.WRITE_STAGES) + ["other"]
    busy = tf.busy_ns(t, windows)["/device:TPU:0"]
    assert sum(secs.values()) * 1e9 == pytest.approx(busy)
    gaps = stages.idle_in_program(t, {"write": windows},
                                  {"write": stage_of})
    assert [g[0] for g in gaps] == ["write:select", "write:end"]
    assert [g[1] for g in gaps] == pytest.approx([10e-9, 10e-9])
    (top,) = stages.top_ops(t, windows, stage_of, "other")
    assert top == ["%while.11 = (s32[]) while()", pytest.approx(18e-9)]
    t.ops["/device:TPU:0"].append((131, 132, "%fusion.99 = s32[] fusion()"))
    assert stages.stage_seconds(t, windows, stage_of, "write")[
        "unresolved"] == pytest.approx(1e-9)


def test_counter_shares():
    calls = [{"kind": "write", "stats": {
        "slow_hop_live_elems": 30, "slow_hop_shipped_elems": 40,
        "requests_before_coalesce": 10, "requests_after_coalesce": 1}},
        {"kind": "read"},
        {"kind": "write", "stats": {
            "slow_hop_live_elems": 30, "slow_hop_shipped_elems": 80,
            "requests_before_coalesce": 10, "requests_after_coalesce": 3}}]
    assert stages.counter_shares(calls) == {"slow_hop_useful.write": 50.0,
                                            "coalesce_ratio.write": 20.0}
    # a program without the counters (an older one) gives nothing
    assert stages.counter_shares([{"kind": "write", "stats": {
        "dropped_elems": 0}}]) == {}
    assert stages.counter_shares([{"kind": "read"}]) == {}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    pb = tmp_path_factory.mktemp("trace") / "tiny_node.xplane.pb"
    pb.write_bytes(gzip.decompress(
        (DATA / "tiny_node.xplane.pb.gz").read_bytes()))
    hlo = {k: gzip.decompress((DATA / f"tiny_node.{k}.hlo.txt.gz")
                              .read_bytes()).decode()
           for k in ("write", "read")}
    return tf.load(pb), hlo


def test_recorded_ops_resolve_in_their_programs(recorded):
    trace, hlo = recorded
    for kind, text in hlo.items():
        defined = stages.stage_map(text)
        windows = tf.call_windows(trace, kind)
        for dev, evs in trace.ops.items():
            inside = stages._inside(evs, windows[dev])
            assert inside, kind
            missing = {stages.instruction(n) for _, _, n in inside} \
                - set(defined)
            assert not missing, (kind, sorted(missing)[:5])


def test_recorded_stages_add_up_to_busy_time(recorded):
    trace, hlo = recorded
    out = stages.report(trace, hlo)
    for kind, secs in out["stages"].items():
        assert list(secs) == list(stages.STAGES[kind]) + ["other"]
        assert sum(secs.values()) == pytest.approx(out["busy_s"][kind],
                                                   rel=0.01)
        # every stage of the program ran, and the scopes cover most of it
        assert all(v > 0 for s, v in secs.items() if s != "other"), secs
        assert secs["other"] <= 0.1 * out["busy_s"][kind], secs
        # the op's own scope decides most of the time
        rules = out["by_rule"][kind]
        assert sum(rules.values()) == pytest.approx(sum(secs.values()))
        assert rules["scope"] >= 0.8 * out["busy_s"][kind], rules
    labels = {g[0].split(":")[0] for g in out["idle_in_program"]}
    assert labels <= {"write", "read"}


def test_counters_on_the_cpu_rehearsal(tmp_path):
    cfg = json.loads((TESTS / "rehearsal" / "configs" /
                      "e3sm_g_tiny_node.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/stages.py", "--workload",
         "e3sm_g_tiny_node.tam_cycle", "--seed", str(2**31 + 29),
         "--table", str(TESTS / "rehearsal" / "BENCHMARK.json"),
         "--allow-cpu"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    elems = cfg["request_bytes"] // 4
    cb = cfg["cb_buffer_bytes"] // 4
    rounds = cfg["file_bytes"] // 4 // cb
    runs = cfg["rank_requests"] // cfg["merged_ranks"]
    assert len(r["stats"]) == 2
    for st in r["stats"]:
        assert st["slow_hop_live_elems"] == cfg["rank_requests"] * elems
        assert st["slow_hop_shipped_elems"] == rounds * cb
        assert st["requests_before_coalesce"] == cfg["rank_requests"]
        assert st["requests_after_coalesce"] == runs
    assert r["counters"] == {
        "slow_hop_useful.write":
            100.0 * cfg["rank_requests"] * elems / (rounds * cb),
        "coalesce_ratio.write": 100.0 * runs / cfg["rank_requests"]}
    assert "stages" not in r       # the CPU trace has no device plane
