"""The E3SM-F node configuration and the two cells that run one chip
beside the first benchmark's: ``e3sm_f_node.tam_cycle`` (TAM on one
node's E3SM-F share) and ``e3sm_g_node.twophase_cycle`` (the flat
two-phase write on the E3SM-G node share).

The configuration's numbers follow from the paper's Table I F case and
the stated cuts; both cells, at a tiny size on the CPU, run through the
harness with a table of their own and come out correct, and the control
(every rank's last request dropped) comes out not correct by exactly
that request's bytes in every call."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import harness
import yardstick as ys
from benchmarks.workloads import node_share, paper_workload

TESTS = Path(__file__).resolve().parent
CHIP = TESTS.parent
ROOT = CHIP.parents[1]
F = json.loads((CHIP / "configs" / "e3sm_f_node.json").read_text())


def test_f_share_matches_table_i():
    assert node_share("e3sm_f") == (F["rank_requests"],
                                    F["request_bytes"]) == (5312500, 8)
    w = paper_workload("e3sm_f")
    sd = F["source_deployment"]
    assert (sd["ranks"], sd["nodes"]) == (w.P, w.nodes) == (16384, 256)
    assert sd["requests"] == round(w.k * w.P) == 1.36e9
    assert sd["total_bytes"] == w.total_bytes == 14 * 2**30
    assert sd["mean_request_bytes"] == round(14 * 2**30 / 1.36e9, 2)
    assert F["request_bytes"] == int(sd["mean_request_bytes"]) // 4 * 4
    assert sd["ranks_per_node"] * sd["nodes"] == sd["ranks"]
    assert round(sd["requests"] / sd["nodes"]) == F["rank_requests"]


def test_f_runs_and_file_follow_from_the_pattern():
    e = ys.ELEM_BYTES
    node, lagg, lmem = F["mesh"]
    assert (F["nodes"], F["ranks_per_node"]) == (node, lagg * lmem) == (1, 1)
    offsets, lengths = ys.e3sm_g_requests(
        1, F["rank_requests"], F["request_bytes"], F["merged_ranks"],
        F["interleave_ranks"])
    ends = offsets[0] + lengths[0]
    runs = 1 + np.count_nonzero(offsets[0, 1:] != ends[:-1])
    assert runs == F["runs"] == -(-F["rank_requests"] // 64) == 83008
    cb = F["cb_buffer_bytes"] // e
    n_elems = ys.padded_file_elems(int(ends[-1]) // e, node, cb)
    assert F["file_bytes"] == n_elems * e == 6 * 16 * 2**20
    assert F["payload_bytes"] == int(lengths.sum()) == 42500000
    # every window of 16 MiB but the last holds 1,048,576 live requests
    # in 16,384 whole runs: windows and 1,024-byte periods align
    window = offsets[0] // F["cb_buffer_bytes"]
    per_window = np.bincount(window)
    assert per_window[:5].tolist() == [1048576] * 5
    assert int(per_window.sum()) == F["rank_requests"]
    assert F["cb_buffer_bytes"] % (F["interleave_ranks"]
                                   * F["request_bytes"]) == 0


def test_f_is_in_the_table_with_its_cuts():
    table = harness.load_table()
    (conf,) = [c for c in table["configs"] if c["name"] == "e3sm_f_node"]
    assert conf["file"] == "benchmarks/chip/configs/e3sm_f_node.json"
    assert conf["source"] == F["source"]
    assert sorted(conf["reduced"]) == sorted(F["reduced_how"])
    cells = {c["name"]: c for c in table["workloads"]}
    for name, config, traffic in (
            ("e3sm_f_node.tam_cycle", "e3sm_f_node", "tam_cycle"),
            ("e3sm_g_node.twophase_cycle", "e3sm_g_node", "twophase_cycle")):
        cell, _, tr = harness.resolve(table, name)
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            config, traffic, 1)
        assert tr["driver"] == "write_read"
        for m in table["per_layer"]:
            if m["name"] != "collective_ms.write":
                assert name in m["workloads"], m["name"]
    assert cells["e3sm_g_node.twophase_cycle"]["config"] == "e3sm_g_node"


# ---------------------------------------------------------------------------
# both cells at a tiny size on the CPU, through the harness
# ---------------------------------------------------------------------------

TINY_F = {"source": "CPU rehearsal of e3sm_f_node: the same pattern and "
                    "mesh at a tiny size",
          "pattern": "e3sm_g", "nodes": 1, "ranks_per_node": 1,
          "rank_requests": 600, "merged_ranks": 64, "interleave_ranks": 128,
          "request_bytes": 8, "cb_buffer_bytes": 2048, "mesh": [1, 1, 1],
          "pipeline_depth": 2, "element": "int32"}

RUNNER = """
import json, sys, time
sys.path[:0] = [{chip!r}, {src!r}]
import control, harness
table = json.load(open({table!r}))
rc = 0
for name in ("none", "control"):
    print("# wrap", name, flush=True)
    rc |= harness.main(["--workload", {cell!r}, "--seed", "{seed}",
                        "--seconds", "0.3", "--trace", "0"],
                       t_start=time.perf_counter(), table=table,
                       allow_cpu=True,
                       wrap=control.control if name == "control" else None)
sys.exit(rc)
"""


def _table(tmp_path) -> Path:
    """The real table's metrics over two tiny cells, every metric listed
    for both."""
    table = harness.load_table()
    f_file = tmp_path / "e3sm_f_tiny_node.json"
    f_file.write_text(json.dumps(TINY_F))
    g_file = TESTS / "rehearsal" / "configs" / "e3sm_g_tiny_node.json"
    table["configs"] = [
        {"name": "e3sm_f_tiny_node", "file": str(f_file)},
        {"name": "e3sm_g_tiny_node", "file": str(g_file)}]
    table["workloads"] = [
        {"name": "e3sm_f_tiny_node.tam_cycle", "config": "e3sm_f_tiny_node",
         "traffic": "tam_cycle", "chips": 1},
        {"name": "e3sm_g_tiny_node.twophase_cycle",
         "config": "e3sm_g_tiny_node", "traffic": "twophase_cycle",
         "chips": 1}]
    for m in table["end_to_end"] + table["per_layer"]:
        m.pop("workloads", None)
    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    return path


@pytest.mark.parametrize("cell,req_bytes", [
    ("e3sm_f_tiny_node.tam_cycle", 8),
    ("e3sm_g_tiny_node.twophase_cycle", 32)])
def test_tiny_cell_sound_and_control(cell, req_bytes, tmp_path):
    code = RUNNER.format(chip=str(CHIP), src=str(ROOT / "src"),
                         table=str(_table(tmp_path)), cell=cell,
                         seed=2**31 + 29)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    runs, name = {}, None
    for line in proc.stdout.splitlines():
        if line.startswith("# wrap "):
            name = line.split()[-1]
        elif line.startswith("{"):
            runs[name] = json.loads(line)
    sound, ctl = runs["none"], runs["control"]
    assert sound["correct"] and sound["failed"] == 0
    assert set(sound["metrics"]) == {"write_bw", "read_bw", "setup_s"}
    assert sound["checks"] == {"file_bytes_wrong": {"value": 0, "limit": 0},
                               "read_bytes_wrong": {"value": 0, "limit": 0}}
    assert not ctl["correct"]
    # one rank, one request dropped: its bytes wrong in every file and read
    writes = ctl["attempted"] // 2
    assert ctl["checks"]["file_bytes_wrong"]["value"] == req_bytes * writes
    assert ctl["checks"]["read_bytes_wrong"]["value"] == req_bytes * writes
