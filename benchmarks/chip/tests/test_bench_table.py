"""``BENCHMARK.json`` holds to the benchmark's contract, and everything
it names is a file the harness finds by name."""
import json
import re
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
TABLE = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|head)"
                   r"|(_dim|_rank)$")


def test_top_level_keys():
    assert set(TABLE) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert TABLE["paths"] == ["benchmarks/chip"]
    assert TABLE["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= TABLE["run_seconds"] <= 51
    assert len(json.dumps(TABLE)) < 64 * 1024


def test_configs():
    for c in TABLE["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("benchmarks/chip/configs/")
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key) and key in conf and not WIDTH.search(key)
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    sources = [c["source"] for c in TABLE["configs"]]
    assert len(set(sources)) == len(sources)


def test_cells():
    configs = {c["name"] for c in TABLE["configs"]}
    seen = set()
    for w in TABLE["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert (CHIP / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
    assert {w["config"] for w in TABLE["workloads"]} == configs
    fours = sum(w["chips"] == 4 for w in TABLE["workloads"])
    assert fours <= max(1, len(TABLE["workloads"]) // 2)


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader(kind):
    for m in TABLE[kind]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_enough():
    e2e = {m["name"]: m for m in TABLE["end_to_end"]}
    assert "setup_s" in e2e
    for w in TABLE["workloads"]:
        def on(m):
            return w["name"] in m.get("workloads", [w["name"]])
        assert on(e2e["setup_s"])
        assert sum(on(m) for m in e2e.values()) >= 2
        assert any(on(m) for m in TABLE["per_layer"])
    for m in TABLE["per_layer"]:
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        for cell in m.get("workloads", []):
            assert any(cell in x.get("workloads", [cell])
                       for x in [e2e[m["moves"]]])
