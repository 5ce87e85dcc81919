"""``BENCHMARK.json`` keeps to the benchmark's contract, and every name
it gives is found as a file."""
import json
import re
from pathlib import Path

import pytest

import run

ROOT = run.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "cell": {"name", "config", "traffic", "chips", "why"},
    "e2e": {"name", "unit", "better", "bound", "source"},
    "layer": {"name", "unit", "better", "source", "layer", "moves"},
}
E2E = {m["name"] for m in SPEC["end_to_end"]}
CELLS = {w["name"] for w in SPEC["workloads"]}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_shape_and_limits():
    assert set(SPEC) == KEYS["top"]
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    n = len(SPEC["workloads"])
    # a full check of 24 cells, 14 runs each, fits in 12 hours
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 1 <= n <= 24
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs_are_files_and_used():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == KEYS["config"] and NAME.match(c["name"])
        assert c["name"] in used and _line(c["source"]) and _line(c["why"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert (run.BENCH / "nets" / f"{cfg['network']}.py").exists()


def test_cells():
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == KEYS["cell"] and NAME.match(w["name"])
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert _line(w["why"])
        mix = run.traffic.load(run.BENCH / "traffic" / f"{w['traffic']}.json")
        assert callable(mix["process"].window)
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(SPEC["workloads"]) == len(CELLS)


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric(m):
    per_layer = m in SPEC["per_layer"]
    assert set(m) - {"workloads"} == KEYS["layer" if per_layer else "e2e"]
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert set(m.get("workloads", CELLS)) <= CELLS
    assert (run.BENCH / "metrics" / f"{m['name']}.py").exists()
    if per_layer:
        assert m["moves"] in E2E and _line(m["layer"])
        moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", CELLS)) <= set(moved.get("workloads",
                                                               CELLS))
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for cell in CELLS:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if cell in m.get("workloads", CELLS)]
        layer = [m for m in SPEC["per_layer"]
                 if cell in m.get("workloads", CELLS)]
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
