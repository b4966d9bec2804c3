"""BENCHMARK.json against the rules its readers hold it to, and a new cell
and metric added as files plus entries, with no file edited."""

from __future__ import annotations

import hashlib
import json
import re
import time

from conftest import ROOT, TINY

from bench import harness

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(SPEC["command"]) <= 32 and all(_text(w) for w in SPEC["command"])
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.fullmatch(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entries():
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and _text(c["source"]) and _text(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.fullmatch(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        names.add(c["name"])
    assert len(names) == len(SPEC["configs"])
    cells = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and NAME.fullmatch(w["traffic"]) and _text(w["why"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        cells.add(w["name"])
    assert len(cells) == len(SPEC["workloads"])
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) == len(cells)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(cells) // 2)
    assert {w["config"] for w in SPEC["workloads"]} == names
    metrics = set()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        metrics.add(m["name"])
    assert len(metrics) == len(SPEC["end_to_end"]) + len(SPEC["per_layer"])
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert _text(m["layer"])


def _reports(cell: str, traced: bool) -> set:
    return {m["name"] for m in harness.cell_metrics(SPEC, cell, traced)}


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        got = _reports(w["name"], False)
        assert "setup_s" in got and got - {"setup_s"} and _reports(w["name"], True)
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", [w["name"] for w in SPEC["workloads"]]):
            assert m["moves"] in _reports(cell, False), (m["name"], cell)


def _digests(root) -> dict:
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file()
    }


def test_a_cell_and_a_metric_are_added_as_files_and_entries(tiny_root):
    before = _digests(tiny_root)
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    (tiny_root / "bench" / "traffic" / "tiny_geotp.json").write_text(
        json.dumps({"presets": ["geotp"], "bank_seeds": [5, 6]})
    )
    (tiny_root / "bench" / "metrics" / "worlds_per_sweep.py").write_text(
        "def read(run):\n    return float(len(run.sweeps[0].inputs.cells))\n"
    )
    spec["workloads"].append(dict(spec["workloads"][0], name="tiny.geotp", traffic="tiny_geotp"))
    spec["per_layer"].append(
        {"name": "worlds_per_sweep", "unit": "worlds", "better": "higher", "source": "program_counter",
         "layer": "placement (placement.py)", "moves": "events_per_s", "workloads": ["tiny.geotp"]}
    )
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digests(tiny_root)
    assert {k: after[k] for k in before if k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"
    }
    line = harness.run_cell(
        harness.load_spec(tiny_root), "tiny.geotp", 5, 0.2, True, time.perf_counter(),
        log_dir=tiny_root / "trace", root=tiny_root, log=lambda m: None,
    )
    assert line["correct"] is True
    assert line["metrics"]["worlds_per_sweep"] == {"value": 2.0, "unit": "worlds"}
    assert "worlds_per_sweep" not in {m["name"] for m in harness.cell_metrics(spec, TINY, True)}
