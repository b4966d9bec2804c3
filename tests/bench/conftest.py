"""Shared fixtures of the benchmark's tests: the repo root on the import
path (the harness is the package ``bench``) and a tiny cell."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY = "tiny.cell"


def write_tiny_root(root: pathlib.Path, presets=("ssp", "geotp"), banks=1) -> dict:
    """A checkout-like directory holding a one-cell BENCHMARK.json over a
    YCSB deployment cut to 8 terminals and a 0.5 s horizon, the real
    metric readers, and the cell's configuration and traffic files."""
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    config = json.loads((ROOT / "bench" / "configs" / "ycsb_geo4.json").read_text())
    config["deployment"].update(terminals=8, txns_per_terminal=16, horizon_s=0.5, warmup_s=0.1)
    config["bank"]["params"]["records_per_node"] = 1000
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    traffic = {"presets": list(presets), "bank_seeds": list(range(1, banks + 1))}
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(traffic))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [dict(spec["configs"][0], name="tiny", file="bench/configs/tiny.json")]
    spec["workloads"] = [dict(spec["workloads"][0], name=TINY, config="tiny", traffic="tiny")]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return spec


@pytest.fixture
def tiny_root(tmp_path):
    write_tiny_root(tmp_path)
    return tmp_path
