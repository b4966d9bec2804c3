"""The interactive cell `ycsb.interactive`: its configuration's bank is
pinned, and a tiny copy of the cell runs correct through the harness on the
CPU, reads `round_stops_per_event` above 0, and matches the plain reference
bit for bit when its worlds run in lockstep (`vmap`, what `auto` picks on
one TPU)."""

from __future__ import annotations

import json
import shutil
import time

import jax
import numpy as np
import pytest

from conftest import ROOT
from test_bench_gen import digest

from bench import harness, ref
from bench.gen import make_bank

CONFIG = "ycsb_geo4_interactive"
CELL = "ycsb.interactive"
TINY = "tiny.interactive"
PRESETS = ["ssp", "ssp-local", "scalardb", "geotp"]
DIGESTS = {
    0: "1e25056a39ad0909b900b02b110fdf1936d0c56d72c590299b7d34288d19ce55",
    2**40 + 3: "a4edbfaf7e89fdc56b809c59b06cc51e306664a55363a25fac10b990979a80d4",
}


def _config() -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{CONFIG}.json").read_text())


def test_the_cell_runs_the_interactive_configuration_on_one_chip():
    spec = harness.load_spec()
    cell, config, traffic = harness.load_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "fig5", 1)
    assert traffic["presets"] == PRESETS and traffic["bank_seeds"] == [1, 2]
    base = json.loads((ROOT / "bench" / "configs" / "ycsb_geo4.json").read_text())
    assert config["deployment"] == base["deployment"]
    params = dict(base["bank"]["params"], ops_per_txn=6, rounds=3)
    assert config["bank"] == dict(base["bank"], params=params)
    assert config["reduced"] == []


@pytest.mark.parametrize("seed", list(DIGESTS))
def test_interactive_bank_is_pinned(seed):
    bank = make_bank(_config()["bank"], 16, 32, seed)
    assert digest(bank) == DIGESTS[seed]
    rows = bank["round_id"].reshape(-1, 6)
    assert (rows == np.array([0, 0, 1, 1, 2, 2], np.int8)).all()


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout-like directory holding the cell cut to 8 terminals, a
    0.5 s horizon and one bank under the four fig5 presets."""
    root = tmp_path_factory.mktemp("interactive")
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    config = _config()
    config["deployment"].update(terminals=8, txns_per_terminal=16, horizon_s=0.5, warmup_s=0.1)
    config["bank"]["params"]["records_per_node"] = 1000
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    traffic = {"presets": PRESETS, "bank_seeds": [1]}
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(traffic))
    spec = harness.load_spec()
    conf = next(c for c in spec["configs"] if c["name"] == CONFIG)
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    spec["configs"] = [dict(conf, name="tiny", file="bench/configs/tiny.json")]
    spec["workloads"] = [dict(cell, name=TINY, config="tiny", traffic="tiny")]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_tiny_interactive_cell_is_correct_and_reads_round_stops(tiny_root):
    line = harness.run_cell(
        harness.load_spec(tiny_root), TINY, 2**40 + 29, 0.3, True, time.perf_counter(),
        log_dir=tiny_root / "trace", root=tiny_root, log=lambda m: None,
    )
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == len(PRESETS)
    assert all(n["value"] == 0 for n in line["checks"].values()), line["checks"]
    metrics = line["metrics"]
    assert metrics["round_stops_per_event"]["unit"] == "stops/event"
    assert 0 < metrics["round_stops_per_event"]["value"] < metrics["trips_per_event"]["value"]


def test_tiny_interactive_worlds_in_lockstep_match_the_reference(tiny_root):
    _, config, traffic = harness.load_cell(harness.load_spec(tiny_root), TINY, tiny_root)
    inputs = harness.sweep_inputs(config, traffic, harness.bank_pool(config, traffic))
    grid = harness.program_grid(inputs)
    res = harness.simulator(config, grid).run_grid(grid, strategy="vmap")
    assert res.strategy_resolved == "vmap"
    assert int(np.asarray(res.states.win_stops)[:, -1].sum()) > 0  # rounds advanced
    for b, (cell, bank) in enumerate(zip(inputs.cells, inputs.banks)):
        want, want_m = ref.simulate(config["deployment"], bank, cell)
        got = jax.tree_util.tree_map(np.asarray, res.world(b))
        assert harness.differing_leaves(got, want) == [], cell["preset"]
        assert harness.differing_metrics(res.metrics[b], want_m) == [], cell["preset"]
