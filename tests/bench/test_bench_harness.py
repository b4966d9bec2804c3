"""The harness end to end on the CPU at a tiny size, and its refusals."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from conftest import ROOT, TINY

from bench import harness

REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_tiny_run_assembles_one_result_line(tiny_root, traced):
    spec = harness.load_spec(tiny_root)
    line = harness.run_cell(
        spec, TINY, 2**40 + 17, 0.3, traced, time.perf_counter(),
        log_dir=tiny_root / "trace", root=tiny_root, log=lambda m: None,
    )
    back = json.loads(json.dumps(line))
    extra = ["breakdown", "checks"] if traced else ["checks"]
    assert list(back) == REQUIRED + extra  # the numbers compared come last
    assert back["correct"] is True
    assert back["failed"] == 0 and back["attempted"] >= 2
    want = {m["name"] for m in spec["per_layer" if traced else "end_to_end"]}
    # on the CPU no device plane is traced: the readers of device time find
    # nothing to read
    if traced:
        want -= {"us_per_trip", "idle_share"}
        assert back["metrics"]["lane_imbalance"]["value"] == 1.0  # map: one lane
        assert set(back["device"]) >= {"busy_s", "window_s"}
    assert set(back["metrics"]) == want
    for m in back["metrics"].values():
        assert set(m) == {"value", "unit"}
    if not traced:
        assert back["metrics"]["events_per_s"]["value"] > 0
        assert back["metrics"]["setup_s"]["value"] > 0
    assert back["device"]["platform"] == "cpu"
    assert back["checks"] == {
        "state_leaves_differing": {"value": 0, "limit": 0},
        "metric_values_differing": {"value": 0, "limit": 0},
        "lock_rule_violations": {"value": 0, "limit": 0},
        "compiles_in_window": {"value": 0, "limit": 0},
    }


def test_only_a_multi_chip_traced_run_is_bounded():
    opens, span_s, opts = harness.trace_plan(1)
    assert (opens, span_s) == ("sweep", harness.TRACE_S) and span_s == 1.5
    default = jax.profiler.ProfileOptions()
    assert (opts.host_tracer_level, opts.python_tracer_level) == (
        default.host_tracer_level, default.python_tracer_level
    )
    assert opts.enable_hlo_proto is False
    opens, span_s, opts = harness.trace_plan(4)
    assert (opens, span_s) == ("device_call", harness.LOOP_S)
    assert opts.python_tracer_level == 0
    assert opts.host_tracer_level == default.host_tracer_level  # markers and spans stay
    assert opts.enable_hlo_proto is False


FOUR_DEVICES = """
import json, pathlib, sys, time
import jax
sys.path[:0] = [{bench!r}, {root!r}]
from conftest import TINY, write_tiny_root
from test_bench_check import _broken_batch
from bench import harness
from repro.core.engine import api
root = pathlib.Path({tmp!r})
spec = write_tiny_root(root, presets=("ssp", "geotp"), banks=2)
spec["workloads"][0]["chips"] = 4
(root / "BENCHMARK.json").write_text(json.dumps(spec))
run = api.simulate_batch
for fault in [None, *{faults!r}]:
    api.simulate_batch = _broken_batch(fault) if fault else run
    logs = []
    line = harness.run_cell(harness.load_spec(root), TINY, 2**35 + 3, 0.3, {traced}, time.perf_counter(),
                            log_dir=root / "trace", root=root, log=logs.append)
    print("\\n".join(logs))
    print(json.dumps(line))
if {traced}:  # the host spans the trace holds
    from bench import trace
    pd = jax.profiler.ProfileData.from_file(str(trace.find_xplane(root / "trace")))
    names = {{e.name for plane in pd.planes for ln in plane.lines for e in ln.events}}
    print("traced spans", sorted(n for n in names if n.startswith(("repro.", "bench."))))
"""
FAULTS = ["unchanged", "half_batch", "no_exchange", "altered"]


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_a_four_device_run_places_worlds_on_every_device(tmp_path, traced):
    """A four-world cell on four CPU devices: `auto` places it on the mesh,
    every world is checked, and each broken timed path (untraced only)
    comes out not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    faults = [] if traced else FAULTS
    code = FOUR_DEVICES.format(bench=str(ROOT / "tests" / "bench"), root=str(ROOT),
                               tmp=str(tmp_path), traced=traced, faults=faults)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = p.stdout.splitlines()
    lines = [json.loads(s) for s in out if s.startswith("{")]
    assert len(lines) == 1 + len(faults)
    line = lines[0]
    assert "placement: auto resolved to mesh" in out
    assert "worlds on devices [0, 1, 2, 3] of 4" in out
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert all(n["value"] == 0 for n in line["checks"].values()), line["checks"]
    assert "check: 4 worlds against the reference" in "\n".join(out)
    if traced:  # one sweep, traced from its device call on: its stacking is left out
        assert line["attempted"] == 4
        assert f"tracing {harness.LOOP_S} s from the device_call, Python tracer level 0" in out
        assert line["metrics"]["lane_imbalance"]["value"] >= 1.0
        # the trace opened after the stacking had ended
        spans = [s for s in out if s.startswith("traced spans")]
        assert spans and "'bench.trace_start'" in spans[0], out
        assert "repro.stack" not in spans[0] and "bench.sweep" not in spans[0], spans
    for fault, broken in zip(faults, lines[1:]):
        assert broken["correct"] is False and broken["failed"] > 0, fault


def _run_main(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_main_refuses_a_cpu():
    p = _run_main(ROOT, "--workload", "ycsb.fig5", "--seed", "3", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_main_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_main(tmp_path, "--workload", "ycsb.fig5", "--seed", "3", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_every_sweep_runs_the_same_worlds():
    _, config, traffic = harness.load_cell(harness.load_spec(), "ycsb.fig5")
    pool = harness.bank_pool(config, traffic)
    assert pool[0]["key"].tobytes() != pool[1]["key"].tobytes()
    again = harness.bank_pool(config, traffic)
    assert all(x["key"].tobytes() == y["key"].tobytes() for x, y in zip(pool, again))
    a = harness.sweep_inputs(config, traffic, pool)
    assert all(bank is pool[c["bank"]] for c, bank in zip(a.cells, a.banks))
    # lanes are preset-major
    assert [(c["preset"], c["bank"]) for c in a.cells] == [
        (p, k) for p in traffic["presets"] for k in range(len(traffic["bank_seeds"]))
    ]


def test_a_compile_inside_the_window_is_not_correct(tiny_root, monkeypatch):
    run = harness.run_sweep

    def compiles_first(st, inputs, index):
        jax.jit(lambda x: x * 3 + index)(np.float32(index)).block_until_ready()
        return run(st, inputs, index)

    monkeypatch.setattr(harness, "run_sweep", compiles_first)
    line = harness.run_cell(
        harness.load_spec(tiny_root), TINY, 5, 0.2, False, time.perf_counter(),
        root=tiny_root, log=lambda m: None,
    )
    assert line["correct"] is False
    assert line["checks"]["compiles_in_window"]["value"] >= 1
    assert line["checks"]["state_leaves_differing"]["value"] == 0


@pytest.mark.parametrize("kind", ["leaf", "metric"])
def test_a_later_sweep_that_differs_from_the_first_is_not_correct(tiny_root, monkeypatch, kind):
    run = harness.run_sweep

    def drifts(st, inputs, index):
        sw = run(st, inputs, index)
        if index == 1:
            res = sw.result
            if kind == "leaf":
                sw.result = dataclasses.replace(res, states=res.states._replace(aborts=res.states.aborts + 1))
            else:
                metrics = [dict(res.metrics[0], throughput_tps=-1.0), *res.metrics[1:]]
                sw.result = dataclasses.replace(res, metrics=metrics)
        return sw

    monkeypatch.setattr(harness, "run_sweep", drifts)
    line = harness.run_cell(
        harness.load_spec(tiny_root), TINY, 5, 1.5, False, time.perf_counter(),
        root=tiny_root, log=lambda m: None,
    )
    assert line["attempted"] > 2  # more than one sweep ran
    assert line["correct"] is False
    name = "state_leaves_differing" if kind == "leaf" else "metric_values_differing"
    assert line["checks"][name]["value"] >= 1
