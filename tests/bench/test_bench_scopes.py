"""The per-phase split of a loop's device time (`bench.scopes`) on a scoped
CPU profile, and the reader of the program's host spans."""

from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from conftest import write_tiny_root

from bench import harness, scopes, trace

START, END = harness.TRACE_START, harness.TRACE_END


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    """A recorded CPU trace of a jitted loop whose body holds scoped and
    unscoped ops, between the window's markers; with the compiled HLO."""
    log_dir = tmp_path_factory.mktemp("trace")

    @jax.jit
    def f(x):
        def body(i, y):
            with jax.named_scope("repro/plan"):
                y = jnp.tanh(y) @ y
            with jax.named_scope("repro/apply"):
                with jax.named_scope("repro/locks"):
                    y = jnp.sin(y) @ y
                y = jnp.cos(y) @ y
            return y @ y / 128  # no scope: the rest

        return jax.lax.fori_loop(0, 40, body, x)

    x = jnp.ones((128, 128), jnp.float32) / 128
    f(x).block_until_ready()
    text = f.lower(x).compile().as_text()
    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation(START):
            pass
        with jax.profiler.TraceAnnotation("bench.sweep"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation(END):
            pass
    finally:
        jax.profiler.stop_trace()
    return jax.profiler.ProfileData.from_file(str(trace.find_xplane(log_dir))), text


def _op_line(pd):
    """The CPU stands in for a device: its busiest XLA thread's events."""
    host = pd.find_plane_with_name(trace.HOST_PLANE)
    lines = [ln for ln in host.lines if ln.name.startswith("tf_XLA")]
    return max(lines, key=lambda ln: sum(e.duration_ns for e in ln.events))


def test_phases_and_the_rest_add_up_to_the_loops_busy_time(profile):
    pd, text = profile
    line = _op_line(pd)
    lo, hi, _ = trace.window_bounds(pd, START, END)
    sp = scopes.split(line, lo, hi, scopes.hlo_op_names(text))
    lp = trace.reduce(pd, START, END, {0: (line, None)}).loops[0]
    assert sp.trips == lp.trips
    assert sp.busy_s == pytest.approx(lp.busy_s, rel=1e-12)
    assert sum(sp.seconds.values()) == pytest.approx(lp.busy_s, rel=1e-9)
    assert set(sp.seconds) == set(scopes.PHASES) | {scopes.REST}
    # each of the body's matmuls lands in its own innermost scope
    for phase in ("plan", "locks", "apply", "rest"):
        assert sp.seconds[phase] > 0, (phase, sp.seconds)
    per_trip = sp.us_per_trip()
    assert sum(per_trip.values()) == pytest.approx(lp.busy_s / lp.trips * 1e6, rel=1e-9)


def test_idle_before_the_loop_divides_among_the_host_intervals(profile):
    pd, text = profile
    line = _op_line(pd)
    lo, hi, _ = trace.window_bounds(pd, START, END)
    sp = scopes.split(line, lo, hi, scopes.hlo_op_names(text))
    mid = (lo + sp.loop_start_ns) / 2
    idle = scopes.pre_loop_idle(line, lo, sp.loop_start_ns, {"a": (lo, mid), "b": (mid, hi)})
    lp = trace.reduce(pd, START, END, {0: (line, None)}).loops[0]
    whole = (sp.loop_start_ns - lo) / 1e9 - lp.pre_busy_s
    assert sum(idle.values()) == pytest.approx(whole, rel=1e-6, abs=1e-9)
    assert idle["other"] == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize(
    "op_name, phase",
    [
        ("jit(f)/vmap()/while/body/repro/apply/repro/plan/repro/locks/eq", "locks"),
        ("jit(f)/while/body/repro/apply/repro/hotspot/scatter-add", "hotspot"),
        ("jit(f)/vmap()/while/cond/repro/pick/reduce_min", "pick"),
        ("jit(f)/vmap()/while", "rest"),
        ("jit(f)/repro/other/add", "rest"),
        (None, "rest"),
    ],
)
def test_the_innermost_repro_scope_names_the_phase(op_name, phase):
    assert scopes.phase_of(op_name) == phase


def test_host_share_reads_the_sweeps_host_spans(tmp_path):
    write_tiny_root(tmp_path)
    spec = harness.load_spec(tmp_path)
    _, config, traffic = harness.load_cell(spec, spec["workloads"][0]["name"], tmp_path)
    st = harness.setup(config, traffic)
    sweeps = [harness.run_sweep(st, st.inputs, i) for i in range(2)]
    run = harness.Run(st.strategy, 0.0, 0.0, sweeps)
    share = harness.read_metric("host_share", run, tmp_path)
    assert 0 < share < 100
    host = sum(sw.result.phases[k] for sw in sweeps for k in ("repro.stack", "repro.gather", "repro.summarize"))
    assert share == pytest.approx(100 * host / sum(sw.seconds for sw in sweeps), rel=1e-12)


def test_host_share_reads_nothing_from_a_program_without_spans(tmp_path):
    write_tiny_root(tmp_path)
    sweep = SimpleNamespace(result=SimpleNamespace(), seconds=1.0)
    run = harness.Run("vmap", 0.0, 0.0, [sweep])
    assert harness.read_metric("host_share", run, tmp_path) is None
    sweep.result.phases = {"repro.device": 0.9}
    assert harness.read_metric("host_share", run, tmp_path) is None


def test_the_device_readers_find_nothing_without_a_tpu_trace(tmp_path):
    write_tiny_root(tmp_path)
    spec = harness.load_spec(tmp_path)
    line = harness.run_cell(
        spec, spec["workloads"][0]["name"], 3, 0.2, True, time.perf_counter(),
        log_dir=tmp_path / "trace", root=tmp_path, log=lambda m: None,
    )
    assert "us_per_trip" not in line["metrics"] and "idle_share" not in line["metrics"]
    assert 0 < line["metrics"]["host_share"]["value"] < 100
