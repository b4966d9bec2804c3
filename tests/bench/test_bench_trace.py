"""The trace reduction on a short CPU profile, against the events read
directly with `jax.profiler.ProfileData`."""

from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace

START, END = "bench.trace_start", "bench.trace_end"


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    """A recorded CPU trace: a jitted loop, a sleep and the loop again,
    between the window's start and end markers."""
    log_dir = tmp_path_factory.mktemp("trace")

    @jax.jit
    def f(x):
        return jax.lax.fori_loop(0, 8, lambda i, y: jnp.tanh(y) @ y, x)

    x = jnp.ones((128, 128), jnp.float32) / 128
    f(x).block_until_ready()
    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation(START):
            pass
        with jax.profiler.TraceAnnotation("bench.sweep"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.idle"):
            time.sleep(0.05)
        with jax.profiler.TraceAnnotation("bench.sweep"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation(END):
            pass
    finally:
        jax.profiler.stop_trace()
    return jax.profiler.ProfileData.from_file(str(trace.find_xplane(log_dir)))


def _op_line(pd):
    """The CPU stands in for a device: its busiest XLA thread's events."""
    host = pd.find_plane_with_name(trace.HOST_PLANE)
    lines = [ln for ln in host.lines if ln.name.startswith("tf_XLA")]
    return max(lines, key=lambda ln: sum(e.duration_ns for e in ln.events))


def _host_spans(pd):
    for ln in pd.find_plane_with_name(trace.HOST_PLANE).lines:
        if any(e.name == START for e in ln.events):
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in ln.events]


def _direct(pd, line):
    """Busy ns, idle ns by innermost host span, and self ns by op name,
    worked out one event at a time."""
    spans = _host_spans(pd)
    lo = next(s for n, s, _ in spans if n == START)
    hi = next(s for n, s, _ in spans if n == END)
    iv = sorted((max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)) for e in line.events)
    merged = []
    for s, e in iv:
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    busy = sum(e - s for s, e in merged)
    idle = {}
    cursor = lo
    for s, e in merged + [[hi, hi]]:
        if s > cursor:
            mid = (s + cursor) / 2
            cover = [(b - a, n) for n, a, b in spans if a <= mid <= b]
            name = min(cover)[1] if cover else "none"
            idle[name] = idle.get(name, 0) + (s - cursor)
        cursor = max(cursor, e)
    return lo, hi, busy, idle


def test_busy_union_idle_share_and_gaps(profile):
    line = _op_line(profile)
    lo, hi, busy, idle = _direct(profile, line)
    red = trace.reduce(profile, START, END, {0: (line, None)})
    assert red.window_s == pytest.approx((hi - lo) / 1e9, rel=1e-12)
    assert red.busy_s[0] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < red.busy_s[0] < red.window_s
    idle_share = 1 - red.busy_s[0] / red.window_s
    assert idle_share == pytest.approx(1 - busy / (hi - lo), rel=1e-9)
    got = dict(red.idle_gaps)
    assert got == pytest.approx({k: v / 1e9 for k, v in idle.items()}, rel=1e-9)
    # the sleep is idle, charged to its span or to one inside it (the
    # profiler records the Python call to sleep as a span of its own)
    _, a, b = next(sp for sp in _host_spans(profile) if sp[0] == "bench.idle")
    inside = {n for n, s, e in _host_spans(profile) if a <= s and e <= b}
    assert sum(v for k, v in got.items() if k in inside) >= 0.045
    assert sum(got.values()) == pytest.approx(red.window_s - red.busy_s[0], rel=1e-9)


def test_op_self_times_add_up_to_the_events(profile):
    line = _op_line(profile)
    lo, hi, _, _ = _direct(profile, line)
    red = trace.reduce(profile, START, END, {0: (line, None)}, top=10_000)
    inside = [e for e in line.events if e.start_ns < hi and e.start_ns + e.duration_ns > lo]
    # one thread's events nest, so self times split the time they cover
    # without counting any of it twice
    covered = trace.union(
        np.array([(e.start_ns, e.start_ns + e.duration_ns) for e in inside], float), 0, np.inf
    )
    assert sum(v for _, v in red.device_ops) == pytest.approx(
        float(np.sum(covered[:, 1] - covered[:, 0])) / 1e9, rel=1e-9
    )
    assert {k for k, _ in red.device_ops} <= {e.name for e in inside}


def test_union_merges_nested_and_touching_intervals():
    iv = np.array([[0, 10], [2, 3], [10, 12], [20, 25], [24, 30], [40, 41]], float)
    assert trace.union(iv, 1, 28).tolist() == [[1, 12], [20, 28]]
    assert trace.gaps(trace.union(iv, 0, 50), 0, 50).tolist() == [[12, 20], [30, 40], [41, 50]]


def test_the_window_ends_where_a_device_dropped_its_buffers(profile):
    line = _op_line(profile)
    lo, hi, _, _ = _direct(profile, line)
    cut = lo + (hi - lo) / 3
    red = trace.reduce(profile, START, END, {0: (line, cut)})
    assert red.window_s == pytest.approx((cut - lo) / 1e9, rel=1e-9)
    iv = np.array([(e.start_ns, e.start_ns + e.duration_ns) for e in line.events], float)
    covered = trace.union(iv, lo, cut)
    assert red.busy_s[0] == pytest.approx(float(np.sum(covered[:, 1] - covered[:, 0])) / 1e9)


def _ev(name, start_ns, duration_ns):
    return SimpleNamespace(name=name, start_ns=start_ns, duration_ns=duration_ns)


def _loop_trace(trips, cut_after_op, pre_ops=30, body=12, gap_ns=400_000):
    """A trace's shape: host work that runs ``pre_ops`` distinct small ops
    three times each, an idle gap, then a while loop of ``body`` distinct
    ops per trip, each 1000 ns long and 100 ns apart; the window ends after
    ``cut_after_op`` ops of the last trip. Returns (trace, op line)."""
    ev, t = [], 0
    for _ in range(3):
        for i in range(pre_ops):
            ev.append(_ev(f"prep.{i}", t, 500))
            t += 700
    t += gap_ns
    for k in range(trips):
        for i in range(body):
            if k == trips - 1 and i == cut_after_op:
                break
            ev.append(_ev(f"body.{i}", t, 1000))
            t += 1100
    host = SimpleNamespace(events=[_ev(START, 0, 1), _ev("bench.sweep", 0, t), _ev(END, t, 1)])
    plane = SimpleNamespace(lines=[host])
    return SimpleNamespace(find_plane_with_name=lambda name: plane), SimpleNamespace(events=ev)


@pytest.mark.parametrize("cut_after_op", [0, 5, 6, 11])
def test_the_loop_part_and_its_trips_come_from_the_ops(cut_after_op):
    tr, ops = _loop_trace(200, cut_after_op)
    red = trace.reduce(tr, START, END, {0: (ops, None)})
    lp = red.loops[0]
    # ops before the cut in the last trip ran 200 times, the others 199
    assert lp.trips == (200 if cut_after_op >= 6 else 199)
    # the loop's part holds its trips' busy time to within half a trip (the
    # partial trip at the cut), and none of the host's work or gap before it
    assert abs(lp.busy_s - lp.trips * 12_000e-9) <= 6 * 1000e-9 + 1e-12
    assert 90 * 500e-9 - 1e-12 <= lp.pre_busy_s <= 90 * 500e-9 + 12 * 1000e-9
    assert lp.pre_busy_s + lp.busy_s == pytest.approx(red.busy_s[0], rel=1e-12)
    assert red.window_s - red.busy_s[0] > 400_000e-9


def test_loop_trips_takes_the_count_of_the_ops_that_ran_most():
    runs = {f"body.{i}": 50 for i in range(10)} | {f"prep.{i}": 3 for i in range(60)}
    runs |= {"branch.a": 20, "branch.b": 30, "cut.0": 49, "cut.1": 49}
    assert trace.loop_trips(runs) == 50
    assert trace.loop_trips({"a": 4, "b": 4, "c": 8}) == 8  # a tie goes to the larger count
    assert trace.loop_trips({}) == 0
