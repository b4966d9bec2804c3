"""One run of one cell: set-up, the measured window, the check, the result.

A cell names a configuration (``bench/configs/<name>.json``: the deployment
and its bank) and a traffic mix (``bench/traffic/<name>.json``: the presets
and the seeds of the banks that make up one sweep). The window drives the
program's public entry, ``Simulator.run_grid(grid)`` with its default
placement, over whole sweeps of the same worlds back to back: the run's
seed does not change the work. The check runs the plain reference
(`bench.ref`) on every world of the first sweep and compares final states
and metric dicts with the program's, bit for bit; holds every later sweep
to the first, bit for bit; and holds the first sweep's lock state to an
independent model of strict two-phase locking (`bench.lockcheck`). A run
that compiled or loaded a program inside its window is not correct either.
Metrics are read by the files of ``bench/metrics/``, one per name.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import threading
import time

import jax
import numpy as np

from bench import lockcheck, ref, trace
from bench.gen import BANK_FIELDS, make_bank

ROOT = pathlib.Path(__file__).resolve().parents[1]
# SimState leaves that each step mode counts its own way (how many events a
# window drained, why windows stopped): path telemetry, not simulated state
PATH_TELEMETRY = ("drained", "windows", "win_stops", "fused", "chained")
# warm-up worlds: every link this slow, so the 10 s horizon holds few events
# and the warm-up runs the cell's own program in well under a second
WARM_RTT_MS = 100_000.0
# the profiler records this much of a one-chip traced sweep's start,
# between these two host markers
TRACE_S = 1.5
TRACE_START, TRACE_END = "bench.trace_start", "bench.trace_end"
# on several chips it records this much from the sweep's device call
LOOP_S = 0.12


def load_spec(root=ROOT) -> dict:
    with open(pathlib.Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(spec: dict, name: str, root=ROOT):
    """(cell entry, configuration, traffic mix) of the cell ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json (cells: {sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(pathlib.Path(root) / conf["file"]) as f:
        config = json.load(f)
    with open(pathlib.Path(root) / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    return cell, config, traffic


class CompileMeter:
    """Counts the backend compiles of this process (a persistent-cache load
    counts too: it sits inside the same event) and sums their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration_secs


@dataclasses.dataclass
class Inputs:
    """One sweep's worlds: a cell dict per lane and the lane's bank."""

    cells: list
    banks: list  # numpy bank dict per lane (every preset runs each bank)


def bank_pool(config: dict, traffic: dict) -> list:
    """The traffic's banks, one per entry of its ``bank_seeds``. Every sweep
    of every run holds the same worlds, so that runs differ in timing alone:
    banks drawn anew from each run's seed changed the work (the slowest
    lockstep lane) by several percent from seed to seed."""
    dep = config["deployment"]
    return [
        make_bank(config["bank"], dep["terminals"], dep["txns_per_terminal"], s)
        for s in traffic["bank_seeds"]
    ]


def sweep_inputs(config: dict, traffic: dict, pool: list) -> Inputs:
    """A sweep: every preset over every bank of ``pool``, preset-major, as
    `Grid.cross(preset=..., bank=...)` orders them."""
    dep = config["deployment"]
    cells = [
        dict(preset=p, rtt_ms=tuple(dep["rtt_ms"]), jitter_milli=dep["jitter_milli"], bank=k)
        for p in traffic["presets"]
        for k in range(len(pool))
    ]
    return Inputs(cells, [pool[c["bank"]] for c in cells])


def program_grid(inputs: Inputs, rtt_ms=None):
    """The program's `Grid` over the sweep's worlds (its own `Bank` per
    lane); ``rtt_ms`` overrides every world's RTTs (the warm-up)."""
    import jax.numpy as jnp
    from repro.core.engine import Grid
    from repro.core.workloads import Bank

    made = {}
    for b in inputs.banks:
        if id(b) not in made:
            made[id(b)] = Bank(
                *(jnp.asarray(b[f]) for f in BANK_FIELDS),
                num_records=int(b["num_records"]),
                num_ds=int(b["num_ds"]),
            )
    cells = [dict(c, rtt_ms=rtt_ms) if rtt_ms else c for c in inputs.cells]
    return Grid(cells, banks=[made[id(b)] for b in inputs.banks])


def simulator(config: dict, grid):
    from repro.core.engine import Simulator

    dep = config["deployment"]
    return Simulator.from_bank(
        grid.banks[0],
        horizon_s=dep["horizon_s"],
        warmup_s=dep["warmup_s"],
        hot_capacity=dep["hot_capacity"],
    )


@dataclasses.dataclass
class Setup:
    sim: object
    strategy: str  # the placement `auto` resolved to
    inputs: Inputs  # every sweep's worlds


def setup(config: dict, traffic: dict, log=lambda msg: None) -> Setup:
    """Draw the traffic's banks, build the simulator and run the cell's own
    program once on slow-link worlds of the same shapes, so that compiling
    (or loading it from the cache) and every small host-side op is done
    before the window opens."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.bank"):
        inputs = sweep_inputs(config, traffic, bank_pool(config, traffic))
    t1 = time.perf_counter()
    n = len(config["deployment"]["rtt_ms"])
    warm_grid = program_grid(inputs, rtt_ms=(WARM_RTT_MS,) * n)
    sim = simulator(config, warm_grid)
    warm = sim.run_grid(warm_grid)
    jax.block_until_ready(warm.states)
    log(f"set-up: banks {t1 - t0} s, warm-up {time.perf_counter() - t1} s "
        f"({int(np.sum(warm.states.iters))} loop iterations; spans {warm.phases})")
    return Setup(sim, warm.strategy_resolved, inputs)


@dataclasses.dataclass
class Sweep:
    index: int
    seconds: float  # the run_grid call, host path included
    inputs: Inputs
    result: object  # the program's RunResult
    events: np.ndarray  # [B] simulated events per world
    trips: np.ndarray  # [B] while-loop trips per world
    lane_device: list  # [B] id of the device each world ran on
    traced: bool = False  # ran under the profiler


def lane_devices(states) -> list:
    """Device id of each world of a batched final state."""
    leaf = states.iters
    B = int(leaf.shape[0])
    out = [None] * B
    for shard in leaf.addressable_shards:
        for b in range(B)[shard.index[0]]:
            out[b] = shard.device.id
    return out


def run_sweep(st: Setup, inputs: Inputs, index: int) -> Sweep:
    grid = program_grid(inputs)
    with jax.profiler.TraceAnnotation("bench.sweep"):
        t0 = time.perf_counter()
        res = st.sim.run_grid(grid)
        seconds = time.perf_counter() - t0
    s = jax.tree_util.tree_map(np.asarray, (res.states.iters, res.states.drained, res.states.windows))
    iters, drained, windows = s
    return Sweep(index, seconds, inputs, res, iters, iters - drained + windows, lane_devices(res.states))


def trace_plan(chips: int):
    """(where the trace opens, seconds it records, profiler options) for a
    cell on ``chips`` chips. One chip: the first `TRACE_S` seconds of the
    sweep, Python calls included. Several chips: every chip adds a plane of
    op events and four TPU v5e chips wrote a trace at about 1.9 s per MB,
    most of it the grid's stacking before the loop; so the trace opens at
    the sweep's device call and records `LOOP_S` there, with the Python
    tracer off. On four TPU v5e chips `ycsb.fig5.mesh4`'s call spent about
    0.05 s sharding its arguments, then each chip ran 231 us trips: 0.16 s
    held 470 of them, 70 MB written in 138 s, and `LOOP_S` leaves about
    290. Dearer trips give fewer, never a longer write. The harness's
    markers and the program's spans stay on the host plane."""
    opts = jax.profiler.ProfileOptions()
    opts.enable_hlo_proto = False
    if chips == 1:
        return "sweep", TRACE_S, opts
    opts.python_tracer_level = 0
    return "device_call", LOOP_S, opts


@contextlib.contextmanager
def at_device_call(fn):
    """Call ``fn`` where `Simulator.run_grid` makes its device call, after
    stacking the grid; the program's own `simulate_batch`, which `api`
    looks up at each call, runs unchanged right after."""
    from repro.core.engine import api

    inner = api.simulate_batch

    def fn_then_inner(*args, **kw):
        fn()
        return inner(*args, **kw)

    api.simulate_batch = fn_then_inner
    try:
        yield
    finally:
        api.simulate_batch = inner


def traced_sweep(st: Setup, inputs: Inputs, index: int, log_dir, chips: int, log=print) -> Sweep:
    """One sweep under the profiler, which records the span that
    `trace_plan` gives for ``chips``: on one chip the sweep's start, the
    host's preparation and then the loop, on several the loop from the
    device call (a TPU's trace buffers hold a few seconds of this loop, and
    writing the trace out takes about forty times as long as the span it
    covers). Markers on the host clock bound the traced span."""
    opens, span_s, opts = trace_plan(chips)
    log(f"tracing {span_s} s from the {opens}, Python tracer level {opts.python_tracer_level}")
    lock = threading.Lock()
    opened, took = [], []

    def stop():
        with lock:
            if not took:
                with jax.profiler.TraceAnnotation(TRACE_END):
                    pass
                t0 = time.perf_counter()
                jax.profiler.stop_trace()
                took.append(time.perf_counter() - t0)

    timer = threading.Timer(span_s, stop)

    def start():
        t0 = time.perf_counter()
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        opened.append(time.perf_counter() - t0)
        with jax.profiler.TraceAnnotation(TRACE_START):
            pass
        timer.start()

    try:
        if opens == "sweep":
            start()
            sw = run_sweep(st, inputs, index)
        else:
            with at_device_call(start):
                sw = run_sweep(st, inputs, index)
            sw.seconds -= opened[0]  # the profiler's start ran inside the sweep
    finally:
        if opened:
            timer.cancel()
            stop()
            timer.join()
    log(f"trace opened in {opened[0]} s, written in {took[0]} s")
    sw.traced = True
    return sw


def window(st: Setup, seconds: float) -> list:
    """Sweeps back to back until ``seconds`` have passed; every sweep that
    started runs to its end and counts."""
    sweeps = []
    t0 = time.perf_counter()
    while True:
        sweeps.append(run_sweep(st, st.inputs, len(sweeps)))
        if time.perf_counter() - t0 >= seconds:
            return sweeps


def traced(st: Setup, log_dir, chips: int, log=print):
    """The traced run's one sweep (`traced_sweep`) and its reduced trace.
    Writing the trace out takes minutes on a TPU, so the traced run holds
    this one sweep and no window."""
    sw = traced_sweep(st, st.inputs, 0, log_dir, chips, log)
    t0 = time.perf_counter()
    path = trace.find_xplane(log_dir)
    pd = jax.profiler.ProfileData.from_file(str(path))
    reduced = trace.reduce(pd, TRACE_START, TRACE_END, trace.device_lines(pd))
    log(f"trace of {path.stat().st_size} bytes read in {time.perf_counter() - t0} s; "
        f"traced span {reduced.window_s} s; loop trips traced per device "
        f"{ {d: lp.trips for d, lp in reduced.loops.items()} }")
    return [sw], reduced


def memory_peak_bytes(device_ids) -> int | None:
    peaks = []
    for d in jax.devices():
        if d.id in device_ids:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def differing_leaves(prog, want) -> list:
    """Names of the reference's state leaves that the program's final state
    lacks or holds with another dtype, shape or any other bit; the path
    telemetry is left out."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(want)[0]:
        if path[0].name in PATH_TELEMETRY:
            continue
        y = prog
        for key in path:
            y = getattr(y, key.name, None)
        x, y = np.asarray(x), (None if y is None else np.asarray(y))
        if y is None or x.dtype != y.dtype or x.shape != y.shape or x.tobytes() != y.tobytes():
            out.append(jax.tree_util.keystr(path))
    return out


def differing_metrics(prog: dict, want: dict) -> list:
    """Keys of the reference's metric dict whose value the program's lacks
    or differs from (NaN equals NaN)."""
    out = []
    for k, v in want.items():
        p = prog.get(k)
        same = p == v or (
            isinstance(p, float) and isinstance(v, float) and math.isnan(p) and math.isnan(v)
        )
        if not same or type(p) is not type(v):
            out.append(k)
    return out


def take_worlds(sweep: Sweep) -> list:
    """Host copies of a sweep's worlds: (cell, bank, final state, metrics)."""
    out = []
    for b, cell in enumerate(sweep.inputs.cells):
        state = jax.tree_util.tree_map(np.asarray, sweep.result.world(b))
        out.append((cell, sweep.inputs.banks[b], state, sweep.result.metrics[b]))
    return out


def repeats(first: list, later: list) -> dict:
    """Later sweeps' worlds against the first sweep's (each a `take_worlds`
    list): every sweep runs the same worlds, so each has to come out the
    same, bit for bit."""
    leaves = metrics = failed = 0
    notes = []
    for i, worlds in enumerate(later, 1):
        for (cell, _, want, want_m), (_, _, got, got_m) in zip(first, worlds):
            bad, bad_m = differing_leaves(got, want), differing_metrics(got_m, want_m)
            leaves += len(bad)
            metrics += len(bad_m)
            failed += bool(bad or bad_m)
            if bad or bad_m:
                notes.append(f"sweep {i} against sweep 0, {cell['preset']}/bank {cell['bank']}: "
                             f"{bad + bad_m}")
    return dict(leaves=leaves, metrics=metrics, failed=failed, notes=notes)


def check(config: dict, worlds: list, devices=None) -> dict:
    """Compare each world with the reference, run on ``devices`` in turn
    (default: JAX's default device), and its lock state with the rules of
    strict two-phase locking; returns the compared numbers (each with its
    limit), the worlds compared and how many failed."""
    devices = devices or [jax.devices()[0]]
    leaves = metrics = locks = failed = 0
    notes = []
    with jax.profiler.TraceAnnotation("bench.check"):
        runs = [
            ref.start(config["deployment"], w[1], w[0], devices[i % len(devices)])
            for i, w in enumerate(worlds)
        ]
        for (cell, _, state, m), run in zip(worlds, runs):
            want_state, want_m = ref.finish(*run)
            bad = differing_leaves(state, want_state)
            bad_m = differing_metrics(m, want_m)
            broken = lockcheck.violations(state)
            leaves += len(bad)
            metrics += len(bad_m)
            locks += broken
            failed += bool(bad or bad_m or broken)
            if bad or bad_m or broken:
                notes.append(f"{cell['preset']}/bank {cell['bank']}: {bad + bad_m}, "
                             f"{broken} pairs of ops against the 2PL rules")
    return dict(
        numbers={
            "state_leaves_differing": {"value": leaves, "limit": 0},
            "metric_values_differing": {"value": metrics, "limit": 0},
            "lock_rule_violations": {"value": locks, "limit": 0},
        },
        compared=len(worlds),
        failed=failed,
        notes=notes,
    )


# ---------------------------------------------------------------------------
# metrics and the result line
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    strategy: str
    setup_s: float
    setup_compile_s: float
    sweeps: list  # [Sweep]
    reduced: trace.Reduced | None = None  # the traced window, --trace 1 only


def read_metric(name: str, run: Run, root=ROOT):
    """The value of metric ``name`` from ``bench/metrics/<name>.py`` under
    ``root``, or None where that reader finds nothing to read in this run."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def cell_metrics(spec: dict, cell_name: str, traced: bool) -> list:
    """The metric entries a run of the cell reports: end-to-end untraced,
    per-layer traced; an entry with ``workloads`` only in those cells."""
    entries = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in entries if cell_name in m.get("workloads", [cell_name])]


def result_line(correct, attempted, failed, metrics, device, numbers, breakdown=None) -> dict:
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = numbers  # last: the numbers compared, each with its limit
    return line


def run_cell(spec, cell_name, seed, seconds, trace_run, t_start, log_dir=None, root=ROOT, log=print):
    """Set-up, window, check and the result line of one run. ``t_start`` is
    the process's start on the `time.perf_counter` clock; ``seed`` is
    logged only, since every run of a cell holds the same worlds."""
    cell, config, traffic = load_cell(spec, cell_name, root)
    meter = CompileMeter()
    log(f"set-up: imports and device start {time.perf_counter() - t_start} s")
    st = setup(config, traffic, log)
    setup_s = time.perf_counter() - t_start
    setup_compile_s = meter.seconds
    log(f"seed {seed}: the cell's worlds are fixed by its traffic's bank_seeds")
    log(f"placement: auto resolved to {st.strategy}")
    log(f"set-up {setup_s} s, of which compile or cache load {setup_compile_s} s "
        f"({meter.count} programs)")
    n0 = meter.count
    if trace_run:
        sweeps, reduced = traced(st, log_dir, cell["chips"], log)
    else:
        sweeps, reduced = window(st, seconds), None
    in_window = meter.count - n0
    log(f"compiles or cache loads inside the window: {in_window}")
    for sw in sweeps:
        log(f"sweep {sw.index}: {sw.seconds} s, {int(sw.events.sum())} events, "
            f"{int(sw.trips.sum())} trips")
    used = sorted({d for sw in sweeps for d in sw.lane_device})
    log(f"worlds on devices {used} of {jax.device_count()}")
    dev0 = jax.devices()[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": memory_peak_bytes(used),
    }
    run = Run(st.strategy, setup_s, setup_compile_s, sweeps, reduced)
    metrics = {}
    for m in cell_metrics(spec, cell_name, trace_run):
        v = read_metric(m["name"], run, root)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = None
    if reduced is not None:
        busy = [reduced.busy_s[d] for d in used if d in reduced.busy_s]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = reduced.window_s
        breakdown = {"device_ops": reduced.device_ops, "idle_gaps": reduced.idle_gaps}
    attempted = sum(len(sw.inputs.cells) for sw in sweeps)
    first = take_worlds(sweeps[0])
    again = repeats(first, [take_worlds(sw) for sw in sweeps[1:]])
    del run, sweeps, st  # free the program's state before the reference runs
    t0 = time.perf_counter()
    res = check(config, first, [d for d in jax.devices() if d.id in used])
    log(f"check: {res['compared']} worlds against the reference in "
        f"{time.perf_counter() - t0} s")
    for note in res["notes"] + again["notes"]:
        log(f"differs: {note}")
    numbers = res["numbers"]
    numbers["state_leaves_differing"]["value"] += again["leaves"]
    numbers["metric_values_differing"]["value"] += again["metrics"]
    numbers["compiles_in_window"] = {"value": in_window, "limit": 0}
    correct = res["compared"] == len(traffic["presets"]) * len(traffic["bank_seeds"]) and all(
        n["value"] <= n["limit"] for n in numbers.values()
    )
    failed = res["failed"] + again["failed"]
    return result_line(correct, attempted, failed, metrics, device, numbers, breakdown)
