"""Device time per phase of the loop's trip, from a traced run's op events.

The program names the phases of a loop trip with `jax.named_scope`s; an
op's HLO metadata ``op_name`` carries every scope it was traced under,
and the innermost ``repro/<phase>`` names its phase:

    pick     the loop condition's min over the event times, the rank-0 pick
    plan     the window plan (`window._window_plan`), less its chain part
    chain    the chain entities (`chain.py`)
    apply    the masked window pass and the single-event step
    locks    lock attempts, grants and the [T,K] key-conflict scans
    hotspot  the hot table: lookups, claims, the Eq. 4 update, the forecast

An op with no such scope falls in ``rest`` (for instance the select over
the while loop's carry that `vmap` adds around the body, outside any
scope of the program). A fusion carries the metadata of its root op.

The reduction uses the window, device and loop of `us_per_trip`, through
`bench.trace`'s functions: the busiest device's op line between the host
markers, cut where a device dropped its buffers, from the loop's first
trip on. Each op's self time (its duration less that of ops nested in it)
inside that part goes to its phase; the phases and the rest add up to the
part's busy time, and over the part's trips to `us_per_trip`.

Where an op event carries no ``op_name`` of its own, its phase comes from
the compiled program's HLO text (``hlo_module``/``hlo_op`` to the
instruction's metadata). Run as a module from the checkout's root, this
file traces one sweep of a cell on the chip, as ``bench/run.py --trace 1``
does, and prints the split, the sweep's host spans and how the device's
idle time before the loop divides among them:

    python -m bench.scopes --workload ycsb.fig5
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import re

import numpy as np

from bench import trace

PHASES = ("pick", "plan", "chain", "apply", "locks", "hotspot")
REST = "rest"
_SCOPE = re.compile(r"repro/([a-z]+)")
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')
_EVENT_OP = re.compile(r"^%?([\w.\-]+)")
# the traced run's other readings, printed beside the split
READ_TOO = ("us_per_trip", "idle_share", "host_share", "trips_per_event", "lane_imbalance")


def phase_of(op_name: str | None) -> str:
    """The innermost ``repro/<phase>`` scope of an op name, or `REST`."""
    found = [p for p in _SCOPE.findall(op_name or "") if p in PHASES]
    return found[-1] if found else REST


def hlo_op_names(text: str) -> dict:
    """{module: {instruction: op_name}} from a compiled program's HLO text
    (`Compiled.as_text()`)."""
    head = re.match(r"HloModule ([^\s,]+)", text)
    ops = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            ops[m.group(1)] = m.group(2)
    return {head.group(1) if head else "": ops}


def event_op_name(event, table: dict) -> str | None:
    """An op event's ``op_name``: its own stat where the trace has one,
    else the metadata of its ``hlo_op`` in its ``hlo_module`` (any module
    of ``table`` where the event names none)."""
    stats = {k: v for k, v in event.stats}
    for key in ("op_name", "tf_op", "name"):
        v = stats.get(key)
        if isinstance(v, str) and "/" in v:
            return v
    op = stats.get("hlo_op")
    if not isinstance(op, str):
        m = _EVENT_OP.match(event.name)
        op = m.group(1) if m else event.name
    if "hlo_module" in stats:
        return table.get(stats["hlo_module"], {}).get(op)
    return next((ops[op] for ops in table.values() if op in ops), None)


@dataclasses.dataclass
class Split:
    seconds: dict  # phase (and REST) -> self seconds in the loop's part
    busy_s: float  # the part's busy time (the union of its op intervals)
    trips: int  # loop trips in the part
    loop_start_ns: float  # the loop's first trip on the trace's clock
    ops: list  # [[op, phase, op_name, self seconds]], most time first

    def us_per_trip(self) -> dict:
        return {k: v / self.trips * 1e6 for k, v in self.seconds.items()}


def split(line, lo: float, hi: float, table: dict) -> Split | None:
    """Self seconds per phase of the loop's part of ``line`` inside
    [lo, hi], found as `trace.reduce` finds it; None where the line holds
    no loop."""
    iv, names = trace.read_line(line, lo, hi)
    runs = collections.Counter(names)
    trips = trace.loop_trips(runs)
    if trips <= 1:
        return None
    at = max(lo, min(s for (s, _), n in zip(iv, names) if runs[n] == trips))
    first = {}
    for e in line.events:
        if e.name in runs and e.name not in first:
            first[e.name] = e
    op_name = {n: event_op_name(e, table) for n, e in first.items()}
    phase = {n: phase_of(o) for n, o in op_name.items()}
    own = trace.self_times(np.clip(iv, at, hi), names)
    seconds = {p: 0.0 for p in PHASES + (REST,)}
    for n, ns in own.items():
        seconds[phase[n]] += ns / 1e9
    part = trace.union(iv, at, hi)
    busy = float(np.sum(part[:, 1] - part[:, 0])) / 1e9
    ops = sorted(([n, phase[n], op_name[n], ns / 1e9] for n, ns in own.items()), key=lambda x: -x[3])
    return Split(seconds, busy, trips, at, ops)


def pre_loop_idle(line, lo: float, at: float, spans: dict) -> dict:
    """Idle seconds of ``line``'s device between ``lo`` and the loop's
    first trip ``at``, divided among host intervals: ``spans`` maps a name
    to its (start_ns, end_ns); idle outside every interval is "other"."""
    iv, _ = trace.read_line(line, lo, at)
    idle = trace.gaps(trace.union(iv, lo, at), lo, at)
    out, left = {}, float(np.sum(idle[:, 1] - idle[:, 0]))
    for name, (s, e) in spans.items():
        cut = np.clip(idle, s, e)
        out[name] = float(np.sum(cut[:, 1] - cut[:, 0])) / 1e9
        left -= out[name] * 1e9
    out["other"] = left / 1e9
    return out


# ---------------------------------------------------------------------------
# the script: one traced sweep of a cell, split by phase
# ---------------------------------------------------------------------------


def _program_text(st) -> str:
    """The optimised HLO of the program `run_grid` ran for the sweep,
    compiled afresh: the compile cache's key leaves out metadata, so a
    cached program may carry another version's scopes."""
    import jax

    from bench import harness
    from repro.core.engine import placement

    grid = harness.program_grid(st.inputs)
    worlds = grid.worlds()
    strategy = st.strategy
    cfg = placement.placement_cfg(st.sim._cfg_for(worlds.faults), strategy)
    ndev = placement.mesh_device_count(strategy)
    lowered = placement._sim_batch_fresh.lower(cfg, grid.bank_stack(), worlds, 0, strategy, ndev)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)


def _host_intervals(pd) -> dict:
    """Name -> (start_ns, end_ns) of the first host event of that name. A
    span still open when the trace stopped (`repro.run_grid`,
    `repro.device`) is not in the trace."""
    out = {}
    for ln in pd.find_plane_with_name(trace.HOST_PLANE).lines:
        for e in ln.events:
            out.setdefault(e.name, (e.start_ns, e.start_ns + e.duration_ns))
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import shutil
    import sys
    import time

    t_start = time.perf_counter()
    root = pathlib.Path(__file__).resolve().parents[1]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    sys.path[:0] = [str(root), str(root / "src")]

    import jax

    from bench import harness
    from repro.compile_cache import use_compile_cache

    def log(msg):
        print(f"[scopes] {msg}", file=sys.stderr, flush=True)

    use_compile_cache()
    cell, config, traffic = harness.load_cell(harness.load_spec(root), args.workload, root)
    st = harness.setup(config, traffic)
    log_dir = root / ".bench_out" / "scopes_trace"
    shutil.rmtree(log_dir, ignore_errors=True)
    try:
        (sw,), reduced = harness.traced(st, log_dir, cell["chips"], log)
        t0 = time.perf_counter()
        pd = jax.profiler.ProfileData.from_file(str(trace.find_xplane(log_dir)))
        lines = trace.device_lines(pd)
        if not lines:
            log("the trace holds no TPU op line; nothing to split")
            return 2
        lo, hi, _ = trace.window_bounds(pd, harness.TRACE_START, harness.TRACE_END)
        for _, dropped in lines.values():
            if dropped is not None:
                hi = min(hi, dropped)
        used = set(sw.lane_device)
        dev = max((d for d in reduced.loops if d in used), key=lambda d: reduced.loops[d].busy_s)
        table = hlo_op_names(_program_text(st))
        sp = split(lines[dev][0], lo, hi, table)
        if sp is None:
            log("the busiest device's op line holds no loop; nothing to split")
            return 2
        host = _host_intervals(pd)
        # the harness's bank upload is the Python call `program_grid`
        spans = {n: iv for n, iv in host.items() if n.endswith(" program_grid")}
        if "repro.stack" in host:
            spans["repro.stack"] = host["repro.stack"]
            spans["repro.run_grid after repro.stack"] = (host["repro.stack"][1], hi)
        idle = pre_loop_idle(lines[dev][0], lo, sp.loop_start_ns, spans)
        log(f"second read of the trace in {time.perf_counter() - t0} s")
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    lp = reduced.loops[dev]
    per_trip = sp.us_per_trip()
    run = harness.Run(st.strategy, 0.0, 0.0, [sw], reduced)
    metrics = {m: harness.read_metric(m, run, root) for m in READ_TOO}
    log("us/trip " + " ".join(f"{k} {v:.3f}" for k, v in per_trip.items())
        + f" | sum {sum(per_trip.values()):.3f} us_per_trip {lp.busy_s / lp.trips * 1e6:.3f}")
    print(json.dumps({
        "workload": args.workload,
        "device": dev,
        "us_per_trip": lp.busy_s / lp.trips * 1e6,
        "phases_us_per_trip": per_trip,
        "split_busy_us_per_trip": sp.busy_s / sp.trips * 1e6,
        "trips": sp.trips,
        "window_s": reduced.window_s,
        "pre_loop_busy_s": lp.pre_busy_s,
        "pre_loop_idle_s": idle,
        "sweep_s": sw.seconds,
        "host_phases_s": getattr(sw.result, "phases", {}),
        "metrics": metrics,
        "busy_s": reduced.busy_s,
        "rest_ops": [o for o in sp.ops if o[1] == REST][:12],
        "top_ops": sp.ops[:12],
        "total_s": time.perf_counter() - t_start,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
