"""Public simulation API: `Simulator` + `Grid` + `RunResult`.

The three documented entry points of the engine package:

* **`Grid`** — a declarative sweep: a validated list of cells (dicts over the
  engine axes `preset` / `rtt_ms` / `tau_true_us` / `jitter_milli` /
  `exec_scale_milli` / `seed` / `faults`, plus free-form labels) with optional per-cell
  Banks. Build from raw cells (`Grid(cells)`), a cross product
  (`Grid.cross(...)`) or zipped axes (`Grid.zipped(...)`). Every cell is
  validated at construction — heterogeneous `num_ds`, unknown presets and
  mismatched bank shapes raise with the offending cell index instead of
  silently producing wrong-shaped worlds.
* **`Simulator`** — the facade over the compiled engine. Constructed from the
  static shapes/horizon (one `SimConfig`); `.run(world, bank)` executes one
  world, `.run_grid(grid, bank)` executes a whole grid as one batched device
  call, `.resume(result)` continues finished states (donating the buffers).
  Run callables are compile-cached per (shape-key, strategy): `SimConfig`
  excludes the protocol preset from its hash, so one `Simulator` — indeed one
  process — compiles the engine once per *shape*, not once per cell, whatever
  mix of presets/latencies/seeds the grids sweep.
* **`RunResult`** — the structured output: final states (batched over cells),
  one metric dict per cell, drain telemetry, wall time. `.rows()` merges cell
  labels with metrics for tabulation, `.world(i)` slices one cell's final
  state, `.save(tag)` records the sweep into the benchmark JSON with the
  exact legacy `sweeps.<tag>` schema (plus the jax runtime environment).

Layering: this package never imports `benchmarks` or `repro.serving` — the
benchmark harness is a thin client of these three objects.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import pathlib
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.netmodel import PAPER_RTT_MS
from repro.core.protocol import PRESETS, ProtocolConfig

from repro.core.engine.batch import _run_jit, _sim_world_fresh
from repro.core.engine.metrics import drain_stats, summarize_batch, world_index
from repro.core.engine.placement import (
    mesh_device_count,
    resolve_strategy,
    simulate_batch,
)
from repro.core.engine.spans import span, wall_s
from repro.core.engine.state import (
    FAULT_COLS,
    INF_US,
    KIND_CRASH,
    KIND_DEGRADE,
    KIND_PARTITION,
    MW,
    SimConfig,
    WorldSpec,
    make_world,
    stack_worlds,
)

# engine-owned axes a Grid cell may set; everything else is a free-form label
GRID_AXES = (
    "preset", "rtt_ms", "tau_true_us", "jitter_milli", "exec_scale_milli",
    "seed", "faults", "replica_tau", "repl_lag_us", "clock_skew_us",
)
# axes whose single value is itself a sequence (one entry per data source)
_VECTOR_AXES = ("rtt_ms", "tau_true_us", "exec_scale_milli", "replica_tau")

BENCH_DIR = pathlib.Path("results/bench")
BENCH_FILE = BENCH_DIR / "BENCH_engine.json"


# ---------------------------------------------------------------------------
# benchmark JSON records (shared writer — benchmarks.common delegates here)
# ---------------------------------------------------------------------------


def runtime_env() -> dict:
    """The jax runtime this process measured on — recorded in every bench
    entry so perf trajectories across rigs/versions stay interpretable."""
    return {
        "jax_version": jax.__version__,
        "jax_backend": jax.default_backend(),
        "jax_device_kind": jax.devices()[0].device_kind,
        "jax_device_count": jax.device_count(),
    }


def load_bench(path=None) -> dict:
    p = pathlib.Path(path) if path is not None else BENCH_FILE
    if p.exists():
        with open(p) as f:
            return json.load(f)
    return {"sweeps": {}, "smoke": {}}


def _write_bench(bench: dict, path) -> None:
    p = pathlib.Path(path) if path is not None else BENCH_FILE
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w") as f:
        json.dump(bench, f, indent=1, default=float)


def record_bench(tag: str, entry: dict, path=None) -> dict:
    """Merge one sweep's perf record into the bench JSON under sweeps.<tag>."""
    entry = {**entry, **runtime_env()}
    bench = load_bench(path)
    bench.setdefault("sweeps", {})[tag] = entry
    _write_bench(bench, path)
    return entry


def record_smoke(entry: dict, path=None) -> dict:
    entry = {**entry, **runtime_env()}
    bench = load_bench(path)
    bench["smoke"] = entry
    _write_bench(bench, path)
    return entry


# ---------------------------------------------------------------------------
# Grid
# ---------------------------------------------------------------------------


def _cell_num_ds(cell: dict, default_rtt_ms) -> int:
    if cell.get("tau_true_us") is not None:
        return len(cell["tau_true_us"])
    rtt = cell.get("rtt_ms")  # an explicit None means "use the default" too
    return len(rtt if rtt is not None else default_rtt_ms)


def _fault_row_resources(kind: int, a: int, b: int) -> tuple:
    """The link/node resources one typed fault row occupies, as hashable
    keys: overlapping intervals on a shared resource are rejected. A CRASH
    claims its node AND its middleware link (the outage accounting
    `down_since`/`down_us` is per-node and cannot track two concurrent
    spells); a middleware-side PARTITION/DEGRADE claims the mw<->b link; a
    mesh row claims the undirected a<->b link."""
    if kind == KIND_CRASH:
        return (("ds", a), ("mw", a))
    if a == MW:
        return (("mw", b),)
    return (("mesh", min(a, b), max(a, b)),)


def _validate_cell_faults(i: int, val, num_ds: int) -> tuple:
    """Normalize + validate one cell's fault schedule at Grid construction.

    Rows are typed 6-tuples ``(t_start_us, kind, endpoint_a, endpoint_b,
    t_end_us, severity)`` with ``kind`` in {KIND_CRASH, KIND_PARTITION,
    KIND_DEGRADE} and ``endpoint_a == MW`` (-1) selecting the middleware
    side of a link; legacy ``(t_crash_us, ds, t_recover_us)`` crash triples
    are accepted and widened. Returns the schedule normalized to a tuple of
    6-tuples. Pad rows (t_start >= INF_US) are kept but skipped by the
    semantic checks. Raises ValueError with the offending cell index for
    malformed rows, unknown kinds, out-of-range endpoints, end-before-start,
    non-positive DEGRADE severity, or overlapping intervals on one
    link/node (see `_fault_row_resources`).
    """
    if not isinstance(val, (list, tuple)):
        raise ValueError(
            f"Grid cell {i}: faults must be a sequence of "
            f"(t_crash_us, ds, t_recover_us) triples or typed "
            f"(t_start_us, kind, endpoint_a, endpoint_b, t_end_us, severity) "
            f"rows, got {type(val).__name__}"
        )
    rows = []
    live = {}  # resource key -> list of ((start, end), row index)
    for j, r in enumerate(val):
        if not isinstance(r, (list, tuple)) or len(r) not in (3, FAULT_COLS):
            raise ValueError(
                f"Grid cell {i}: faults row {j} must be a "
                f"(t_crash_us, ds, t_recover_us) triple or a "
                f"(t_start_us, kind, endpoint_a, endpoint_b, t_end_us, "
                f"severity) 6-tuple, got {r!r}"
            )
        if len(r) == 3:
            crash, ds, rec = (int(x) for x in r)
            start, kind, a, b, end, sev = crash, KIND_CRASH, ds, ds, rec, 0
        else:
            start, kind, a, b, end, sev = (int(x) for x in r)
        rows.append((start, kind, a, b, end, sev))
        if start >= INF_US:
            continue  # pad row — never fires inside the horizon
        if kind not in (KIND_CRASH, KIND_PARTITION, KIND_DEGRADE):
            raise ValueError(
                f"Grid cell {i}: faults row {j} has unknown kind={kind} "
                f"(crash={KIND_CRASH}, partition={KIND_PARTITION}, "
                f"degrade={KIND_DEGRADE})"
            )
        if kind == KIND_CRASH:
            if not 0 <= a < num_ds:
                raise ValueError(
                    f"Grid cell {i}: faults row {j} targets ds={a}, out of "
                    f"range for num_ds={num_ds}"
                )
        else:
            if a != MW and not 0 <= a < num_ds:
                raise ValueError(
                    f"Grid cell {i}: faults row {j} endpoint_a={a} is "
                    f"neither MW (-1) nor a ds in range for num_ds={num_ds}"
                )
            if not 0 <= b < num_ds:
                raise ValueError(
                    f"Grid cell {i}: faults row {j} endpoint_b={b}, out of "
                    f"range for num_ds={num_ds}"
                )
            if a == b:
                raise ValueError(
                    f"Grid cell {i}: faults row {j} links ds={a} to itself"
                )
        if end <= start:
            raise ValueError(
                f"Grid cell {i}: faults row {j} "
                + (
                    f"recovers at {end}us, which is not after its crash "
                    f"at {start}us"
                    if kind == KIND_CRASH
                    else f"ends at {end}us, which is not after its start "
                    f"at {start}us"
                )
            )
        if kind == KIND_DEGRADE and sev <= 0:
            raise ValueError(
                f"Grid cell {i}: faults row {j} is a degrade with "
                f"severity={sev}; need a positive milli-scale RTT "
                f"multiplier (e.g. 3000 = 3x)"
            )
        for res in _fault_row_resources(kind, a, b):
            for (c0, r0), j0 in live.get(res, ()):
                if start < r0 and c0 < end:
                    what = "ds" if res[0] == "ds" else "link"
                    name = res[1] if len(res) == 2 else f"{res[1]}<->{res[2]}"
                    raise ValueError(
                        f"Grid cell {i}: faults rows {j0} and {j} overlap "
                        f"on {what}={name} ([{c0}, {r0}) vs "
                        f"[{start}, {end}) us)"
                    )
            live.setdefault(res, []).append(((start, end), j))
    return tuple(rows)


# axes dropped from tabulated rows (per-DS arrays don't tabulate; rtt_ms is
# kept — figures label cells by it)
_NON_LABEL_AXES = ("tau_true_us", "exec_scale_milli", "faults", "replica_tau")


def _row_labels(cell: dict) -> dict:
    """A cell's tabulation labels — single source for Grid.labels and
    RunResult.rows."""
    return {k: v for k, v in cell.items() if k not in _NON_LABEL_AXES}


def _bank_shapes(bank) -> tuple:
    return tuple(
        (getattr(x, "shape", None), str(getattr(x, "dtype", type(x).__name__)))
        for x in jax.tree_util.tree_leaves(bank)
    )


class Grid:
    """A validated evaluation grid: cells × (optional) per-cell Banks.

    `cells` is a list of dicts. Required key: ``preset`` (a name from
    `protocol.PRESETS` or a `ProtocolConfig`). Optional engine axes:
    ``rtt_ms``, ``tau_true_us``, ``jitter_milli``, ``exec_scale_milli``,
    ``seed``, ``faults``. Any other key is a free-form label carried into
    `RunResult.rows()` (figure axes like ``theta`` or ``level``).

    ``faults`` is a deterministic fault schedule: a sequence of typed
    ``(t_start_us, kind, endpoint_a, endpoint_b, t_end_us, severity)`` rows
    (kind in {crash, partition, degrade}; ``endpoint_a == MW`` (-1) selects
    the middleware side of a link; legacy ``(t_crash_us, ds, t_recover_us)``
    crash triples still accepted; pad rows: ``(INF_US, 0, INF_US)``).
    Schedules are validated at construction (kind/endpoint ranges, end after
    start, no overlapping intervals per link/node) and must have the same
    row count in every cell — the schedule is a static engine axis
    (`SimConfig.max_faults`), derived per grid by the `Simulator`.
    ``replica_tau`` (per-DS replica-link RTT vector, INF_US = no replica)
    and ``repl_lag_us`` enable read-only replica failover during outages.
    ``clock_skew_us`` is the worst-case middleware<->DS clock offset the
    ``tiga`` preset's synchronized-clock fast path must absorb (a
    non-negative integer; irrelevant to the other presets).

    NOTE: an unset ``jitter_milli`` defaults to **30** (±3% one-way jitter —
    the historical `run_sweep` cell default, kept for baseline
    compatibility), whereas bare `make_world` defaults to 0; set it
    explicitly when porting `make_world` calls that relied on zero jitter.

    Construction validates EVERY cell — `run_sweep`'s old behavior of
    inferring shapes from ``cells[0]`` silently produced wrong-shaped worlds
    for heterogeneous grids; a bad cell now raises with its index.

    >>> g = Grid.cross(preset=("ssp", "geotp"), seed=(0, 1))
    >>> len(g), g.cells[0], g.cells[3]  # later axes vary fastest
    (4, {'preset': 'ssp', 'seed': 0}, {'preset': 'geotp', 'seed': 1})

    A flat sequence on a vector axis (``rtt_ms``/``tau_true_us``/
    ``exec_scale_milli``) is ONE value; a sequence of sequences sweeps it:

    >>> g2 = Grid.zipped(preset="geotp", rtt_ms=((0.0, 30.0), (0.0, 90.0)))
    >>> len(g2), g2.cells[1]["rtt_ms"]
    (2, (0.0, 90.0))

    Bad cells raise with their index at construction, not at run time:

    >>> Grid([{"preset": "ssp"}, {"preset": "nope"}])
    Traceback (most recent call last):
        ...
    ValueError: Grid cell 1: unknown preset 'nope' (known: ['chiller', 'fastc', 'geotp', 'geotp-o1', 'geotp-o1o2', 'opta', 'quro', 'scalardb', 'ssp', 'ssp-local', 'tiga', 'yugabyte-like'])
    """

    def __init__(self, cells, *, banks=None, default_rtt_ms=None):
        if default_rtt_ms is None:
            default_rtt_ms = PAPER_RTT_MS
        cells = [dict(c) for c in cells]
        if not cells:
            raise ValueError("Grid needs at least one cell")
        self.default_rtt_ms = tuple(default_rtt_ms)
        self.cells = cells
        self.banks = list(banks) if banks is not None else None
        self.num_ds = _cell_num_ds(cells[0], default_rtt_ms)
        for i, c in enumerate(cells):
            preset = c.get("preset")
            if preset is None:
                raise ValueError(f"Grid cell {i}: missing required key 'preset'")
            if isinstance(preset, str):
                if preset not in PRESETS:
                    raise ValueError(
                        f"Grid cell {i}: unknown preset {preset!r} "
                        f"(known: {sorted(PRESETS)})"
                    )
            elif not isinstance(preset, ProtocolConfig):
                raise ValueError(
                    f"Grid cell {i}: preset must be a PRESETS name or a "
                    f"ProtocolConfig, got {type(preset).__name__}"
                )
            nd = _cell_num_ds(c, default_rtt_ms)
            if nd != self.num_ds:
                raise ValueError(
                    f"Grid cell {i}: num_ds={nd} (from "
                    f"{'tau_true_us' if c.get('tau_true_us') is not None else 'rtt_ms'})"
                    f" differs from cell 0's num_ds={self.num_ds} — "
                    "heterogeneous grids must be split into separate sweeps"
                )
            if c.get("faults") is not None:
                c["faults"] = _validate_cell_faults(i, c["faults"], self.num_ds)
            rt = c.get("replica_tau")
            if rt is not None and len(rt) != self.num_ds:
                raise ValueError(
                    f"Grid cell {i}: replica_tau has {len(rt)} entries, "
                    f"need one per data source (num_ds={self.num_ds}; use "
                    f"INF_US for data sources without a replica)"
                )
            skew = c.get("clock_skew_us")
            if skew is not None and (
                not isinstance(skew, int) or isinstance(skew, bool) or skew < 0
            ):
                raise ValueError(
                    f"Grid cell {i}: clock_skew_us must be a non-negative "
                    f"integer (microseconds of worst-case clock offset), "
                    f"got {skew!r}"
                )
        # the fault axis is static-shaped: every cell must carry the same
        # number of schedule rows (F) so the worlds stack into one batch
        fault_cells = [i for i, c in enumerate(cells) if c.get("faults") is not None]
        if fault_cells:
            i0 = fault_cells[0]
            self.max_faults = len(cells[i0]["faults"])
            for i, c in enumerate(cells):
                f = c.get("faults")
                if f is None:
                    raise ValueError(
                        f"Grid cell {i}: no fault schedule, but cell {i0} "
                        f"has {self.max_faults} rows — fault schedules are a "
                        "static axis; give every cell a schedule (pad "
                        "fault-free cells with (INF_US, 0, INF_US) rows)"
                    )
                if len(f) != self.max_faults:
                    raise ValueError(
                        f"Grid cell {i}: fault schedule has {len(f)} rows "
                        f"but cell {i0} has {self.max_faults} — pad shorter "
                        "schedules with (INF_US, 0, INF_US) rows so every "
                        "cell shares one static shape"
                    )
        else:
            self.max_faults = 0
        if self.banks is not None:
            if len(self.banks) != len(cells):
                raise ValueError(
                    f"Grid: {len(self.banks)} banks for {len(cells)} cells "
                    "(need exactly one bank per cell)"
                )
            ref = _bank_shapes(self.banks[0])
            for i, b in enumerate(self.banks):
                if _bank_shapes(b) != ref:
                    raise ValueError(
                        f"Grid bank {i}: leaf shapes/dtypes differ from bank 0 "
                        "(all per-cell banks must share one shape so they "
                        "stack into a single batched sweep)"
                    )

    # ---- builders ---------------------------------------------------------

    @staticmethod
    def _axis_values(key: str, val) -> list:
        """One axis -> list of per-cell values. Strings and scalars are a
        single value; for the vector axes (rtt_ms, ...) a flat sequence of
        numbers is ONE value, a sequence of sequences is a swept axis. For
        ``faults`` a sequence of (crash, ds, recover) triples is ONE
        schedule; a sequence of such schedules sweeps the axis."""
        if val is None:
            return [None]
        if isinstance(val, (str, ProtocolConfig)):
            return [val]
        if not isinstance(val, (list, tuple)):
            return [val]  # scalar
        if key == "faults":
            # one schedule is depth-2 (rows of numbers); a sweep is depth-3
            if len(val) > 0 and isinstance(val[0], (list, tuple)) and (
                len(val[0]) > 0 and isinstance(val[0][0], (list, tuple))
            ):
                return [tuple(tuple(r) for r in sched) for sched in val]
            return [tuple(tuple(r) if isinstance(r, (list, tuple)) else r
                          for r in val)]
        if key in _VECTOR_AXES:
            if len(val) > 0 and isinstance(val[0], (list, tuple)):
                return list(val)
            return [tuple(val)]
        return list(val)

    @classmethod
    def cross(cls, *, banks=None, default_rtt_ms=None, **axes) -> "Grid":
        """Cross product of every axis, in the given key order (later axes
        vary fastest): ``Grid.cross(preset=("ssp", "geotp"), seed=(0, 1))``
        yields ssp/0, ssp/1, geotp/0, geotp/1."""
        keys = list(axes)
        lists = [cls._axis_values(k, axes[k]) for k in keys]
        cells = [
            {k: v for k, v in zip(keys, combo) if v is not None}
            for combo in itertools.product(*lists)
        ]
        return cls(cells, banks=banks, default_rtt_ms=default_rtt_ms)

    @classmethod
    def zipped(cls, *, banks=None, default_rtt_ms=None, **axes) -> "Grid":
        """Zip axes elementwise (all the same length): cell i takes value i
        of every axis. Scalars broadcast to every cell."""
        keys = list(axes)
        lists = [cls._axis_values(k, axes[k]) for k in keys]
        n = max((len(v) for v in lists), default=0)
        for k, v in zip(keys, lists):
            if len(v) not in (1, n):
                raise ValueError(
                    f"Grid.zipped: axis {k!r} has {len(v)} values, expected "
                    f"1 or {n}"
                )
        lists = [v * n if len(v) == 1 else v for v in lists]
        cells = [
            {k: v[i] for k, v in zip(keys, lists) if v[i] is not None}
            for i in range(n)
        ]
        return cls(cells, banks=banks, default_rtt_ms=default_rtt_ms)

    def with_banks(self, banks) -> "Grid":
        return Grid(self.cells, banks=banks, default_rtt_ms=self.default_rtt_ms)

    # ---- views ------------------------------------------------------------

    def __len__(self) -> int:
        return len(self.cells)

    def __iter__(self):
        return iter(self.cells)

    def world(self, i: int) -> WorldSpec:
        c = self.cells[i]
        rtt = c.get("rtt_ms")
        return make_world(
            c["preset"],
            rtt if rtt is not None else self.default_rtt_ms,
            tau_true_us=c.get("tau_true_us"),
            jitter_milli=c.get("jitter_milli", 30),
            exec_scale_milli=c.get("exec_scale_milli"),
            seed=c.get("seed", 0),
            faults=c.get("faults"),
            max_faults=self.max_faults,
            replica_tau=c.get("replica_tau"),
            repl_lag_us=c.get("repl_lag_us", 0),
            clock_skew_us=c.get("clock_skew_us", 0),
        )

    def worlds(self) -> WorldSpec:
        """All cells stacked into one WorldSpec with a leading [B] axis."""
        return stack_worlds([self.world(i) for i in range(len(self.cells))])

    def bank_stack(self):
        """Per-cell banks stacked along a leading [B] axis (banks required)."""
        if self.banks is None:
            raise ValueError("Grid has no per-cell banks")
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *self.banks)

    def labels(self, i: int) -> dict:
        """Cell i's row labels: every non-vector cell key (preset included)."""
        return _row_labels(self.cells[i])


# ---------------------------------------------------------------------------
# RunResult
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunResult:
    """Structured output of `Simulator.run` / `Simulator.run_grid`.

    `states` carries the full final engine state (batched over cells for grid
    runs) — everything needed to resume, slice histograms or extract custom
    telemetry; `metrics` is one `summarize` dict per cell.

    Consume a grid result by rows (labels merged with metrics), per-cell
    final states, or the aggregated windowed-drain telemetry:

    >>> from repro.core import workloads
    >>> bank = workloads.make_ycsb_bank(
    ...     workloads.YCSBConfig(num_ds=2, records_per_node=64, ops_per_txn=2),
    ...     terminals=2, txns_per_terminal=8)
    >>> sim = Simulator.from_bank(bank, horizon_s=0.2, warmup_s=0.0)
    >>> res = sim.run_grid(
    ...     Grid.cross(preset=("ssp", "geotp"), rtt_ms=(0.0, 10.0)), bank)
    >>> [r["preset"] for r in res.rows()]
    ['ssp', 'geotp']
    >>> sorted(res.rows()[0])[:3]
    ['abort_rate', 'aborts', 'avg_latency_dist_ms']
    >>> res.world(1).now.ndim  # one cell's final SimState
    0
    >>> sorted(res.drain)  # doctest: +NORMALIZE_WHITESPACE
    ['abort_causes', 'availability', 'commits_during_fault',
     'drain_hit_rate', 'drained_events', 'events', 'failovers',
     'fast_commits', 'link_downtime_us', 'loop_iters', 'max_staleness_us',
     'mean_window_len', 'plan_fused', 'seq_events', 'stale_reads',
     'wan_rounds', 'window_stops', 'windows']
    >>> res.drain["availability"]  # fault-free run: every DS up throughout
    1.0
    """

    cfg: SimConfig
    states: Any  # SimState, leaves [B, ...] when batched
    metrics: list
    cells: list  # one label dict per cell ([] -> [{}] for single runs)
    strategy: str  # as requested ("auto" preserved — recorded in .save)
    # seconds of the device call, the host copy and the summaries (the
    # `repro.device/gather/summarize` spans; compile included on a cold cache)
    wall_s: float
    bank: Any = None
    bank_batched: bool = False
    batched: bool = True
    # what the placement layer actually ran: the concrete strategy "auto"
    # resolved to (map / vmap / mesh) and the mesh device count (1 off-mesh)
    # — recorded in .save so BENCH entries distinguish map/vmap/mesh runs
    strategy_resolved: str = ""
    mesh_devices: int = 1
    phases: dict = dataclasses.field(default_factory=dict)  # span -> seconds

    # ---- accessors --------------------------------------------------------

    def __len__(self) -> int:
        return len(self.metrics)

    @property
    def events(self) -> int:
        return sum(m["events"] for m in self.metrics)

    @property
    def drain(self) -> dict:
        """Windowed-drain + fault telemetry aggregated over every cell.

        Passes the run horizon so `availability` charges a DS still down at
        the end for its open outage up to the horizon, not just to the last
        processed event."""
        return drain_stats(self.states, horizon_us=self.cfg.horizon_us)

    def world(self, i: int):
        """Final SimState of cell i."""
        if not self.batched:
            if i != 0:
                raise IndexError(f"single-world result has no cell {i}")
            return self.states
        return world_index(self.states, i)

    def rows(self) -> list:
        """One dict per cell: the cell's labels merged with its metrics
        (vector-valued axes dropped — they don't tabulate)."""
        return [
            {**_row_labels(cell), **m}
            for cell, m in zip(self.cells, self.metrics)
        ]

    def with_states(self, states) -> "RunResult":
        """Copy with substituted states (e.g. after editing `tau_true` for an
        online-reconfiguration segment, before `Simulator.resume`)."""
        return dataclasses.replace(self, states=states)

    def save(self, tag: str, path=None) -> dict:
        """Record this run under ``sweeps.<tag>`` in the bench JSON.

        Writes the exact legacy schema (worlds/terminals/events/wall_s/
        events_per_sec/strategy/horizon_s + drain telemetry) so stored
        baselines and the smoke-guard comparisons keep working, plus the jax
        runtime environment keys, the per-stopper window-termination counts,
        whether the fused lockstep plan ran, the *resolved* placement
        (`strategy_resolved` / `mesh_devices` — `strategy` stays the
        requested string, so "auto" entries still say what actually ran),
        and the fault telemetry (availability / abort-cause breakdown /
        commits during outages / per-link downtime / replica failovers +
        stale reads — see docs/benchmarks.md).
        """
        d = self.drain
        entry = {
            "worlds": len(self.metrics),
            "terminals": self.cfg.terminals,
            "events": self.events,
            "wall_s": round(self.wall_s, 2),
            "events_per_sec": round(self.events / max(self.wall_s, 1e-9), 1),
            "strategy": self.strategy,
            "strategy_resolved": self.strategy_resolved or self.strategy,
            "mesh_devices": self.mesh_devices,
            "horizon_s": self.cfg.horizon_us / 1e6,
            "drain_hit_rate": d["drain_hit_rate"],
            "mean_window_len": d["mean_window_len"],
            "loop_iters": d["loop_iters"],
            "window_stops": d["window_stops"],
            "plan_fused": d["plan_fused"],
            "availability": d["availability"],
            "abort_causes": d["abort_causes"],
            "commits_during_fault": d["commits_during_fault"],
            "link_downtime_us": d["link_downtime_us"],
            "stale_reads": d["stale_reads"],
            "failovers": d["failovers"],
            "max_staleness_us": d["max_staleness_us"],
            "wan_rounds": d["wan_rounds"],
            "fast_commits": d["fast_commits"],
        }
        return record_bench(tag, entry, path)


# ---------------------------------------------------------------------------
# Simulator
# ---------------------------------------------------------------------------


class Simulator:
    """Facade over the compiled engine, fixed to one set of static shapes.

    Shapes + horizon live in `self.cfg` (the jit compile key, protocol
    excluded); per-run dynamics (preset knobs, latency matrices, jitter,
    seeds) arrive as `WorldSpec`s / `Grid`s. The run callables
    (`batch._sim_world_fresh` / `_sim_batch_fresh` / `_run_batch` / `_run_jit`)
    are jitted with `cfg` and the strategy as static arguments, so every call
    is compile-cached per (shape-key, strategy) process-wide: two Simulators
    with equal shapes share one compilation, and a preset×latency×seed grid
    compiles once per shape, not once per cell.

    The quickstart (shapes inferred from the Bank, default paper RTTs):

    >>> from repro.core import workloads
    >>> bank = workloads.make_ycsb_bank(
    ...     workloads.YCSBConfig(num_ds=2, records_per_node=64, ops_per_txn=2),
    ...     terminals=2, txns_per_terminal=8)
    >>> sim = Simulator.from_bank(bank, horizon_s=0.2, warmup_s=0.0)
    >>> grid = Grid.cross(preset=("ssp", "geotp"), rtt_ms=(0.0, 10.0))
    >>> res = sim.run_grid(grid, bank)  # ONE batched device call
    >>> len(res), res.metrics[0]["noops"]
    (2, 0)
    >>> res.metrics[0]["commits"] > 0
    True

    Continue the same cells to a longer horizon (donates the state buffers):

    >>> res2 = sim.resume(res, horizon_s=0.4)
    >>> res2.metrics[0]["events"] >= res.metrics[0]["events"]
    True
    """

    def __init__(
        self,
        terminals: int,
        max_ops: int,
        num_ds: int,
        bank_txns: int,
        *,
        proto="geotp",
        horizon_s: float = 10.0,
        warmup_s: float = 2.0,
        drain: bool = True,
        track_slots: bool = False,
        hot_capacity: int = 1024,
    ):
        if isinstance(proto, str):
            proto = PRESETS[proto]
        self.cfg = SimConfig(
            terminals=terminals,
            max_ops=max_ops,
            num_ds=num_ds,
            bank_txns=bank_txns,
            proto=proto,
            hot_capacity=hot_capacity,
            warmup_us=int(warmup_s * 1e6),
            horizon_us=int(horizon_s * 1e6),
            drain=drain,
            track_slots=track_slots,
        )

    @classmethod
    def from_bank(cls, bank, terminals: int | None = None, **kw) -> "Simulator":
        """Infer shapes from a Bank: key is [T, N, K], num_ds from the Bank."""
        T, N, K = bank.key.shape
        return cls(terminals or T, K, bank.num_ds, N, **kw)

    # ---- internals --------------------------------------------------------

    def _check_bank(self, bank, batched: bool) -> None:
        shape = bank.key.shape[1:] if batched else bank.key.shape
        want = (self.cfg.terminals, self.cfg.bank_txns, self.cfg.max_ops)
        if tuple(shape) != want:
            raise ValueError(
                f"bank.key shape {tuple(shape)} != (terminals, bank_txns, "
                f"max_ops) = {want} of this Simulator"
            )
        # num_ds is a python int on a plain Bank but a stacked [B] array on a
        # per-cell bank batch — compare elementwise either way
        nd = jnp.asarray(bank.num_ds)
        if not bool(jnp.all(nd == self.cfg.num_ds)):
            raise ValueError(
                f"bank.num_ds={bank.num_ds} != Simulator num_ds={self.cfg.num_ds}"
            )

    def _cfg_for(self, faults) -> SimConfig:
        """The static config for one run: `max_faults` follows the worlds'
        schedule shape ([..., F, 3]), so fault-free runs compile the exact
        tail-free program and fault runs recompile once per distinct F."""
        F = int(faults.shape[-2])
        if F == self.cfg.max_faults:
            return self.cfg
        return dataclasses.replace(self.cfg, max_faults=F)

    # ---- entry points -----------------------------------------------------

    def run(self, world: WorldSpec, bank, *, labels: dict | None = None) -> RunResult:
        """Run ONE world (fused init+run, the scalar map-style path)."""
        self._check_bank(bank, batched=False)
        cfg = self._cfg_for(world.faults)
        phases: dict = {}
        with span("repro.device", phases):
            states = jax.block_until_ready(_sim_world_fresh(cfg, bank, world))
        [m] = summarize_batch(cfg, states, phases)
        assert m["noops"] == 0, ("noop event fired", m["noops"])
        return RunResult(
            cfg=cfg,
            states=states,
            metrics=[m],
            cells=[dict(labels or {})],
            strategy="map",
            wall_s=wall_s(phases),
            bank=bank,
            bank_batched=False,
            batched=False,
            strategy_resolved="map",
            mesh_devices=1,
            phases=phases,
        )

    def run_grid(
        self,
        grid: Grid,
        bank=None,
        *,
        strategy: str = "auto",
        mesh_devices: int | None = None,
    ) -> RunResult:
        """Run every cell of a Grid as ONE batched device call.

        `bank` is shared by every cell unless the Grid carries per-cell banks.
        `strategy` picks the placement — "map" / "vmap" / "mesh" (grid cells
        sharded over a 1-D jax device mesh) / "auto" (resolved by
        `placement.resolve_strategy`); `mesh_devices` optionally caps the
        mesh device count (default: every visible device). All strategies are
        bitwise-identical per cell to per-cell `run` (asserted in
        tests/core/test_api.py and tests/core/test_placement.py). The
        result's `phases` holds the seconds of the call's spans (`spans`).
        """
        phases: dict = {}  # the result's `phases`; each span adds to it on exit
        with span("repro.run_grid", phases):
            if grid.num_ds != self.cfg.num_ds:
                raise ValueError(
                    f"grid num_ds={grid.num_ds} != Simulator num_ds={self.cfg.num_ds}"
                )
            with span("repro.stack", phases):
                if grid.banks is not None:
                    bank = grid.bank_stack()
                elif bank is None:
                    raise ValueError("run_grid needs a shared bank or a Grid with banks")
                bank_batched = grid.banks is not None
                self._check_bank(bank, batched=bank_batched)
                worlds = grid.worlds()
            cfg = self._cfg_for(worlds.faults)
            resolved = resolve_strategy(strategy)
            ndev = mesh_device_count(resolved, mesh_devices)
            states, metrics = simulate_batch(
                cfg, bank, worlds, bank_batched=bank_batched, strategy=resolved,
                mesh_devices=ndev, phases=phases,
            )
            for i, m in enumerate(metrics):
                assert m["noops"] == 0, (f"grid cell {i}", grid.cells[i], m["noops"])
            return RunResult(
                cfg=cfg,
                states=states,
                metrics=metrics,
                cells=[dict(c) for c in grid.cells],
                strategy=strategy,
                wall_s=wall_s(phases),
                bank=bank,
                bank_batched=bank_batched,
                batched=True,
                strategy_resolved=resolved,
                mesh_devices=ndev,
                phases=phases,
            )

    def resume(
        self,
        result: RunResult,
        *,
        horizon_s: float | None = None,
        warmup_s: float | None = None,
        strategy: str | None = None,
        mesh_devices: int | None = None,
    ) -> RunResult:
        """Continue a finished run's states (batched continuations donate the
        state buffers — `result.states` must not be reused afterwards; mesh
        continuations re-place the donated states on the worlds mesh).

        `horizon_s` extends the absolute horizon (a continuation with the old
        horizon is a no-op: every pending event already lies beyond it);
        `warmup_s` re-gates the metric warmup for the continued span. The
        placement defaults to the original run's: same requested strategy,
        same mesh device count.
        """
        cfg = result.cfg
        # round, don't truncate: horizon_s often arrives as now/1e6 + delta,
        # and float error would otherwise clip the boundary microsecond
        if horizon_s is not None:
            cfg = dataclasses.replace(cfg, horizon_us=round(horizon_s * 1e6))
        if warmup_s is not None:
            cfg = dataclasses.replace(cfg, warmup_us=round(warmup_s * 1e6))
        strategy = strategy if strategy is not None else result.strategy
        resolved = resolve_strategy(strategy)
        if mesh_devices is None and resolved == "mesh" and result.mesh_devices > 1:
            mesh_devices = result.mesh_devices
        ndev = mesh_device_count(resolved, mesh_devices)
        phases: dict = {}
        if result.batched:
            states, metrics = simulate_batch(
                cfg,
                result.bank,
                None,  # worlds unused on the continuation path
                bank_batched=result.bank_batched,
                states=result.states,
                strategy=resolved,
                mesh_devices=ndev,
                phases=phases,
            )
        else:
            with span("repro.device", phases):
                states = jax.block_until_ready(_run_jit(cfg, result.bank, result.states))
            metrics = summarize_batch(cfg, states, phases)
            resolved, ndev = "map", 1
        return RunResult(
            cfg=cfg,
            states=states,
            metrics=metrics,
            cells=result.cells,
            strategy=strategy,
            wall_s=wall_s(phases),
            bank=result.bank,
            bank_batched=result.bank_batched,
            batched=result.batched,
            strategy_resolved=resolved,
            mesh_devices=ndev,
            phases=phases,
        )
