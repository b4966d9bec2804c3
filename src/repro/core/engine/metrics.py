"""Host-side metric extraction: summaries, drain telemetry, CDFs."""

from __future__ import annotations

import jax
import numpy as np

from repro.core.engine.state import (
    ABORT_CAUSES,
    HIST_BINS,
    STOP_REASONS,
    _HIST_BASE_US,
    SimConfig,
    SimState,
)
from repro.core.engine.spans import span

def world_index(states: SimState, i: int) -> SimState:
    """Slice world i out of a batched final state."""
    return jax.tree_util.tree_map(lambda x: x[i], states)


def summarize_batch(cfg: SimConfig, states: SimState, phases: dict | None = None) -> list:
    """Host-side metric extraction: copy the final state (batched over
    worlds, or one world's) to the host, then one `summarize` dict per
    world; the two steps are the `repro.gather` and `repro.summarize`
    spans, timed into ``phases``."""
    phases = {} if phases is None else phases
    with span("repro.gather", phases):
        host = jax.tree_util.tree_map(np.asarray, states)
    with span("repro.summarize", phases):
        if host.now.ndim == 0:  # one world's state
            return [summarize(cfg, host)]
        return [summarize(cfg, world_index(host, i)) for i in range(host.now.shape[0])]


def summarize(cfg: SimConfig, s: SimState) -> dict:
    """Host-side metric extraction."""
    span_s = max((cfg.horizon_us - cfg.warmup_us) / 1e6, 1e-9)
    commits = int(s.commits)
    aborts = int(s.aborts)
    hist = np.asarray(s.hist_all)
    lat_p = _percentiles(hist, (0.5, 0.99, 0.999))
    cen = _percentiles(np.asarray(s.hist_cen), (0.5, 0.99))
    dst = _percentiles(np.asarray(s.hist_dist), (0.5, 0.99))
    return {
        "throughput_tps": commits / span_s,
        "commits": commits,
        "aborts": aborts,
        "abort_rate": aborts / max(commits + aborts, 1),
        "avg_latency_ms": int(s.lat_sum) / max(commits, 1),
        "avg_latency_dist_ms": int(s.lat_sum_dist) / max(int(s.commits_dist), 1),
        "p50_ms": lat_p[0],
        "p99_ms": lat_p[1],
        "p999_ms": lat_p[2],
        "p50_centralized_ms": cen[0],
        "p99_centralized_ms": cen[1],
        "p50_distributed_ms": dst[0],
        "p99_distributed_ms": dst[1],
        "avg_lcs_ms": int(s.lcs_sum) / max(int(s.lcs_cnt), 1),
        "noops": int(s.noops),
        "events": int(s.iters),
        "sim_end_s": float(s.now) / 1e6,
    }


def drain_stats(state: SimState, horizon_us: int | None = None) -> dict:
    """Windowed-drain + fault telemetry for a final state (single or batched).

    Deliberately NOT part of `summarize`: the metric dicts there are part of
    the bitwise drain-vs-sequential contract, while the hit rate by
    construction differs between the two paths.

    `loop_iters` is the actual `lax.while_loop` trip count: sequential events
    take one iteration each, a whole window takes one iteration.
    `window_stops` counts, per stop reason, why each applied window ended
    (see `state.STOP_REASONS`); `chained` counts the follow-up events the
    two-pass plan admitted across the scheduling fence (each drained with its
    sequential salt/timestamp); `plan_fused` reports whether any lane ran the
    fused plan+omnibus lockstep pass (`fused._omni_window`).

    Fault-injection fields: `availability` is the mean fraction of
    (world, data source) wall-clock spent reachable — 1.0 on fault-free
    runs; a DS still crashed OR still partitioned from the middleware at the
    end contributes its open outage up to `horizon_us` (pass
    `SimConfig.horizon_us`; defaults to each world's final clock).
    `link_downtime_us` is the same charge per middleware<->DS link, summed
    across worlds. `abort_causes` breaks measured aborts down by first cause
    (see `state.ABORT_CAUSES`) and `commits_during_fault` counts commits
    measured while at least one DS was unreachable (goodput under degraded
    service). `failovers` counts subtxns routed to a replica while their
    primary was unreachable, `stale_reads` the read-only statements those
    served, and `max_staleness_us` the worst staleness window any such read
    observed (outage age at dispatch + configured replication lag).

    Protocol-zoo fields: `wan_rounds` is the total middleware<->DS WAN
    round-trip count (one-way legs / 2, receive-side charged from t=0 —
    statement delivery, round replies, 2PC prepare/vote, commit/abort
    command + ack; local commits and early-abort mesh notifications charge
    nothing), the protocol-efficiency metric behind the fig18 head-to-head
    sweeps. `fast_commits` counts round completions that landed directly in
    a DS-local commit (YugabyteDB-style centralized fast path, FASTC
    co-coordinator commit, TIGA in-slack single-round commit).
    """
    events = int(np.sum(np.asarray(state.iters)))
    drained = int(np.sum(np.asarray(state.drained)))
    windows = int(np.sum(np.asarray(state.windows)))
    stops = np.asarray(state.win_stops).reshape(-1, len(STOP_REASONS)).sum(axis=0)
    causes = np.asarray(state.ab_cause).reshape(-1, len(ABORT_CAUSES)).sum(axis=0)
    down_us = np.asarray(state.down_us, dtype=np.int64)
    ds_down = np.asarray(state.ds_down)
    down_since = np.asarray(state.down_since, dtype=np.int64)
    if horizon_us is None:
        end = np.asarray(state.now, dtype=np.int64)[..., None]  # per world
    else:
        end = np.int64(horizon_us)
    # open outage: crashed, or mw-link still severed past the end of the run
    mw_heal = np.asarray(state.mw_heal, dtype=np.int64)
    still_cut = ds_down | (mw_heal > end)
    total_down = down_us + np.where(still_cut, np.maximum(end - down_since, 0), 0)
    wall = np.broadcast_to(end, total_down.shape)
    avail = 1.0 - float(total_down.sum()) / max(float(wall.sum()), 1.0)
    link_down = total_down.reshape(-1, total_down.shape[-1]).sum(axis=0)
    return {
        "events": events,
        "drained_events": drained,
        "seq_events": events - drained,
        "drain_hit_rate": round(drained / max(events, 1), 4),
        "windows": windows,
        "mean_window_len": round(drained / max(windows, 1), 2),
        "loop_iters": (events - drained) + windows,
        "window_stops": {r: int(c) for r, c in zip(STOP_REASONS, stops)},
        "chained": int(np.sum(np.asarray(state.chained))),
        "plan_fused": bool(np.sum(np.asarray(state.fused)) > 0),
        "availability": round(avail, 6),
        "abort_causes": {r: int(c) for r, c in zip(ABORT_CAUSES, causes)},
        "commits_during_fault": int(np.sum(np.asarray(state.commits_fault))),
        "link_downtime_us": [int(x) for x in link_down],
        "stale_reads": int(np.sum(np.asarray(state.stale_reads))),
        "failovers": int(np.sum(np.asarray(state.failovers))),
        "max_staleness_us": int(np.max(np.asarray(state.max_stale_us))),
        "wan_rounds": int(np.sum(np.asarray(state.wan_legs))) / 2.0,
        "fast_commits": int(np.sum(np.asarray(state.fast_commits))),
    }


def _percentiles(hist: np.ndarray, qs) -> list:
    total = hist.sum()
    out = []
    if total == 0:
        return [float("nan")] * len(qs)
    cum = np.cumsum(hist)
    for q in qs:
        b = int(np.searchsorted(cum, q * total))
        b = min(b, HIST_BINS - 1)
        out.append(_HIST_BASE_US * (2.0 ** ((b + 0.5) / 8.0)) / 1000.0)  # ms
    return out


def latency_cdf(hist: np.ndarray):
    """Returns (latency_ms[bins], cdf[bins]) for CDF plots (Fig 8)."""
    edges = _HIST_BASE_US * (2.0 ** ((np.arange(HIST_BINS) + 1) / 8.0)) / 1000.0
    total = max(hist.sum(), 1)
    return edges, np.cumsum(hist) / total
