"""Host-side metric extraction of the reference (a copy of the program's
`summarize`)."""

from __future__ import annotations

import numpy as np

from bench.ref.state import HIST_BINS, _HIST_BASE_US, SimConfig, SimState


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
