"""Network model for geo-distributed deployments.

Models the WAN/LAN topology of the paper's experimental setup (§VII-A-3):
the database middleware (DM) connects to D data sources with heterogeneous
round-trip times (default Beijing/Shanghai/Singapore/London = 0/27/73/251 ms),
plus a DS<->DS matrix used by the early-abort mechanism (geo-agents talk to each
other directly, bypassing the DM).

All times are int32 **microseconds** — the engine runs on a deterministic integer
clock so that every experiment is exactly reproducible (hardware adaptation noted
in DESIGN.md §3).

The latency *monitor* mirrors the paper's implementation (§VI: a thread pings each
data source every 10 ms and the estimate is an exponential weighted moving average,
§VII-D). Here the DM updates the EWMA from every observed round trip; under static
latency the estimate equals the truth, under dynamic latency it lags exactly like
the paper's monitor does.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Sentinel for "no pending event": far beyond any simulation horizon.
INF_US = jnp.int32(2**30)

MS = 1000  # microseconds per millisecond

# Default deployment from the paper (§VII-A-3): client+DM+DS1 in Beijing,
# DS2 Shanghai, DS3 Singapore, DS4 London. RTTs in ms: 0, 27, 73, 251.
PAPER_RTT_MS = (0.0, 27.0, 73.0, 251.0)


class NetParams(NamedTuple):
    """Dynamic (traceable) network parameters.

    tau_dm:  [D]   RTT between DM and each data source, µs.
    tau_ds:  [D,D] RTT between data sources (geo-agent mesh), µs.
    jitter_milli: scalar int32, per-message uniform jitter in 1/1000 fractions of
                  the one-way latency (e.g. 100 = ±10%).
    """

    tau_dm: jax.Array
    tau_ds: jax.Array
    jitter_milli: jax.Array


def make_net_params(
    rtt_ms=PAPER_RTT_MS,
    jitter_frac: float = 0.0,
    tau_ds_ms=None,
) -> NetParams:
    """Build NetParams from RTTs in milliseconds.

    If tau_ds_ms is not given, DS<->DS RTT is approximated by triangle routing
    through geography: |tau_i - tau_j| <= tau_ij <= tau_i + tau_j; we use
    max(|tau_i - tau_j|, min-positive) which matches the linear chain layout of
    the paper's regions (Beijing-Shanghai-Singapore-London).
    """
    tau = jnp.asarray([int(t * MS) for t in rtt_ms], dtype=jnp.int32)
    if tau_ds_ms is None:
        tds = derive_tau_ds_us(tau)
    else:
        tds = jnp.asarray([[int(t * MS) for t in row] for row in tau_ds_ms], dtype=jnp.int32)
    return NetParams(
        tau_dm=tau,
        tau_ds=tds,
        jitter_milli=jnp.int32(int(jitter_frac * 1000)),
    )


def derive_tau_ds_us(tau_us: jax.Array) -> jax.Array:
    """DS<->DS mesh from the DM RTT vector (µs): triangle routing through
    geography, |tau_i - tau_j| <= tau_ij, with a 1ms off-diagonal floor (two
    distinct sites are at least 1ms apart). The single source of the mesh
    derivation — used by make_net_params and engine.make_world."""
    tau_us = jnp.asarray(tau_us, jnp.int32)
    d = tau_us.shape[0]
    tds = jnp.abs(tau_us[:, None] - tau_us[None, :])
    floor = jnp.where(~jnp.eye(d, dtype=bool), jnp.int32(1 * MS), jnp.int32(0))
    return jnp.maximum(tds, floor)


def _hash_u32(x: jax.Array) -> jax.Array:
    """Cheap deterministic integer hash (xorshift-multiply), uint32 -> uint32."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def one_way_delay(net: NetParams, tau_rtt: jax.Array, salt: jax.Array) -> jax.Array:
    """One-way message delay = RTT/2 with deterministic per-message jitter.

    salt: any int32 scalar unique-ish per message (e.g. txn_id*K + hop counter).
    Jitter is uniform in ±jitter_milli/1000 of the one-way time.
    """
    half = tau_rtt // 2
    h = _hash_u32(salt)
    # u in [-1000, 1000)
    u = (h % jnp.uint32(2001)).astype(jnp.int32) - 1000
    jit = (half * net.jitter_milli // 1000) * u // 1000
    return (half + jit).astype(jnp.int32)


# ---------------------------------------------------------------------------
# EWMA latency estimator (the paper's "ping thread" §VI + §VII-D).
# ---------------------------------------------------------------------------


def ewma_update(est: jax.Array, sample: jax.Array, beta_milli: jax.Array) -> jax.Array:
    """est' = beta*est + (1-beta)*sample with beta expressed in 1/1000.

    float32 internally (int32 `est*beta` would overflow for RTTs > ~2 s)."""
    e = est.astype(jnp.float32)
    sm = sample.astype(jnp.float32)
    b = jnp.asarray(beta_milli).astype(jnp.float32) / 1000.0
    return (e * b + sm * (1.0 - b)).astype(jnp.int32)


def ewma_update_where(
    est: jax.Array, sample: jax.Array, beta_milli: jax.Array, mask: jax.Array
) -> jax.Array:
    """Masked EWMA delta: update only where `mask`, keep `est` elsewhere.

    The engine's omnibus masked step applies one monitor update per data
    source with at most one observation per drained timestamp; elementwise
    float32 math keeps it bitwise-equal to `ewma_update` applied per event.
    """
    return jnp.where(mask, ewma_update(est, sample, beta_milli), est)


@dataclasses.dataclass(frozen=True)
class GeoSites:
    """Named multi-region layouts used by benchmarks (Fig 10/11/15)."""

    name: str
    rtt_ms: tuple

    @staticmethod
    def paper_default() -> "GeoSites":
        return GeoSites("beijing-dm", PAPER_RTT_MS)

    @staticmethod
    def mirrored() -> "GeoSites":
        # Fig 15's DM2: latencies 251, 226, 175, 0 (London-side DM).
        return GeoSites("london-dm", (251.0, 226.0, 175.0, 0.0))

    @staticmethod
    def mean_std(mean_ms: float, std_ms: float, d: int = 4) -> "GeoSites":
        # Fig 10: e.g. mean 20 -> 10/20/30 across data nodes (node 0 co-located).
        if d <= 1:
            return GeoSites(f"mean{mean_ms}", (0.0,))
        lats = [0.0] + [
            max(0.0, mean_ms + std_ms * (2.0 * i / max(d - 2, 1) - 1.0)) for i in range(d - 1)
        ]
        return GeoSites(f"mean{mean_ms}-std{std_ms}", tuple(lats))
