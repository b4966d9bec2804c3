"""Property-based differential harness: four-mode bitwise identity.

Random workloads — preset choice, bank contention shape, jitter, zero-RTT
tie density, crash/partition/degrade fault rows, clock skew — must produce
BITWISE-identical final states through all four step modes:

    step   = sequential single-event loop      (lockstep=F, drain=F)
    drain  = map-lane windowed drain           (lockstep=F, drain=T)
    omni   = branchless lockstep, no windows   (lockstep=T, drain=F)
    fused  = fused plan+omnibus lockstep       (lockstep=T, drain=T)

Two tiers:
  * fixed-seed deterministic examples (always run, tier-1): the generator
    below is a pure function of an integer seed, so each case is exactly
    reproducible without hypothesis installed;
  * `@given` generative runs through the same generator (skip without
    hypothesis — scripts/ci.sh asserts they really ran when the [dev]
    extra installed; REQUIRE_HYPOTHESIS=1 turns the skip into a failure),
    with a larger shrinking budget behind `-m slow`.

Compile-cache discipline: `SimConfig` is a static jit argument, so the
generated space draws shapes and presets from small fixed pools — each
(preset, shape, mode) triple compiles once per process and every further
example reuses the cached executable.

The telemetry-conservation suite rides along: window stop reasons must sum
to the window count, chained admissions must bound-check against drained
events, and the map-drain and fused lockstep paths must agree on all drain
telemetry exactly.
"""

import dataclasses

import jax
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, example, given, settings, st
from repro.core import engine, workloads
from repro.core.engine.state import (
    KIND_CRASH,
    KIND_DEGRADE,
    KIND_PARTITION,
    MW,
)
from repro.core.protocols import PRESETS

HORIZON_US = 1_200_000
MAX_FAULTS = 3  # static fault capacity; inert rows start past the horizon

# static pools: every generated case compiles into one of these cache keys
PRESET_POOL = ("ssp", "geotp", "fastc", "tiga")
SHAPE_POOL = ((8, 4, 2, 24), (4, 4, 2, 12))  # (terminals, ops, ds, txns)

# (lockstep, drain) selectors for the four bitwise-interchangeable modes
MODES = {
    "step": (False, False),
    "drain": (False, True),
    "omni": (True, False),
    "fused": (True, True),
}

_INERT_FAULT = (HORIZON_US * 2, KIND_CRASH, 0, 0, HORIZON_US * 2 + 1, 0)


def _params(seed: int) -> dict:
    """Deterministic workload parameters from an integer seed.

    Mirrors the hypothesis strategy below so fixed-seed tier-1 examples and
    generative runs draw from the identical space.
    """
    # RandomState takes seeds below 2**32; the property tier draws up to 2**31-1
    rng = np.random.RandomState((seed * 7919 + 13) % 2**32)
    shape = SHAPE_POOL[int(rng.randint(len(SHAPE_POOL)))]
    _, _, num_ds, _ = shape
    tie_heavy = bool(rng.randint(3) == 0)  # 1/3 of cases: zero-RTT tie storms
    if tie_heavy:
        rtt, jitter = (0.0,) * num_ds, 0
    else:
        rtt = tuple(float(rng.choice([5.0, 10.0, 40.0, 100.0, 150.0]))
                    for _ in range(num_ds))
        jitter = int(rng.choice([0, 30, 100]))
    faults = []
    for _ in range(int(rng.randint(MAX_FAULTS + 1))):
        kind = int(rng.choice([KIND_CRASH, KIND_PARTITION, KIND_DEGRADE]))
        t0 = int(rng.randint(50_000, HORIZON_US - 200_000))
        t1 = t0 + int(rng.randint(100_000, 800_000))
        ds = int(rng.randint(num_ds))
        if kind == KIND_CRASH:
            faults.append((t0, KIND_CRASH, ds, ds, t1, 0))
        elif kind == KIND_PARTITION:
            faults.append((t0, KIND_PARTITION, MW, ds, t1, 0))
        else:
            faults.append((t0, KIND_DEGRADE, MW, ds, t1,
                           int(rng.choice([2000, 5000, 8000]))))
    faults += [_INERT_FAULT] * (MAX_FAULTS - len(faults))
    return dict(
        preset=PRESET_POOL[int(rng.randint(len(PRESET_POOL)))],
        shape=shape,
        bank_seed=int(rng.randint(1000)),
        theta=float(rng.choice([0.5, 0.9, 1.3])),
        dist_ratio=float(rng.choice([0.2, 0.5, 0.9])),
        jitter=jitter,
        rtt=rtt,
        faults=tuple(faults),
        skew=int(rng.choice([0, 0, 50_000, 300_000])),
    )


def _run_case(preset, shape, bank_seed, theta, dist_ratio, jitter, rtt,
              faults, skew):
    """Final states of one generated world through all four step modes."""
    t, k, d, n = shape
    bank = workloads.make_ycsb_bank(
        workloads.YCSBConfig(
            num_ds=d, records_per_node=512, ops_per_txn=k,
            dist_ratio=dist_ratio, theta=theta, seed=bank_seed,
        ),
        terminals=t, txns_per_terminal=n,
    )
    base = engine.SimConfig(
        terminals=t, max_ops=k, num_ds=d, bank_txns=n,
        proto=PRESETS[preset], warmup_us=0, horizon_us=HORIZON_US,
        track_slots=True,  # widen the bitwise fingerprint
        max_faults=MAX_FAULTS,
    )
    w = engine.make_world(
        preset, rtt, jitter_milli=jitter, clock_skew_us=skew,
        faults=faults, max_faults=MAX_FAULTS,
    )
    outs = {}
    for mode, (lockstep, drain) in MODES.items():
        cfg = dataclasses.replace(base, lockstep=lockstep, drain=drain)
        outs[mode] = jax.block_until_ready(engine._sim_world_fresh(cfg, bank, w))
    return outs


def _assert_modes_bitwise(outs):
    # `drained`/`windows`/`win_stops`/`fused`/`chained` are path telemetry;
    # every other leaf must match bitwise
    ref = outs["step"]
    for mode in ("drain", "omni", "fused"):
        s = outs[mode]._replace(
            drained=ref.drained, windows=ref.windows,
            win_stops=ref.win_stops, fused=ref.fused, chained=ref.chained,
        )
        fa = jax.tree_util.tree_flatten_with_path(s)[0]
        fb = jax.tree_util.tree_flatten_with_path(ref)[0]
        assert len(fa) == len(fb)
        for (path, a), (_, b) in zip(fa, fb):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{mode} {jax.tree_util.keystr(path)}",
            )


def _check_case(params):
    outs = _run_case(**params)
    _assert_modes_bitwise(outs)
    _assert_telemetry_conserves(outs)
    return outs


def _assert_telemetry_conserves(outs):
    """Drain-telemetry invariants that must hold on EVERY workload."""
    seq, drain, fused = outs["step"], outs["drain"], outs["fused"]
    for s in (drain, fused):
        stats = engine.drain_stats(s, horizon_us=HORIZON_US)
        # every applied window records exactly one stop reason
        assert sum(stats["window_stops"].values()) == stats["windows"], stats
        # chained follow-ups are a subset of drained events
        assert 0 <= stats["chained"] <= stats["drained_events"], stats
        # windowed + singleton iterations account for every event once:
        # fence-chained admissions must not double- or zero-count
        assert stats["drained_events"] + stats["seq_events"] == stats["events"]
        # conservation across the scheduling fence: the drained paths
        # process exactly the events the sequential loop processes
        assert stats["events"] == int(np.sum(np.asarray(seq.iters))), stats
    # the map-lane planner and the fused lockstep planner must form the
    # SAME windows: all drain telemetry agrees exactly
    da = engine.drain_stats(drain, horizon_us=HORIZON_US)
    db = engine.drain_stats(fused, horizon_us=HORIZON_US)
    for key in ("events", "drained_events", "windows", "chained",
                "window_stops"):
        assert da[key] == db[key], (key, da[key], db[key])


class TestFixedSeedDifferential:
    """Deterministic examples through the generator — always run (tier-1)."""

    @pytest.mark.parametrize("seed", range(6))
    def test_four_mode_bitwise(self, seed):
        _check_case(_params(seed))

    def test_generator_covers_the_space(self):
        # the fixed-seed band must actually exercise ties, faults and skew —
        # otherwise the tier-1 examples silently degenerate to easy cases
        ps = [_params(s) for s in range(64)]
        assert any(p["rtt"][0] == 0.0 and p["jitter"] == 0 for p in ps)
        assert any(p["skew"] > 0 for p in ps)
        kinds = {row[1] for p in ps for row in p["faults"]
                 if row[0] < HORIZON_US}
        assert kinds == {KIND_CRASH, KIND_PARTITION, KIND_DEGRADE}
        assert {p["preset"] for p in ps} == set(PRESET_POOL)
        assert {p["shape"] for p in ps} == set(SHAPE_POOL)


class TestTelemetryConservationAllPresets:
    """Per-preset stopper accounting over the WHOLE zoo: every applied
    window records exactly one stop reason, chained admissions stay within
    the drained count, and windowed + singleton iterations account for
    every sequential event exactly once. Deliberately uses the same shapes
    and SimConfig as tests/core/test_protocols.py so the four compiled
    step functions are shared between the two modules within one run."""

    T, K, D, N = 8, 4, 2, 32

    @pytest.mark.parametrize("preset", sorted(PRESETS))
    def test_stoppers_and_events_conserve(self, preset):
        bank = workloads.make_ycsb_bank(
            workloads.YCSBConfig(
                num_ds=self.D, records_per_node=2000, ops_per_txn=self.K,
                dist_ratio=0.5, theta=0.9, seed=0,
            ),
            terminals=self.T, txns_per_terminal=self.N,
        )
        base = engine.SimConfig(
            terminals=self.T, max_ops=self.K, num_ds=self.D,
            bank_txns=self.N, proto=PRESETS[preset], warmup_us=0,
            horizon_us=1_500_000, track_slots=True,
        )
        w = engine.make_world(preset, (10.0, 100.0), jitter_milli=100)
        outs = {}
        for mode, (lockstep, drain) in MODES.items():
            cfg = dataclasses.replace(base, lockstep=lockstep, drain=drain)
            outs[mode] = jax.block_until_ready(
                engine._sim_world_fresh(cfg, bank, w))
        seq_events = int(np.sum(np.asarray(outs["step"].iters)))
        for mode in ("drain", "fused"):
            stats = engine.drain_stats(outs[mode], horizon_us=base.horizon_us)
            assert sum(stats["window_stops"].values()) == stats["windows"], (
                preset, mode, stats)
            assert 0 <= stats["chained"] <= stats["drained_events"], (
                preset, mode, stats)
            assert (stats["drained_events"] + stats["seq_events"]
                    == stats["events"] == seq_events), (preset, mode, stats)
            assert stats["loop_iters"] == stats["seq_events"] + stats["windows"]
        da = engine.drain_stats(outs["drain"], horizon_us=base.horizon_us)
        db = engine.drain_stats(outs["fused"], horizon_us=base.horizon_us)
        for key in ("events", "drained_events", "windows", "chained",
                    "window_stops"):
            assert da[key] == db[key], (preset, key, da[key], db[key])


if HAVE_HYPOTHESIS:
    _seeds = st.integers(min_value=0, max_value=2**31 - 1)
else:  # shim: @given skips (or fails under REQUIRE_HYPOTHESIS=1)
    _seeds = None


class TestPropertyDifferential:
    """Generative runs through the same parameter space, with shrinking:
    a failing seed minimizes toward the smallest integer reproducing the
    divergence, and `_params` replays it exactly."""

    @given(seed=_seeds)
    @example(seed=542363)  # once overflowed the RandomState seed range
    @settings(max_examples=8, deadline=None, derandomize=True)
    def test_four_mode_bitwise(self, seed):
        _check_case(_params(seed))

    @pytest.mark.slow
    @given(seed=_seeds)
    @settings(max_examples=48, deadline=None, derandomize=True)
    def test_four_mode_bitwise_deep(self, seed):
        _check_case(_params(seed))
