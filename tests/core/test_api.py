"""Public-API tests: `Simulator` + `Grid` + `RunResult` (the api_redesign
tentpole) and the engine package's layering/size guarantees.

1. `Grid` validates every cell at construction — the old `run_sweep` path
   silently inferred shapes from cells[0]; heterogeneous grids must now raise
   with the offending cell index (regression-tested on the old-style dict
   cell format).
2. Golden equivalence: `Simulator.run_grid` must be bitwise-identical (final
   states AND metric dicts) to the legacy `engine.simulate_batch` path for
   both batching strategies, including on the smoke fig5 grid.
3. `RunResult.save` writes the exact legacy `sweeps.<tag>` schema plus the
   jax runtime-environment keys.
4. Importing `repro.core.engine` is side-effect-free and never pulls in
   `benchmarks` / `repro.serving`; no package module exceeds ~900 lines.
"""

import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import engine, protocol, workloads
from repro.core.engine import Grid, RunResult, Simulator
from repro.core.netmodel import make_net_params

T, K, D, N = 8, 4, 2, 32
RTT = (10.0, 100.0)


def _bank(seed=0, theta=0.9, num_ds=D):
    cfg_w = workloads.YCSBConfig(
        num_ds=num_ds, records_per_node=2000, ops_per_txn=K, dist_ratio=0.5,
        theta=theta, seed=seed,
    )
    return workloads.make_ycsb_bank(cfg_w, terminals=T, txns_per_terminal=N)


def _assert_metrics_equal(ms_a, ms_b):
    # dict equality with NaN == NaN (empty-histogram percentiles are NaN)
    assert len(ms_a) == len(ms_b)
    for i, (ma, mb) in enumerate(zip(ms_a, ms_b)):
        assert set(ma) == set(mb), i
        for k in ma:
            va, vb = ma[k], mb[k]
            assert va == vb or (va != va and vb != vb), (i, k, va, vb)


def _assert_states_bitwise(sa, sb):
    fa = jax.tree_util.tree_flatten_with_path(sa)[0]
    fb = jax.tree_util.tree_flatten_with_path(sb)[0]
    assert len(fa) == len(fb)
    for (path, a), (_, b) in zip(fa, fb):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path)
        )


class TestGridValidation:
    def test_heterogeneous_num_ds_raises_with_cell_index(self):
        # the old-style dict cell format (run_sweep's input): cell 1 carries
        # a 3-site RTT vector in a 2-site grid — previously silently shaped
        # by cells[0], now an error naming the offending cell
        cells = [
            dict(preset="ssp", rtt_ms=(10.0, 100.0)),
            dict(preset="geotp", rtt_ms=(10.0, 50.0, 100.0)),
        ]
        with pytest.raises(ValueError, match="cell 1"):
            Grid(cells)

    def test_heterogeneous_tau_true_raises(self):
        cells = [
            dict(preset="ssp", tau_true_us=(0, 27_000)),
            dict(preset="ssp", tau_true_us=(0, 27_000, 73_000)),
        ]
        with pytest.raises(ValueError, match="cell 1"):
            Grid(cells)

    def test_unknown_preset_raises_with_cell_index(self):
        with pytest.raises(ValueError, match="cell 1.*no-such-preset"):
            Grid([dict(preset="ssp"), dict(preset="no-such-preset")])

    def test_missing_preset_raises(self):
        with pytest.raises(ValueError, match="cell 0.*preset"):
            Grid([dict(rtt_ms=RTT)])

    def test_bank_count_mismatch_raises(self):
        with pytest.raises(ValueError, match="banks"):
            Grid([dict(preset="ssp"), dict(preset="geotp")], banks=[_bank()])

    def test_bank_shape_mismatch_raises_with_bank_index(self):
        cfg_w = workloads.YCSBConfig(
            num_ds=D, records_per_node=2000, ops_per_txn=K + 1, dist_ratio=0.5,
        )
        odd = workloads.make_ycsb_bank(cfg_w, terminals=T, txns_per_terminal=N)
        with pytest.raises(ValueError, match="bank 1"):
            Grid(
                [dict(preset="ssp"), dict(preset="geotp")],
                banks=[_bank(), odd],
            )

    def test_run_sweep_dict_path_still_validates(self):
        # regression: the benchmarks entry point keeps accepting raw dict
        # cells AND inherits Grid's validation (no silent cells[0] inference)
        pytest.importorskip("benchmarks.common")
        from benchmarks.common import run_sweep

        cells = [
            dict(preset="ssp", rtt_ms=(10.0, 100.0)),
            dict(preset="ssp", rtt_ms=(10.0, 50.0, 100.0)),
        ]
        with pytest.raises(ValueError, match="cell 1"):
            run_sweep("t", cells, _bank(), T, record=False)

    def test_simulator_rejects_mismatched_grid_and_bank(self):
        sim = Simulator.from_bank(_bank(), horizon_s=0.5)
        grid = Grid([dict(preset="ssp", rtt_ms=(10.0, 50.0, 100.0))])
        with pytest.raises(ValueError, match="num_ds"):
            sim.run_grid(grid, _bank())
        with pytest.raises(ValueError, match="bank"):
            sim.run_grid(Grid([dict(preset="ssp", rtt_ms=RTT)]))


class TestGridBuilders:
    def test_cross_product_order_and_labels(self):
        g = Grid.cross(preset=("ssp", "geotp"), seed=(0, 1), level="hi")
        assert len(g) == 4
        assert g.cells[0] == dict(preset="ssp", seed=0, level="hi")
        assert g.cells[3] == dict(preset="geotp", seed=1, level="hi")

    def test_cross_vector_axis_is_one_value(self):
        # a flat RTT tuple is ONE cell value, not a swept axis
        g = Grid.cross(preset=("ssp",), rtt_ms=(10.0, 100.0))
        assert len(g) == 1 and g.num_ds == 2
        g2 = Grid.cross(preset=("ssp",), rtt_ms=((5.0, 20.0), (10.0, 100.0)))
        assert len(g2) == 2

    def test_zipped_broadcasts_scalars(self):
        g = Grid.zipped(preset="geotp", seed=(0, 1, 2))
        assert len(g) == 3
        assert [c["seed"] for c in g.cells] == [0, 1, 2]
        assert all(c["preset"] == "geotp" for c in g.cells)
        with pytest.raises(ValueError, match="zipped"):
            Grid.zipped(preset=("ssp", "geotp"), seed=(0, 1, 2))

    def test_worlds_match_make_world(self):
        g = Grid([dict(preset="geotp", rtt_ms=RTT, jitter_milli=7, seed=3)])
        w = g.world(0)
        ref = engine.make_world("geotp", RTT, jitter_milli=7, seed=3)
        _assert_states_bitwise(w, ref)


class TestGoldenEquivalence:
    """`Simulator.run_grid` vs the legacy `engine.simulate_batch` path:
    bitwise-identical final states and identical metric dicts, both
    strategies."""

    def _legacy(self, cfg, bank, cells, strategy):
        worlds = engine.stack_worlds(
            [
                engine.make_world(
                    c["preset"], c.get("rtt_ms", engine.Grid([c]).default_rtt_ms),
                    jitter_milli=c.get("jitter_milli", 30),
                    seed=c.get("seed", 0),
                )
                for c in cells
            ]
        )
        return engine.simulate_batch(cfg, bank, worlds, strategy=strategy)

    @pytest.mark.parametrize("strategy", ["map", "vmap"])
    def test_run_grid_matches_simulate_batch(self, strategy):
        bank = _bank()
        cells = [
            dict(preset="ssp", rtt_ms=RTT, jitter_milli=0),
            dict(preset="geotp", rtt_ms=RTT, jitter_milli=30, seed=1),
            dict(preset="chiller", rtt_ms=(20.0, 80.0), jitter_milli=0),
        ]
        sim = Simulator.from_bank(bank, horizon_s=1.0, warmup_s=0.0)
        res = sim.run_grid(Grid(cells), bank, strategy=strategy)
        states_ref, metrics_ref = self._legacy(sim.cfg, bank, cells, strategy)
        _assert_metrics_equal(res.metrics, metrics_ref)
        _assert_states_bitwise(res.states, states_ref)

    @pytest.mark.slow
    @pytest.mark.parametrize("strategy", ["map", "vmap"])
    def test_run_grid_matches_on_smoke_fig5_cells(self, strategy):
        # the exact smoke grid: presets x seeds, per-seed banks, reduced
        # horizon — the baseline-compatibility surface of benchmarks.run
        pytest.importorskip("benchmarks.run")
        from benchmarks.run import SMOKE_PRESETS, SMOKE_SEEDS

        T_s, H_s, W_s = 32, 1.0, 0.5
        banks = {
            sd: workloads.make_ycsb_bank(
                workloads.YCSBConfig(
                    num_ds=4, records_per_node=1_000_000, ops_per_txn=5,
                    dist_ratio=0.2, theta=0.9, seed=sd,
                ),
                T_s, 256,
            )
            for sd in SMOKE_SEEDS
        }
        cells, cell_banks = [], []
        for sd in SMOKE_SEEDS:
            for preset in SMOKE_PRESETS:
                cells.append(dict(preset=preset, seed=sd))
                cell_banks.append(banks[sd])
        sim = Simulator.from_bank(
            cell_banks[0], terminals=T_s, horizon_s=H_s, warmup_s=W_s
        )
        res = sim.run_grid(Grid(cells, banks=cell_banks), strategy=strategy)
        import jax.numpy as jnp

        bank_b = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *cell_banks)
        worlds = engine.stack_worlds(
            [
                engine.make_world(c["preset"], jitter_milli=30, seed=c["seed"])
                for c in cells
            ]
        )
        states_ref, metrics_ref = engine.simulate_batch(
            sim.cfg, bank_b, worlds, bank_batched=True, strategy=strategy
        )
        _assert_metrics_equal(res.metrics, metrics_ref)
        _assert_states_bitwise(res.states, states_ref)


class TestRunResult:
    def _res(self, strategy="map"):
        bank = _bank()
        grid = Grid(
            [
                dict(preset="ssp", rtt_ms=RTT, level="lo"),
                dict(preset="geotp", rtt_ms=RTT, level="hi", seed=1),
            ]
        )
        # same (shapes, horizon, warmup) as TestGoldenEquivalence -> the
        # compile-cached runner is shared between the two test classes
        sim = Simulator.from_bank(bank, horizon_s=1.0, warmup_s=0.0)
        return sim, bank, sim.run_grid(grid, bank, strategy=strategy)

    def test_rows_merge_labels_and_metrics(self):
        _, _, res = self._res()
        rows = res.rows()
        assert len(rows) == 2
        assert rows[0]["preset"] == "ssp" and rows[0]["level"] == "lo"
        assert rows[1]["preset"] == "geotp" and rows[1]["seed"] == 1
        assert "throughput_tps" in rows[0] and "events" in rows[1]

    def test_world_slices_batched_state(self):
        _, _, res = self._res()
        st1 = res.world(1)
        assert st1.now.ndim == 0
        assert int(st1.iters) == res.metrics[1]["events"]

    def test_save_writes_legacy_schema_plus_env(self, tmp_path):
        _, _, res = self._res()
        path = tmp_path / "BENCH.json"
        entry = res.save("api_test", path=path)
        stored = engine.load_bench(path)["sweeps"]["api_test"]
        assert stored == entry
        legacy_keys = {
            "worlds", "terminals", "events", "wall_s", "events_per_sec",
            "strategy", "horizon_s", "drain_hit_rate", "mean_window_len",
            "loop_iters",
        }
        assert legacy_keys <= set(entry)
        # satellite: jax runtime recorded in every sweep/smoke entry
        assert entry["jax_version"] == jax.__version__
        assert entry["jax_backend"] == jax.default_backend()
        assert entry["jax_device_kind"] == jax.devices()[0].device_kind
        assert entry["jax_device_count"] == jax.device_count()
        assert entry["worlds"] == 2 and entry["terminals"] == T
        assert entry["events"] == res.events

    def test_record_smoke_includes_env(self, tmp_path):
        path = tmp_path / "BENCH.json"
        entry = engine.record_smoke({"events_per_sec_batched": 1.0}, path=path)
        stored = engine.load_bench(path)["smoke"]
        assert stored["jax_backend"] == jax.default_backend()
        assert stored["jax_device_kind"] == jax.devices()[0].device_kind
        assert stored == entry


class TestResume:
    @staticmethod
    def _neutral(s, ref):
        # drained/windows/win_stops/fused/chained are window-telemetry: a
        # window cut at the first run's horizon may merge in the
        # uninterrupted run; every other leaf must stay bitwise-identical
        # (same convention as the drain tests)
        return s._replace(
            drained=ref.drained, windows=ref.windows,
            win_stops=ref.win_stops, fused=ref.fused, chained=ref.chained,
        )

    @pytest.mark.slow
    def test_resume_continues_bitwise(self):
        # run to 0.6s then resume to 1.2s == one uninterrupted 1.2s run
        bank = _bank()
        world = engine.make_world("geotp", RTT, jitter_milli=30)
        sim_a = Simulator.from_bank(bank, horizon_s=0.6, warmup_s=0.0)
        res = sim_a.run(world, bank)
        res = sim_a.resume(res, horizon_s=1.2)
        sim_b = Simulator.from_bank(bank, horizon_s=1.2, warmup_s=0.0)
        ref = sim_b.run(world, bank)
        assert res.metrics == ref.metrics
        _assert_states_bitwise(self._neutral(res.states, ref.states), ref.states)

    @pytest.mark.slow
    def test_resume_grid_continues_bitwise(self):
        bank = _bank()
        grid = Grid(
            [dict(preset="ssp", rtt_ms=RTT), dict(preset="geotp", rtt_ms=RTT)]
        )
        sim = Simulator.from_bank(bank, horizon_s=0.6, warmup_s=0.0)
        res = sim.resume(sim.run_grid(grid, bank, strategy="map"), horizon_s=1.2)
        sim_b = Simulator.from_bank(bank, horizon_s=1.2, warmup_s=0.0)
        ref = sim_b.run_grid(grid, bank, strategy="map")
        assert res.metrics == ref.metrics
        _assert_states_bitwise(self._neutral(res.states, ref.states), ref.states)


class TestPackageLayering:
    def test_engine_import_is_clean(self):
        # side-effect-free import that never pulls in the benchmark harness
        # or the serving stack (checked in a fresh interpreter)
        code = (
            "import sys; import repro.core.engine; "
            "bad = sorted(m for m in sys.modules "
            "if m.startswith('benchmarks') or m.startswith('repro.serving')); "
            "assert not bad, bad"
        )
        subprocess.run(
            [sys.executable, "-c", code],
            check=True,
            cwd=str(pathlib.Path(engine.__file__).parents[3]),
        )

    def test_no_module_exceeds_size_cap(self):
        pkg = pathlib.Path(engine.__file__).parent
        for f in pkg.glob("*.py"):
            n = len(f.read_text().splitlines())
            assert n <= 900, f"{f.name} has {n} lines (cap 900)"

    def test_legacy_names_still_reexported(self):
        for name in (
            "SimConfig", "SimState", "WorldSpec", "DynProto", "simulate",
            "simulate_batch", "make_world", "stack_worlds", "init_state",
            "summarize", "drain_stats", "latency_cdf", "world_index",
            "dyn_from_proto", "INF_US", "SUB_ACK", "OP_ENROUTE", "T_ACTIVE",
            "_step", "_drain_step", "_omni_step", "_omni_window", "_run_jit",
        ):
            assert hasattr(engine, name), name
