"""chip_smoke.py's checks, rehearsed on the CPU at a tiny size.

The phases run here exactly as on the chip, with fewer terminals, worlds and
seconds: every compared world must match bit for bit. The mesh phase runs in
a child process with four host CPU devices (the device count is fixed when
JAX starts). `main()` itself must refuse to run anywhere but on a TPU.
"""

import os
import pathlib
import subprocess
import sys

import chip_smoke

TINY = dict(terminals=8, horizon_s=0.5, warmup_s=0.1, presets=("ssp", "geotp"))


def test_one_chip_phases_agree_bitwise_on_cpu(capsys):
    problems = chip_smoke.one_chip_phases(seeds=(0,), **TINY)
    assert problems == []
    out = capsys.readouterr().out
    for phase in ("vmap:", "map:", "reference world 0", "reference world 1"):
        assert f"[chip_smoke] {phase}" in out, out


def test_mismatch_is_reported():
    # a flipped bit in one leaf is named; path telemetry is not compared
    from repro.core import engine, workloads

    bank = workloads.make_ycsb_bank(
        workloads.YCSBConfig(num_ds=2, records_per_node=64, ops_per_txn=2),
        terminals=2, txns_per_terminal=8,
    )
    sim = engine.Simulator.from_bank(bank, horizon_s=0.2, warmup_s=0.0)
    st = sim.run(engine.make_world("ssp", (0.0, 10.0)), bank).states
    assert chip_smoke.mismatched_leaves(st, st) == []
    assert chip_smoke.mismatched_leaves(st._replace(windows=st.windows + 1), st) == []
    flipped = st._replace(commits=st.commits ^ 1)
    assert chip_smoke.mismatched_leaves(flipped, st) == [".commits"]


def test_mesh_phase_agrees_on_four_cpu_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    root = pathlib.Path(chip_smoke.__file__).parent
    code = (
        "import jax, chip_smoke\n"
        "assert jax.device_count() == 4\n"
        f"p = chip_smoke.mesh_phases(seeds=(0, 1), **{TINY!r})\n"
        "assert p == [], p\n"
        "print('mesh phase OK')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=str(root), env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "4 worlds on 4 of 4 devices" in out.stdout, out.stdout
    assert "mesh phase OK" in out.stdout


def test_main_refuses_a_cpu(capsys):
    for argv in ([], ["--mesh"]):
        assert chip_smoke.main(argv) != 0
        captured = capsys.readouterr()
        assert '"ok"' not in captured.out
        assert "no TPU" in captured.err
