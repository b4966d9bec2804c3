"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload ycsb.fig5 --seed 7 --seconds 10 --trace 0

The cell, its configuration and its traffic mix are read from
BENCHMARK.json. The run needs a TPU and exactly the cell's number of chips;
otherwise it exits nonzero and prints no result. It sets up (banks, the
program compiled or loaded from the compile cache inside the checkout, one
warm-up call), runs whole sweeps through `Simulator.run_grid` for
``--seconds``, checks the first sweep's worlds against the plain reference
and the rules of strict two-phase locking and every later sweep against
the first, and prints one JSON object as the last line of stdout. The
cell's worlds are fixed by its traffic file: ``--seed`` does not change
them. With ``--trace 1``
the window runs under the profiler and the line carries the per-layer
metrics, the device's busy time and a breakdown instead of the end-to-end
metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / ".bench_out"  # traces, inside the checkout (git-ignored)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"the program is not in this checkout ({ROOT / 'src' / 'repro'}); nothing was run")
        return 2
    # before JAX starts: libtpu logs nowhere (else it writes under /tmp), and
    # the compile cache is the checkout's own fixed directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    from bench import harness
    from repro.compile_cache import use_compile_cache

    spec = harness.load_spec(ROOT)
    cell, _, _ = harness.load_cell(spec, args.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found platform {devices[0].platform!r}; nothing was run")
        return 2
    if len(devices) != cell["chips"]:
        log(f"cell {args.workload} needs {cell['chips']} chips, JAX sees {len(devices)}; "
            "nothing was run")
        return 2
    log(f"device {devices[0].device_kind} x{len(devices)}; compile cache {use_compile_cache()}")

    log_dir = OUT / "trace"
    if args.trace:
        shutil.rmtree(log_dir, ignore_errors=True)
    try:
        line = harness.run_cell(
            spec, args.workload, args.seed, args.seconds, bool(args.trace), T_START,
            log_dir=log_dir, root=ROOT, log=log,
        )
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    for name, n in line["checks"].items():
        log(f"check {name} = {n['value']} (limit {n['limit']})")
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
