"""The engine compiles for a TPU v5e at chip_smoke.py's full size.

These compile for chips that are described, not attached: no chip runs, so
they say nothing about results or times, but what the chip's compiler would
refuse fails here. The topology is described inside a fixture, never while a
module is imported, so that every test worker collects the same tests and
only the worker given this file loads the TPU library.

* `vmap` and `map` on one chip: the two single-chip placements of
  `chip_smoke.py`'s 8-world, 128-terminal grid;
* `mesh` over four chips: the worlds shard over a 1-D mesh, and since worlds
  are independent the program must hold no collective;
* the `vmap` program reads per-data-source values at each op's data source
  with selects, not gathers (`state._at_ds`).
"""

import math
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import chip_smoke
from repro.core.engine import placement
from repro.launch.mesh import WORLDS_AXIS

V5E_HBM_BYTES = 16 * 2**30
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def full_size():
    bank, grid, sim = chip_smoke.deployment(
        chip_smoke.TERMINALS, chip_smoke.HORIZON_S, chip_smoke.WARMUP_S,
        chip_smoke.PRESETS, chip_smoke.SEEDS,
    )
    return sim, bank, grid.worlds()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if isinstance(x, jax.Array) else x,
        tree,
    )


@pytest.fixture(scope="module")
def one_chip_program(topo, full_size):
    """strategy -> the one-chip program at full size, compiled once."""
    sim, bank, worlds = full_size
    one_chip = SingleDeviceSharding(topo.devices[0])
    done = {}

    def compiled(strategy):
        if strategy not in done:
            done[strategy] = placement._sim_batch_fresh.lower(
                placement.placement_cfg(sim.cfg, strategy),
                _shapes(bank, one_chip),
                _shapes(worlds, one_chip),
                None,
                strategy,
                1,
            ).compile()
        return done[strategy]

    return compiled


@pytest.mark.parametrize("strategy", ["vmap", "map"])
def test_one_chip_program_compiles(one_chip_program, strategy):
    compiled = one_chip_program(strategy)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


def test_mesh_program_has_no_collectives(topo, full_size, monkeypatch):
    sim, bank, worlds = full_size
    # the engine builds its mesh from jax.devices(), which sees the CPU here
    mesh = Mesh(np.asarray(topo.devices[:4]), (WORLDS_AXIS,))
    monkeypatch.setattr(
        placement, "make_worlds_mesh", lambda n: Mesh(mesh.devices[:n], mesh.axis_names)
    )
    compiled = placement._sim_batch_fresh.lower(
        placement.placement_cfg(sim.cfg, "mesh"),
        _shapes(bank, NamedSharding(mesh, P())),
        _shapes(worlds, NamedSharding(mesh, P(WORLDS_AXIS))),
        None,
        "mesh",
        4,
    ).compile()
    hlo = compiled.as_text()
    assert "while" in hlo
    assert [c for c in COLLECTIVES if c in hlo] == []


_DEF = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]", re.M)
_GATHER = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]\S* gather\(%([\w.\-]+),", re.M)


def _dims(text: str) -> list:
    return [int(n) for n in text.split(",") if n]


def test_vmap_program_reads_op_data_sources_without_gathers(one_chip_program, full_size):
    """No gather in the optimised `vmap` program reads an operand whose minor
    dimension is the data-source count and yields one element per op slot
    (T*K per world): each such read is `state._at_ds`'s selects. Under
    `vmap` a gather lowers to a batched TPU gather that walks its indices
    one by one."""
    sim, _, worlds = full_size
    cfg = sim.cfg
    n_worlds = worlds.tau_true.shape[0]
    hlo = one_chip_program("vmap").as_text()
    dims = {m.group(1): _dims(m.group(2)) for m in _DEF.finditer(hlo)}
    gathers = [(_dims(out), dims[src]) for out, src in _GATHER.findall(hlo)]
    assert gathers, "the pattern finds no gather: the HLO text changed form"
    per_op_ds = [
        (out, src) for out, src in gathers
        if src and src[-1] == cfg.num_ds
        and math.prod(out) == n_worlds * cfg.terminals * cfg.max_ops
    ]
    assert per_op_ds == []
