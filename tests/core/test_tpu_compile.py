"""The engine compiles for a TPU v5e at chip_smoke.py's full size.

These compile for chips that are described, not attached: no chip runs, so
they say nothing about results or times, but what the chip's compiler would
refuse fails here. The topology is described inside a fixture, never while a
module is imported, so that every test worker collects the same tests and
only the worker given this file loads the TPU library.

* `vmap` and `map` on one chip: the two single-chip placements of
  `chip_smoke.py`'s 8-world, 128-terminal grid;
* `mesh` over four chips: the worlds shard over a 1-D mesh, and since worlds
  are independent the program must hold no collective.
"""

import os

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


@pytest.mark.parametrize("strategy", ["vmap", "map"])
def test_one_chip_program_compiles(topo, full_size, strategy):
    sim, bank, worlds = full_size
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = placement._sim_batch_fresh.lower(
        placement.placement_cfg(sim.cfg, strategy),
        _shapes(bank, one_chip),
        _shapes(worlds, one_chip),
        None,
        strategy,
        1,
    ).compile()
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
