"""The check comes out not correct for its control (the reference with
exclusive write locks broken) and for a run whose timed path is broken
underneath: a step that leaves its state unchanged, half of the batch left
out, the exchange between chips left out, an answer altered where it is
produced."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY, write_tiny_root

from bench import harness
from bench.control import control_inputs, simulate_control
from repro.core.engine import api
from repro.core.engine.metrics import summarize_batch
from repro.core.engine.state import init_state_world


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny")
    write_tiny_root(root, presets=("ssp", "geotp"), banks=2)
    return root


@pytest.mark.parametrize("seed", [11, 2**33 + 12, 13])
def test_the_control_is_not_correct(root, seed):
    _, config, traffic = harness.load_cell(harness.load_spec(root), TINY, root)
    inputs = control_inputs(config, traffic, seed)
    worlds = [
        (cell, bank, *simulate_control(config["deployment"], bank, cell))
        for cell, bank in zip(inputs.cells, inputs.banks)
    ]
    numbers = harness.check(config, worlds)["numbers"]
    assert any(n["value"] > n["limit"] for n in numbers.values()), numbers


def _broken_batch(kind):
    run = api.simulate_batch

    def simulate_batch(cfg, bank, worlds, **kw):
        states, _ = run(cfg, bank, worlds, **kw)
        B = int(states.now.shape[0])
        if kind == "unchanged":
            # what a step that returns its state unchanged leaves behind
            states = jax.vmap(lambda w: init_state_world(cfg, w))(worlds)
        elif kind == "half_batch":
            # the second half never ran: the first half stands in for it
            idx = np.arange(B) % (B // 2)
            states = jax.tree_util.tree_map(lambda x: x[idx], states)
        elif kind == "no_exchange":
            # the second device's lanes never come back to the host
            states = jax.tree_util.tree_map(lambda x: x.at[B // 2 :].set(jnp.zeros_like(x[B // 2 :])), states)
        elif kind == "altered":
            states = states._replace(commits=states.commits.at[B - 1].add(1))
        return states, summarize_batch(cfg, states)

    return simulate_batch


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "no_exchange", "altered"])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, kind):
    monkeypatch.setattr(api, "simulate_batch", _broken_batch(kind))
    line = harness.run_cell(
        harness.load_spec(root), TINY, 7, 0.2, False, time.perf_counter(),
        root=root, log=lambda m: None,
    )
    assert line["correct"] is False
    assert line["failed"] > 0
    assert list(line)[-1] == "checks"
