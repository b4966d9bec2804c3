"""The benchmark's bank generators are held to their output when the
benchmark was defined (then equal to the program's `repro.core.workloads`),
so a later change to the program's generator cannot move its traffic."""

from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest

from conftest import ROOT

from bench.gen import BANK_FIELDS, derive_seed, make_bank

DIGESTS = {
    ("ycsb_geo4", 0): "17104930709671e1fbce1159cac2a58a4f32daec61226c9909d89c5944298db0",
    ("ycsb_geo4", 2**40 + 3): "3d11a64d314f3ea291b6310dbc4124a96fc6ec7a96a759776720f3abfbea8d2b",
    ("tpcc_geo4", 0): "68c20d3f187e0e9269ef2d142256e502dece1fe78a1cac6596ae629990a42ae7",
    ("tpcc_geo4", 2**40 + 3): "c180d41206ccf78b8a6a2e0cf2aca8929586c5cbcfef2cc92db73b781beec4da",
}


def digest(bank: dict) -> str:
    h = hashlib.sha256()
    for f in BANK_FIELDS:
        x = np.ascontiguousarray(bank[f])
        h.update(f"{f}:{x.dtype.str}:{x.shape}".encode())
        h.update(x.tobytes())
    h.update(f"{int(bank['num_records'])}:{int(bank['num_ds'])}".encode())
    return h.hexdigest()


@pytest.mark.parametrize("config,seed", list(DIGESTS))
def test_generator_output_is_pinned(config, seed):
    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    bank = make_bank(conf["bank"], 16, 32, seed)
    assert digest(bank) == DIGESTS[(config, seed)]


def test_derived_seeds_take_any_whole_number():
    seeds = {derive_seed(s, i, r) for s in (0, 1, 2**31 + 9, 2**63 + 1, -4) for i in (0, 1) for r in (0, 1)}
    assert len(seeds) == 20
    assert all(0 <= s < 2**63 for s in seeds)
    assert derive_seed(2**40, 3, bits=31) < 2**31
