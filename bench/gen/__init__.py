"""Traffic generation: transaction banks and the sweeps that run them.

A configuration's ``bank.kind`` names a module of this package (``ycsb``,
``tpcc``) whose ``make(params, terminals, txns_per_terminal, seed)`` returns
the bank as numpy arrays. The generators are copies of the program's
(`repro.core.workloads` as it stood when the benchmark was defined), so a
later change to the program's generator cannot move the benchmark's
traffic; `tests/bench/test_bench_gen.py` holds their output to a digest.
"""

from __future__ import annotations

import importlib

import numpy as np

BANK_FIELDS = ("key", "write", "ds", "round_id", "valid", "is_dist")


def make_bank(bank_cfg: dict, terminals: int, txns_per_terminal: int, seed: int) -> dict:
    """The bank of one configuration, drawn from ``seed``."""
    gen = importlib.import_module(f"bench.gen.{bank_cfg['kind']}")
    return gen.make(bank_cfg["params"], terminals, txns_per_terminal, seed)


def derive_seed(*parts: int, bits: int = 63) -> int:
    """A seed below 2**bits, fixed by ``parts`` (the run's seed, a sweep's
    index, a replica's index); any whole numbers of any size."""
    words = []
    for p in parts:
        p = int(p)
        words += [p & 0xFFFFFFFF, (p >> 32) & 0xFFFFFFFF, int(p < 0)]
    state = np.random.SeedSequence(words).generate_state(2, np.uint32)
    return int(((int(state[0]) << 32) | int(state[1])) >> (64 - bits))
