"""YCSB transactional bank (a copy of the program's generator).

1M records per data node by default, ops 50% read / 50% write, Zipf key
skew, a share of distributed transactions whose ops alternate between a
home node and a second one, and interactive rounds.
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=4)
def _zipf_cdf(n: int, theta: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    p = 1.0 / np.power(ranks, theta)
    cdf = np.cumsum(p)
    return (cdf / cdf[-1]).astype(np.float64)


def _sample_zipf(rng: np.random.Generator, cdf: np.ndarray, shape) -> np.ndarray:
    u = rng.random(shape)
    return np.searchsorted(cdf, u, side="left").astype(np.int64)


def dedup_linear(keys: np.ndarray, modulo: int) -> np.ndarray:
    """Ensure keys are unique within the last axis (linear probing)."""
    k = keys.copy()
    K = k.shape[-1]
    for i in range(1, K):
        for _ in range(K):
            dup = (k[..., i : i + 1] == k[..., :i]).any(axis=-1)
            if not dup.any():
                break
            k[..., i] = np.where(dup, (k[..., i] + 1) % modulo, k[..., i])
    return k


def make(params: dict, terminals: int, txns_per_terminal: int, seed: int) -> dict:
    rng = np.random.default_rng(np.random.PCG64(seed))
    T, N, K = terminals, txns_per_terminal, params["ops_per_txn"]
    D, R = params["num_ds"], params["records_per_node"]

    cdf = _zipf_cdf(R, float(params["theta"]))
    local = _sample_zipf(rng, cdf, (T, N, K))
    local = dedup_linear(local, R)

    is_dist = rng.random((T, N)) < params["dist_ratio"]
    home = rng.integers(0, D, size=(T, N))
    # distributed txns touch `dist_nodes` distinct nodes; op i -> node cycle
    offsets = rng.integers(1, D, size=(T, N)) if D > 1 else np.zeros((T, N), dtype=np.int64)
    second = (home + offsets) % D
    op_slot = np.arange(K)[None, None, :]
    use_second = is_dist[..., None] & (op_slot % max(params["dist_nodes"], 2) == 1)
    ds = np.where(use_second, second[..., None], home[..., None]).astype(np.int8)

    key = (ds.astype(np.int64) * R + local).astype(np.int32)
    write = rng.random((T, N, K)) < (1.0 - params["read_frac"])
    rounds = np.minimum(params["rounds"], K)
    round_id = (op_slot * rounds // K).astype(np.int8) * np.ones((T, N, 1), dtype=np.int8)
    valid = np.ones((T, N, K), dtype=bool)
    return dict(
        key=key, write=write, ds=ds, round_id=round_id, valid=valid,
        is_dist=is_dist, num_records=D * R, num_ds=D,
    )
