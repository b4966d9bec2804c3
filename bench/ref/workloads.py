"""The transaction bank the reference reads (a copy of the program's type)."""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp


class Bank(NamedTuple):
    """Pre-generated transaction bank. T terminals x N txns x K op slots."""

    key: jnp.ndarray  # [T,N,K] int32 global record id
    write: jnp.ndarray  # [T,N,K] bool
    ds: jnp.ndarray  # [T,N,K] int8 data source of the op
    round_id: jnp.ndarray  # [T,N,K] int8 interactive round of the op
    valid: jnp.ndarray  # [T,N,K] bool real op?
    is_dist: jnp.ndarray  # [T,N] bool distributed txn?
    num_records: int  # global key-space size (static)
    num_ds: int
