"""Hotspot footprint (§IV-C): per-record contention statistics.

Four fields per record r (paper §IV-C "Hotspot statistics collecting"):
  w_lat_r — EWMA of the latency share of subtransactions on r   (Eq.4)
  t_cnt_r — total transactions that accessed r
  c_cnt_r — committed transactions that accessed r
  a_cnt_r — transactions currently accessing r

Two implementations:

* `DenseHotspot` — statistics arrays indexed directly by record id. Used by the
  discrete-event engine, where the benchmark key space is bounded (YCSB: 1M
  records/node). O(1) vectorized gather/scatter.

* `HashHotspot` — fixed-capacity open-addressing hash table with clock (second
  chance) eviction. This is the TPU-native replacement for the paper's
  AVL-tree + LRU-list (§IV-C): pointer-chasing balanced trees do not map to
  vectorized/TPU execution, but a bounded-probe hash table is a few gathers.
  Used by the serving engine where the "record" space (KV pages × pods) is
  unbounded. Hardware adaptation recorded in DESIGN.md §3.

w_lat is stored in µs as int32 (deterministic integer EWMA, same convention as
the engine clock).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.netmodel import _hash_u32


class DenseHotspot(NamedTuple):
    w_lat: jax.Array  # [R] int32 µs
    t_cnt: jax.Array  # [R] int32
    c_cnt: jax.Array  # [R] int32
    a_cnt: jax.Array  # [R] int32


def dense_init(num_records: int) -> DenseHotspot:
    z = jnp.zeros((num_records,), dtype=jnp.int32)
    return DenseHotspot(w_lat=z, t_cnt=z, c_cnt=z, a_cnt=z)


def dense_on_dispatch(hs: DenseHotspot, keys: jax.Array, valid: jax.Array) -> DenseHotspot:
    """A transaction starts accessing `keys` (a_cnt+1). t_cnt counts *finished*
    accesses so that c_cnt/t_cnt in Eq.(9) is the historical commit ratio and
    is not biased down by in-flight transactions."""
    upd = valid.astype(jnp.int32)
    safe = jnp.where(valid, keys, 0)
    return hs._replace(a_cnt=hs.a_cnt.at[safe].add(upd, mode="drop"))


def dense_on_complete(
    hs: DenseHotspot,
    keys: jax.Array,
    valid: jax.Array,
    committed: jax.Array,
    lel_us: jax.Array,
    alpha_milli: jax.Array,
) -> DenseHotspot:
    """Subtransaction finished (committed or aborted): Eq.(4) EWMA + counters.

    keys/valid: [K] records the subtransaction accessed.
    committed:  scalar bool.
    lel_us:     scalar int32 — measured local execution latency of the subtxn.
    alpha_milli: EWMA coefficient α in 1/1000 (Eq.4).

    The per-record share is w_r = w_lat_r / Σ w_lat (uniform if the sum is 0),
    and w_lat_r <- α w_lat_r + (1-α) LEL * w_r   — exactly Eq.(4).
    (float32 internally; results rounded back to int32 µs, capped at 10 s.)
    """
    safe = jnp.where(valid, keys, 0)
    vf = valid.astype(jnp.float32)
    w = hs.w_lat[safe].astype(jnp.float32) * vf
    total = jnp.sum(w)
    n = jnp.maximum(jnp.sum(vf), 1.0)
    share = jnp.where(total > 0.0, w / jnp.maximum(total, 1.0), vf / n)
    lel_share = lel_us.astype(jnp.float32) * share  # LEL * w_r
    a = alpha_milli.astype(jnp.float32) / 1000.0
    old = hs.w_lat[safe].astype(jnp.float32)
    new = old * a + lel_share * (1.0 - a)
    new = jnp.clip(jnp.where(valid, new, old), 0.0, 1e7).astype(jnp.int32)
    dec = valid.astype(jnp.int32)
    return hs._replace(
        w_lat=hs.w_lat.at[safe].set(new, mode="drop"),
        a_cnt=jnp.maximum(hs.a_cnt.at[safe].add(-dec, mode="drop"), 0),
        t_cnt=hs.t_cnt.at[safe].add(dec, mode="drop"),
        c_cnt=hs.c_cnt.at[safe].add(dec * committed.astype(jnp.int32), mode="drop"),
    )


def dense_forecast_lel(hs: DenseHotspot, keys: jax.Array, valid: jax.Array) -> jax.Array:
    """Eq.(5): LEL̂ = Σ_r w_lat_r over the records of one subtransaction.

    keys/valid: [..., K]; returns [...] int32 µs.
    """
    safe = jnp.where(valid, keys, 0)
    w = hs.w_lat[safe] * valid.astype(jnp.int32)
    return jnp.sum(w, axis=-1).astype(jnp.int32)


def dense_gather_stats(hs: DenseHotspot, keys: jax.Array, valid: jax.Array):
    """Gather (c_cnt, t_cnt, a_cnt) for Eq.(9); invalid slots read as benign."""
    safe = jnp.where(valid, keys, 0)
    return hs.c_cnt[safe], hs.t_cnt[safe], hs.a_cnt[safe]


# ---------------------------------------------------------------------------
# Fixed-capacity hash table variant (production / serving engine).
# ---------------------------------------------------------------------------

_EMPTY = jnp.int32(-1)


class HashHotspot(NamedTuple):
    slot_key: jax.Array  # [C] int32, -1 = empty
    w_lat: jax.Array  # [C] int32
    t_cnt: jax.Array  # [C] int32
    c_cnt: jax.Array  # [C] int32
    a_cnt: jax.Array  # [C] int32
    clock: jax.Array  # [C] int8 second-chance bit


def hash_init(capacity: int) -> HashHotspot:
    return HashHotspot(
        slot_key=jnp.full((capacity,), _EMPTY, dtype=jnp.int32),
        w_lat=jnp.zeros((capacity,), jnp.int32),
        t_cnt=jnp.zeros((capacity,), jnp.int32),
        c_cnt=jnp.zeros((capacity,), jnp.int32),
        a_cnt=jnp.zeros((capacity,), jnp.int32),
        clock=jnp.zeros((capacity,), jnp.int8),
    )


def _probe_slots(key: jax.Array, capacity: int, probes: int) -> jax.Array:
    """Probe sequence: (h(k) + i*step) mod C, step odd => full cycle for C=2^m."""
    h = _hash_u32(key)
    step = (_hash_u32(key + 0x9E3779B9) | jnp.uint32(1)).astype(jnp.uint32)
    i = jnp.arange(probes, dtype=jnp.uint32)
    return ((h + i * step) % jnp.uint32(capacity)).astype(jnp.int32)


def probe_slots_batch(keys: jax.Array, capacity: int, probes: int = 8) -> jax.Array:
    """[K] keys -> [K, P] probe slots (vectorized double hashing)."""
    h = _hash_u32(keys)
    step = _hash_u32(keys + jnp.int32(0x9E3779B9 - 2**32)) | jnp.uint32(1)
    i = jnp.arange(probes, dtype=jnp.uint32)
    return ((h[:, None] + i[None, :] * step[:, None]) % jnp.uint32(capacity)).astype(
        jnp.int32
    )


@jax.named_scope("repro/hotspot")
def find_or_claim_slots(
    slot_key: jax.Array, keys: jax.Array, valid: jax.Array, probes: int = 8
):
    """Batched find-or-insert for the engine's hot-record table.

    slot_key: [C] stored keys (-1 empty). keys/valid: [K].
    Returns (slots [K] int32 — C (scratch) for invalid entries, evict [K] bool —
    True when the slot held a *different* key and its stats must be reset).

    Two distinct keys in one batch may race for the same empty slot; the loser's
    update lands on the winner's entry. This is a benign, deterministic
    approximation (the table is a heuristic cache, like the paper's LRU list).
    """
    capacity = slot_key.shape[0] - 1  # last row is scratch
    pr = probe_slots_batch(keys, capacity, probes)  # [K,P]
    at = slot_key[pr]
    match = at == keys[:, None]
    empty = at == _EMPTY
    has_match = jnp.any(match, axis=1)
    has_empty = jnp.any(empty, axis=1)
    first_match = pr[jnp.arange(pr.shape[0]), jnp.argmax(match, axis=1)]
    first_empty = pr[jnp.arange(pr.shape[0]), jnp.argmax(empty, axis=1)]
    victim = pr[:, 0]
    slot = jnp.where(has_match, first_match, jnp.where(has_empty, first_empty, victim))
    slot = jnp.where(valid, slot, capacity)
    evict = valid & ~has_match
    return slot, evict


@jax.named_scope("repro/hotspot")
def claim_keys(
    slot_key: jax.Array, slot: jax.Array, keys: jax.Array, valid: jax.Array
) -> jax.Array:
    """Store the claimed keys at their `find_or_claim_slots` slots.

    When two keys race for one slot, the later key in the batch wins, as in a
    scatter applied in order. XLA leaves the winner of a scatter with
    duplicate indices to the backend, and a TPU may pick differently in each
    program; so the race is settled here and the scatter's indices are unique
    (losers and invalid entries rewrite the scratch row with its own value).
    """
    capacity = slot_key.shape[0] - 1
    K = slot.shape[0]
    later = jnp.arange(K)[None, :] > jnp.arange(K)[:, None]  # [i, j]: j after i
    lost = jnp.any(later & (slot[:, None] == slot[None, :]) & valid[None, :], axis=1)
    win = valid & ~lost
    return slot_key.at[jnp.where(win, slot, capacity)].set(
        jnp.where(win, keys, slot_key[capacity])
    )


@jax.named_scope("repro/hotspot")
def eq4_masked_w(
    w_lat: jax.Array,
    slot: jax.Array,
    found: jax.Array,
    lel: jax.Array,
    alpha_milli: int,
) -> jax.Array:
    """Eq.(4) share/EWMA/clip over one footprint's records (trailing axis).

    slot/found: [..., K] hash-table slots + hit mask for a subtransaction's
    footprint, grouped per subtransaction along every leading axis;
    lel: float32, broadcastable against [..., 1] (the measured LEL).
    Returns the updated w_lat values [..., K] int32 (meaningful where found).

    Single source for every engine path that applies the update — the
    sequential handler, the branchless omnibus step and the windowed drain
    must agree bitwise, like `commit_decision` / `ewma_update_where`.
    """
    vf = found.astype(jnp.float32)
    w_old = w_lat[slot].astype(jnp.float32) * vf
    total = jnp.sum(w_old, axis=-1, keepdims=True)
    n = jnp.maximum(jnp.sum(vf, axis=-1, keepdims=True), 1.0)
    share = jnp.where(total > 0.0, w_old / jnp.maximum(total, 1.0), vf / n)
    a = jnp.float32(alpha_milli / 1000.0)
    return jnp.clip(w_old * a + lel * share * (1.0 - a), 0.0, 1e7).astype(jnp.int32)


@jax.named_scope("repro/hotspot")
def lookup_slots(
    slot_key: jax.Array, keys: jax.Array, valid: jax.Array, probes: int = 8
) -> tuple[jax.Array, jax.Array]:
    """Batched read-only lookup: [K] keys -> ([K] slots, [K] found).
    Misses (cold records) map to the scratch row (capacity index)."""
    capacity = slot_key.shape[0] - 1
    pr = probe_slots_batch(keys, capacity, probes)
    at = slot_key[pr]
    match = at == keys[:, None]
    found = jnp.any(match, axis=1) & valid
    slot = jnp.where(
        found, pr[jnp.arange(pr.shape[0]), jnp.argmax(match, axis=1)], capacity
    )
    return slot, found


def hash_lookup(hs: HashHotspot, key: jax.Array, probes: int = 8):
    """Returns (slot, found). Vectorize with vmap for batches."""
    capacity = hs.slot_key.shape[0]
    slots = _probe_slots(key, capacity, probes)
    match = hs.slot_key[slots] == key
    found = jnp.any(match)
    slot = jnp.where(found, slots[jnp.argmax(match)], -1)
    return slot, found


def hash_touch(hs: HashHotspot, key: jax.Array, probes: int = 8):
    """Find-or-insert `key`; evicts via clock second-chance within the probe
    window when full. Returns (hs, slot)."""
    capacity = hs.slot_key.shape[0]
    slots = _probe_slots(key, capacity, probes)
    keys_at = hs.slot_key[slots]
    match = keys_at == key
    empty = keys_at == _EMPTY
    found = jnp.any(match)
    has_empty = jnp.any(empty)
    # victim: first clock==0 slot in window, else first slot in window
    clocks = hs.clock[slots]
    cold = clocks == 0
    victim_in = jnp.where(jnp.any(cold), slots[jnp.argmax(cold)], slots[0])
    slot = jnp.where(
        found, slots[jnp.argmax(match)], jnp.where(has_empty, slots[jnp.argmax(empty)], victim_in)
    )
    fresh = ~found
    hs = hs._replace(
        slot_key=hs.slot_key.at[slot].set(key),
        w_lat=hs.w_lat.at[slot].set(jnp.where(fresh, 0, hs.w_lat[slot])),
        t_cnt=hs.t_cnt.at[slot].set(jnp.where(fresh, 0, hs.t_cnt[slot])),
        c_cnt=hs.c_cnt.at[slot].set(jnp.where(fresh, 0, hs.c_cnt[slot])),
        a_cnt=hs.a_cnt.at[slot].set(jnp.where(fresh, 0, hs.a_cnt[slot])),
        clock=hs.clock.at[slot].set(1),
    )
    # age the rest of the probe window (approximate clock hand)
    hs = hs._replace(clock=hs.clock.at[slots].min(jnp.where(slots == slot, 1, 0).astype(jnp.int8)))
    return hs, slot
