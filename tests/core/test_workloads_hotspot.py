"""Unit + property tests: workload generators and the hot-record table."""

import jax
import jax.numpy as jnp
import numpy as np

from _hypothesis_compat import given, settings, st

from repro.core import hotspot as hs
from repro.core import workloads


class TestYCSB:
    def test_bank_shapes_and_ranges(self):
        cfg = workloads.YCSBConfig(num_ds=4, records_per_node=1000, ops_per_txn=5)
        bank = workloads.make_ycsb_bank(cfg, terminals=8, txns_per_terminal=16)
        key = np.asarray(bank.key)
        ds = np.asarray(bank.ds)
        assert key.shape == (8, 16, 5)
        assert (key >= 0).all() and (key < 4000).all()
        # key's node prefix must equal the op's data source
        np.testing.assert_array_equal(key // 1000, ds)

    def test_keys_unique_within_txn(self):
        cfg = workloads.YCSBConfig(num_ds=2, records_per_node=200, ops_per_txn=8, theta=1.4)
        bank = workloads.make_ycsb_bank(cfg, terminals=4, txns_per_terminal=32)
        key = np.asarray(bank.key)
        for t in range(4):
            for n in range(32):
                row = key[t, n]
                per_ds = {}
                for k in row:
                    per_ds.setdefault(k // 200, []).append(k)
                assert len(row) == len(set(row.tolist())), row

    def test_zipf_skew_monotone(self):
        lo = workloads.make_ycsb_bank(
            workloads.YCSBConfig(records_per_node=10_000, theta=0.3), 8, 64
        )
        hi = workloads.make_ycsb_bank(
            workloads.YCSBConfig(records_per_node=10_000, theta=1.5), 8, 64
        )

        def top_frac(bank):
            local = np.asarray(bank.key) % 10_000
            return (local < 10).mean()

        assert top_frac(hi) > 5 * top_frac(lo)

    def test_dist_ratio(self):
        cfg = workloads.YCSBConfig(num_ds=4, records_per_node=1000, dist_ratio=0.5)
        bank = workloads.make_ycsb_bank(cfg, 16, 64)
        ds = np.asarray(bank.ds)
        n_nodes = np.array([len(set(row.tolist())) for row in ds.reshape(-1, 5)])
        frac = (n_nodes > 1).mean()
        assert 0.4 < frac < 0.6

    def test_quro_moves_writes_last(self):
        cfg = workloads.YCSBConfig(num_ds=2, records_per_node=1000, read_frac=0.5)
        bank = workloads.quro_reorder(workloads.make_ycsb_bank(cfg, 4, 16))
        w = np.asarray(bank.write)
        # once a write appears, everything after is a write
        first_w = np.argmax(w, axis=-1)
        for t in range(4):
            for n in range(16):
                if w[t, n].any():
                    assert w[t, n, first_w[t, n] :].all()

    def test_rounds_partition_ops(self):
        cfg = workloads.YCSBConfig(records_per_node=1000, ops_per_txn=6, rounds=3)
        bank = workloads.make_ycsb_bank(cfg, 2, 4)
        rid = np.asarray(bank.round_id)
        assert set(np.unique(rid)) == {0, 1, 2}
        assert (np.diff(rid, axis=-1) >= 0).all()  # nondecreasing in slot order


class TestTPCC:
    def test_bank_structure(self):
        cfg = workloads.TPCCConfig(num_ds=2, warehouses_per_node=2, dist_ratio=0.3)
        bank, ttype = workloads.make_tpcc_bank(cfg, terminals=8, txns_per_terminal=32)
        assert bank.key.shape == (8, 32, workloads.TPCC_MAX_OPS)
        valid = np.asarray(bank.valid)
        key = np.asarray(bank.key)
        assert (key[valid] >= 0).all() and (key[valid] < bank.num_records).all()
        # payment txns have exactly 3 ops; neworder 13
        nops = valid.sum(-1)
        assert (nops[ttype == workloads.TPCC_PAYMENT] == 3).all()
        assert (nops[ttype == workloads.TPCC_NEWORDER] == 13).all()

    def test_payment_warehouse_is_exclusive(self):
        cfg = workloads.TPCCConfig(num_ds=1, warehouses_per_node=2, only_type=workloads.TPCC_PAYMENT)
        bank, _ = workloads.make_tpcc_bank(cfg, 4, 8)
        w = np.asarray(bank.write)
        v = np.asarray(bank.valid)
        assert w[v].all()  # payment ops are all writes


class TestHashHotspot:
    def test_find_claim_and_lookup(self):
        t = hs.hash_init(65)  # 64 slots + scratch
        keys = jnp.asarray([5, 9, 13, -1], jnp.int32)
        valid = jnp.asarray([True, True, True, False])
        slot, evict = hs.find_or_claim_slots(t.slot_key, keys, valid)
        t = t._replace(slot_key=t.slot_key.at[slot].set(jnp.where(valid, keys, -1)))
        s2, found = hs.lookup_slots(t.slot_key, keys, valid)
        np.testing.assert_array_equal(np.asarray(found), [True, True, True, False])
        np.testing.assert_array_equal(np.asarray(s2[:3]), np.asarray(slot[:3]))

    def test_claim_race_goes_to_the_later_key(self):
        # two keys racing for slot 3: the later one is stored, as a scatter
        # applied in order would; invalid entries leave the table alone
        t = hs.hash_init(9)
        slot = jnp.asarray([3, 5, 3, 8, 3], jnp.int32)
        keys = jnp.asarray([10, 20, 30, 40, 50], jnp.int32)
        valid = jnp.asarray([True, True, True, False, False])
        want = np.full((9,), -1, np.int32)
        for s, k, v in zip(np.asarray(slot), np.asarray(keys), np.asarray(valid)):
            if v:
                want[s] = k
        got = hs.claim_keys(t.slot_key, slot, keys, valid)
        np.testing.assert_array_equal(np.asarray(got), want)
        assert int(got[3]) == 30 and int(got[8]) == -1

    def test_miss_maps_to_scratch(self):
        t = hs.hash_init(33)
        slot, found = hs.lookup_slots(t.slot_key, jnp.asarray([7], jnp.int32), jnp.asarray([True]))
        assert not bool(found[0])
        assert int(slot[0]) == 32  # scratch row

    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.integers(0, 10_000), min_size=1, max_size=16, unique=True))
    def test_claimed_keys_findable(self, keys):
        t = hs.hash_init(257)
        ka = jnp.asarray(keys, jnp.int32)
        valid = jnp.ones((len(keys),), bool)
        slot, _ = hs.find_or_claim_slots(t.slot_key, ka, valid)
        sk = t.slot_key.at[slot].set(ka)
        # within-batch slot races may drop a key; every *stored* key is findable
        _, found = hs.lookup_slots(sk, ka, valid)
        stored = set(np.asarray(sk).tolist())
        for k, f in zip(keys, np.asarray(found)):
            if k in stored:
                assert f
