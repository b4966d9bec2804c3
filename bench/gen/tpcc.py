"""TPC-C bank (a copy of the program's generator).

NewOrder/Payment/OrderStatus/Delivery/StockLevel as record-level S/X lock
footprints over warehouse, district, customer and stock rows, NURand skew,
remote warehouses (Payment) and remote stock (NewOrder) for the distributed
share. The op slots are 21 wide (StockLevel: 1 district + 20 stock reads).
"""

from __future__ import annotations

import numpy as np

from bench.gen.ycsb import dedup_linear

N_DIST = 10
N_CUST_PER_DIST = 3000
N_STOCK = 100_000
MAX_OPS = 21
NEWORDER, PAYMENT, ORDERSTATUS, DELIVERY, STOCKLEVEL = range(5)


def _nurand(rng: np.random.Generator, A: int, n: int, shape) -> np.ndarray:
    """TPC-C NURand non-uniform distribution."""
    C = 123 % (A + 1)
    x = rng.integers(0, A + 1, size=shape)
    y = rng.integers(0, n, size=shape)
    return (((x | y) + C) % n).astype(np.int64)


def make(params: dict, terminals: int, txns_per_terminal: int, seed: int) -> dict:
    rng = np.random.default_rng(np.random.PCG64(seed + 1))
    T, N, K = terminals, txns_per_terminal, MAX_OPS
    D, W = params["num_ds"], params["warehouses_per_node"]
    span = W * (1 + N_DIST + N_DIST * N_CUST_PER_DIST + N_STOCK)

    def wh_key(node, w):
        return node * span + w

    def dist_key(node, w, d):
        return node * span + W + w * N_DIST + d

    def cust_key(node, w, d, c):
        return node * span + W * (1 + N_DIST) + (w * N_DIST + d) * N_CUST_PER_DIST + c

    def stock_key(node, w, i):
        return node * span + W * (1 + N_DIST + N_DIST * N_CUST_PER_DIST) + w * N_STOCK + i

    key = np.zeros((T, N, K), dtype=np.int64)
    write = np.zeros((T, N, K), dtype=bool)
    ds = np.zeros((T, N, K), dtype=np.int8)
    valid = np.zeros((T, N, K), dtype=bool)
    is_dist = np.zeros((T, N), dtype=bool)

    ty = rng.choice(5, size=(T, N), p=np.asarray(params["mix"]))
    node = rng.integers(0, D, size=(T, N))
    w = rng.integers(0, W, size=(T, N))
    d = rng.integers(0, N_DIST, size=(T, N))
    c = _nurand(rng, 1023, N_CUST_PER_DIST, (T, N))
    remote = rng.random((T, N)) < params["dist_ratio"]
    rnode = (node + rng.integers(1, D, size=(T, N))) % D if D > 1 else node

    def put(mask, slot, k, wr, nd):
        key[mask, slot] = k[mask]
        write[mask, slot] = wr
        ds[mask, slot] = nd[mask]
        valid[mask, slot] = True

    # NewOrder: S(warehouse), X(district), S(customer), X(stock) x 10
    m = ty == NEWORDER
    put(m, 0, wh_key(node, w), False, node)
    put(m, 1, dist_key(node, w, d), True, node)
    put(m, 2, cust_key(node, w, d, c), False, node)
    items = dedup_linear(_nurand(rng, 8191, N_STOCK, (T, N, 10)), N_STOCK)
    # distributed NewOrder: items 8-9 come from a remote node's stock
    for j in range(10):
        nd = np.where(m & remote & (j >= 8), rnode, node)
        put(m, 3 + j, stock_key(nd, w, items[..., j]), True, nd)
    is_dist |= m & remote

    # Payment: X(warehouse), X(district), X(customer, remote when distributed)
    m = ty == PAYMENT
    put(m, 0, wh_key(node, w), True, node)
    put(m, 1, dist_key(node, w, d), True, node)
    cnode = np.where(remote, rnode, node)
    cw = rng.integers(0, W, size=(T, N))
    put(m, 2, cust_key(cnode, cw, d, c), True, cnode)
    is_dist |= m & remote

    # OrderStatus: S(customer)
    m = ty == ORDERSTATUS
    put(m, 0, cust_key(node, w, d, c), False, node)

    # Delivery: X(customer) x 10 (one per district)
    m = ty == DELIVERY
    cs = rng.integers(0, N_CUST_PER_DIST, size=(T, N, N_DIST))
    for j in range(N_DIST):
        put(m, j, cust_key(node, w, np.full_like(d, j), cs[..., j]), True, node)

    # StockLevel: S(district), S(stock) x 20
    m = ty == STOCKLEVEL
    put(m, 0, dist_key(node, w, d), False, node)
    sl_items = dedup_linear(rng.integers(0, N_STOCK, size=(T, N, 20)), N_STOCK)
    for j in range(20):
        put(m, 1 + j, stock_key(node, w, sl_items[..., j]), False, node)

    return dict(
        key=key.astype(np.int32), write=write, ds=ds,
        round_id=np.zeros((T, N, K), dtype=np.int8), valid=valid,
        is_dist=is_dist, num_records=D * span, num_ds=D,
    )
