"""Strict two-phase locking, held against a world's lock state.

A plain model of the rules the configurations state, written apart from
the reference's lock code (`bench.ref.locks`, a copy of the program's):
it reads only the op arrays of a final state, where an op that executes or
holds (EXEC, HOLD) holds a lock on its record, exclusive if it writes, and
an op that waits (WAIT) is queued for one, since the time in ``op_enq``.

- Compatibility: a record held exclusively has no other holder.
- FIFO order: no op holds a record while an op it conflicts with (either
  of the two writes) has waited for that record since an earlier time.

`violations` counts the pairs of ops that break either rule; a sound run
has none at any moment, and the check reads it on every world the window
produced.
"""

from __future__ import annotations

import numpy as np

from bench.ref.state import OP_EXEC, OP_HOLD, OP_WAIT


def violations(state) -> int:
    """Pairs of ops on one record that hold together though one of them
    writes, or of which the one that holds was queued after the other,
    which waits, and one of them writes."""
    st = np.asarray(state.op_state).reshape(-1)
    hold = (st == OP_EXEC) | (st == OP_HOLD)
    live = np.flatnonzero(hold | (st == OP_WAIT))
    key = np.asarray(state.op_key).reshape(-1)[live]
    write = np.asarray(state.op_write).reshape(-1)[live]
    enq = np.asarray(state.op_enq).reshape(-1)[live].astype(np.int64)
    hold = hold[live]
    order = np.argsort(key, kind="stable")
    key, write, enq, hold = key[order], write[order], enq[order], hold[order]
    bounds = np.flatnonzero(np.diff(key)) + 1
    n = 0
    for lo, hi in zip(np.r_[0, bounds], np.r_[bounds, len(key)]):
        if hi - lo < 2:
            continue
        h, w, e = hold[lo:hi], write[lo:hi], enq[lo:hi]
        xh, sh = int(np.sum(h & w)), int(np.sum(h & ~w))
        n += xh * (xh - 1) // 2 + xh * sh
        # (holder, waiter) pairs: the holder queued later, and one writes
        later = e[h][:, None] > e[~h][None, :]
        conflict = w[h][:, None] | w[~h][None, :]
        n += int(np.sum(later & conflict))
    return n
