"""`state._at_ds`, the per-data-source read by selects, against the gathers
it replaces: `jnp.take_along_axis(x, d, axis=-1)` for `[T,D]` rows and
`x[d]` for `[D]` vectors, bit for bit, for every index in `[0, D)`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import SimConfig, init_state
from repro.core.engine.state import _at_ds, _exec_us
from repro.core.protocols import PRESETS

T, K, LANES = 6, 5, 3


def _values(rng, shape, dtype):
    if dtype == jnp.bool_:
        return jnp.asarray(rng.random(shape) < 0.5)
    info = np.iinfo(dtype)
    return jnp.asarray(rng.integers(info.min, info.max, shape, endpoint=True), dtype)


def _read_rows(x, d):  # [T,D] by [T,K]
    return _at_ds(x, d), jnp.take_along_axis(x, d, axis=-1)


def _read_vector(x, d):  # [D] by any index shape
    return _at_ds(x, d), x[d]


# (value shape, index shape, reader, vmapped over a leading lane axis)
CASES = {
    "rows_TD_by_TK": ((T, None), (T, K), _read_rows, False),
    "vector_by_scalar": ((None,), (), _read_vector, False),
    "vector_by_TK": ((None,), (T, K), _read_vector, False),
    "vector_by_flat_TK": ((None,), (T * K,), _read_vector, False),
    "rows_TD_by_TK_vmap": ((T, None), (T, K), _read_rows, True),
    "vector_by_flat_TK_vmap": ((None,), (T * K,), _read_vector, True),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("D", [1, 2, 3, 4, 7])
@pytest.mark.parametrize("dtype", [jnp.bool_, jnp.int8, jnp.int32], ids=["bool", "int8", "int32"])
def test_at_ds_equals_gather(dtype, D, case):
    x_shape, d_shape, read, lanes = CASES[case]
    x_shape = tuple(D if n is None else n for n in x_shape)
    rng = np.random.default_rng(D * 1009 + list(CASES).index(case))
    if lanes:
        x_shape, d_shape = (LANES,) + x_shape, (LANES,) + d_shape
        read = jax.vmap(read)
    x = _values(rng, x_shape, dtype)
    d = jnp.asarray(rng.integers(0, D, d_shape), jnp.int8)
    got, want = jax.jit(read)(x, d)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("preset", ["ssp", "scalardb"])  # middleware_cc off / on
@pytest.mark.parametrize("index", ["scalar", "TK", "flat_TK"])
def test_exec_us_equals_gather(index, preset):
    D = 4
    cfg = SimConfig(terminals=T, max_ops=K, num_ds=D, bank_txns=8, proto=PRESETS[preset])
    s = init_state(
        cfg,
        jnp.asarray([0, 27_000, 73_000, 251_000], jnp.int32),
        jnp.zeros((D, D), jnp.int32),
        exec_scale_milli=jnp.asarray([700, 1000, 1333, 2500], jnp.int32),
    )
    shape = {"scalar": (), "TK": (T, K), "flat_TK": (T * K,)}[index]
    d = jnp.asarray(np.random.default_rng(7).integers(0, D, shape), jnp.int32)

    def gather_form(s, d):
        base = s.dyn.exec_us * s.exec_scale_milli[d] // 1000
        return base + jnp.where(s.dyn.middleware_cc, s.tau_mw_eff[d], 0)

    got = jax.jit(lambda s, d: _exec_us(cfg, s, d))(s, d)
    want = jax.jit(gather_form)(s, d)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
