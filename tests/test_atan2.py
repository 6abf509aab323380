"""The program's own ``atan2`` (``core.mathf.atan2``), which Mosaic
lowers where it has no ``atan2`` or ``atan``: against ``np.arctan2`` on
a million seeded points of all four quadrants, |y/x| from 1e-30 to 1e30,
called plainly and inside an interpret-mode ``pallas_call``; and its
conventions at signed zeros and on the axes, exactly. Those pick the
logarithm's branch on the negative real axis: equal float32 coordinates
occur in clustered pair lists at N = 2**20, and a wrong sign of zero
there moves Im phi by 2 pi q."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.core.mathf import atan2

#: Rows x lanes of the seeded points: 2**20 > 1e6.
SHAPE = (1024, 1024)
#: The acceptance bound: within 5e-7 rad of ``np.arctan2`` (two float32
#: ulps at pi; the polynomial itself is within 5e-9).
TOL = 5e-7


def _points():
    g = np.random.default_rng(20261019)
    n = SHAPE[0] * SHAPE[1]
    # magnitudes 1e-15..1e15 each, so |y/x| spans 1e-30..1e30; then a
    # quarter of ordinary normal points, where most pairs of a tile lie
    x = g.choice([-1.0, 1.0], n) * 10.0 ** g.uniform(-15, 15, n)
    y = g.choice([-1.0, 1.0], n) * 10.0 ** g.uniform(-15, 15, n)
    k = n // 4
    x[:k], y[:k] = g.normal(size=k), g.normal(size=k)
    return (y.astype(np.float32).reshape(SHAPE),
            x.astype(np.float32).reshape(SHAPE))


def _plain(y, x):
    return jax.jit(atan2)(y, x)


def _interpret(y, x):
    def kernel(y_ref, x_ref, o_ref):
        o_ref[...] = atan2(y_ref[...], x_ref[...])

    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        y.shape, y.dtype), interpret=True)(y, x)


MODES = {"plain": _plain, "interpret": _interpret}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_atan2_within_5e_7_rad_on_a_million_points(mode):
    y, x = _points()
    got = np.asarray(MODES[mode](jnp.asarray(y), jnp.asarray(x)))
    assert got.dtype == np.float32
    ref = np.arctan2(y.astype(np.float64), x.astype(np.float64))
    err = np.abs(got.astype(np.float64) - ref)
    assert err.max() < TOL, (err.max(), np.unravel_index(err.argmax(),
                                                         SHAPE))
    # every quadrant and both octants of each are covered
    for sy in (1, -1):
        for sx in (1, -1):
            quad = (np.sign(y) == sy) & (np.sign(x) == sx)
            assert (quad & (np.abs(y) > np.abs(x))).any()
            assert (quad & (np.abs(y) < np.abs(x))).any()


_VALUES = [0.0, -0.0, 1.0, -1.0, 3e-30, -3e-30, 7e25, -7e25]


@pytest.mark.parametrize("mode", sorted(MODES))
def test_atan2_conventions_at_zeros_and_axes_are_exact(mode):
    """(+-0, x < 0) -> +-pi, (+-0, x > 0) -> +-0, (y != 0, +-0) -> pi/2
    with the sign of y, (+-0, +-0) as numpy: every value and every sign
    of zero equal to ``np.arctan2`` in float32."""
    pairs = [(yv, xv) for yv in _VALUES for xv in _VALUES
             if yv == 0 or xv == 0]
    y = np.array([p[0] for p in pairs], np.float32).reshape(1, -1)
    x = np.array([p[1] for p in pairs], np.float32).reshape(1, -1)
    y, x = np.pad(y, ((0, 7), (0, 128 - y.shape[1]))), np.pad(
        x, ((0, 7), (0, 128 - x.shape[1])), constant_values=1.0)
    got = np.asarray(MODES[mode](jnp.asarray(y), jnp.asarray(x)))[0]
    ref = np.arctan2(y, x)[0]
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(np.signbit(got), np.signbit(ref))
    pi, half = np.float32(np.pi), np.float32(np.pi / 2)
    for (yv, xv), a in zip(pairs, got):
        if yv == 0 and xv != 0:       # on the real axis: +-0 or +-pi
            want = np.copysign(pi if xv < 0 else np.float32(0), yv)
            assert a == want and np.signbit(a) == np.signbit(yv)
        elif xv == 0 and yv != 0:     # on the imaginary axis: +-pi/2
            assert a == np.copysign(half, yv)


def test_atan2_float64_planes_keep_float64_accuracy():
    """Float64 planes run only in interpret mode (Mosaic has no float64);
    there the helper is XLA's own ``atan2``."""
    g = np.random.default_rng(7)
    y, x = g.normal(size=4096), g.normal(size=4096)
    got = np.asarray(atan2(jnp.asarray(y), jnp.asarray(x)))
    np.testing.assert_allclose(got, np.arctan2(y, x), rtol=0, atol=1e-15)
