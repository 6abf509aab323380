"""The program's own float32 ``log`` and complex ``log``
(``core.mathf``), which the logarithmic kernel takes in place of XLA's:
on a TPU v5e XLA's float32 ``log`` is off by up to about 1e-4. Against
numpy's on a million seeded points over the whole normal float32 range,
called plainly and inside an interpret-mode ``pallas_call`` (as the
kernels call it); numpy's special values exactly; and the complex
``log``'s branch cut, which picks Im phi's branch as ``jnp.log`` does."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.core.mathf import clog, log

#: Rows x lanes of the seeded points: 2**20 > 1e6.
SHAPE = (1024, 1024)
#: Within 2 float32 ulps of the float64 log (the polynomial reads 0.82
#: ulp on these points; XLA's own on the CPU 1.17).
ULPS = 2.0


def _points():
    g = np.random.default_rng(20261019)
    n = SHAPE[0] * SHAPE[1]
    # the normal float32 range, 1.2e-38..3.4e38, then a quarter in
    # [0.5, 2], where log is near 0 and its relative error shows
    x = 10.0 ** g.uniform(-37.9, 38.5, n)
    k = n // 4
    x[:k] = g.uniform(0.5, 2.0, k)
    return x.astype(np.float32).reshape(SHAPE)


def _plain(x):
    return jax.jit(log)(x)


def _interpret(x):
    def kernel(x_ref, o_ref):
        o_ref[...] = log(x_ref[...])

    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        x.shape, x.dtype), interpret=True)(x)


MODES = {"plain": _plain, "interpret": _interpret}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_log_within_2_ulps_on_a_million_points(mode):
    x = _points()
    got = np.asarray(MODES[mode](jnp.asarray(x)))
    assert got.dtype == np.float32
    ref = np.log(x.astype(np.float64))
    ulp = np.spacing(np.abs(ref).astype(np.float32)).astype(np.float64)
    err = np.abs(got.astype(np.float64) - ref) / ulp
    assert err.max() <= ULPS, (err.max(), x.ravel()[err.argmax()])


@pytest.mark.parametrize("mode", sorted(MODES))
def test_log_special_values_as_numpy(mode):
    vals = np.array([0.0, -0.0, np.inf, -np.inf, -1.0, np.nan, 1.0, 2.0,
                     0.5, np.finfo(np.float32).max,
                     np.finfo(np.float32).tiny], np.float32)
    x = np.ones((8, 128), np.float32)
    x[0, :len(vals)] = vals
    got = np.asarray(MODES[mode](jnp.asarray(x)))[0, :len(vals)]
    with np.errstate(divide="ignore", invalid="ignore"):
        ref = np.log(vals)
    np.testing.assert_array_equal(got[:7], ref[:7])      # -inf, inf, nan, 0
    np.testing.assert_allclose(got[7:], ref[7:], rtol=2 ** -23)


def test_log_float64_keeps_float64_accuracy():
    x = np.random.default_rng(5).uniform(1e-6, 10.0, 4096)
    np.testing.assert_allclose(np.asarray(log(jnp.asarray(x))), np.log(x),
                               rtol=1e-15)


def test_clog_matches_numpy_and_its_branch_cut():
    g = np.random.default_rng(11)
    z = (g.normal(size=4096) + 1j * g.normal(size=4096)) * 10.0 ** g.uniform(
        -6, 6, 4096)
    z[:4] = [complex(-1.0, 0.0), complex(-1.0, -0.0), complex(-3.0, 0.0),
             complex(0.0, -2.0)]
    z = z.astype(np.complex64)
    got = np.asarray(jax.jit(clog)(jnp.asarray(z)))
    assert got.dtype == np.complex64
    ref = np.log(z.astype(np.complex128))
    # real part: log|z| within 2 ulps of its magnitude plus the rounding
    # of |z|**2 (half an ulp of log 1, 6e-8)
    ulp = np.spacing(np.abs(ref.real).astype(np.float32)).astype(np.float64)
    assert (np.abs(got.real - ref.real) <= 2 * ulp + 6e-8).all()
    # imaginary part: XLA's own atan2, so jnp.log's branch at -0 and +0
    np.testing.assert_array_equal(got.imag, np.asarray(
        jnp.log(jnp.asarray(z))).imag)
    np.testing.assert_array_equal(np.signbit(got.imag[:2]), [False, True])
    assert np.asarray(clog(jnp.zeros((1,), jnp.complex64))).real[0] == -np.inf
