"""Float32 ``log``, complex ``log``, ``atan2`` and negation built from
ordinary float and integer arithmetic, for the logarithmic kernel.

On a TPU v5e XLA's float32 ``log`` is off by up to about 1e-4 absolute,
which the logarithmic kernel's sums q log|y - x| carry into Re phi at
20-100 times the float32 floor; Mosaic (the Pallas TPU compiler) has no
``atan2`` or ``atan`` at all, and lowers ``-x`` as ``0 - x``, which loses
the sign of a zero. The functions here use only multiplies, adds,
selects, one division and bit operations on the float's encoding, which
both XLA and Mosaic lower, so the reference backend and the Pallas
kernels compute the same function on the chip as on the CPU. Float64
arguments (CPU and interpret mode only: Mosaic has no float64) take
XLA's own functions.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

_SIGN = np.int32(-2**31)

#: log(1 + f) = f - f**2 / 2 + f**3 * P(f) on sqrt(1/2) - 1 <= f <
#: sqrt(2) - 1 (Cephes ``logf``), within one float32 ulp.
_LOG_P = (7.0376836292e-2, -1.1514610310e-1, 1.1676998740e-1,
          -1.2420140846e-1, 1.4249322787e-1, -1.6668057665e-1,
          2.0000714765e-1, -2.4999993993e-1, 3.3333331174e-1)
#: ln 2 = _LN2_HI + _LN2_LO, _LN2_HI with 9 significant bits, so that
#: e * _LN2_HI is exact for every float32 exponent e.
_LN2_HI, _LN2_LO = 0.693359375, -2.12194440e-4
#: The mantissa bits of sqrt(2) (0x3FB504F3): above them the mantissa is
#: halved and the exponent raised, so f stays within the fit.
_SQRT2_MANT = np.int32(0x3504F3)
_MANT = np.int32(0x7FFFFF)
#: int32 and float32 constants, so that no operand is 64-bit under
#: ``jax_enable_x64`` (Mosaic lowers no 64-bit scalar).
_ONE_BITS, _HALF_BITS = np.int32(0x3F800000), np.int32(0x3F000000)
_EXP_SHIFT, _BIAS, _BIAS_HALF = np.int32(23), np.int32(127), np.int32(126)
_NEG_INF, _NAN = np.float32(-np.inf), np.float32(np.nan)

#: atan(u) = u + u**3 * P(u**2) on |u| <= tan(pi/8): an odd fit, minimax
#: to within 5e-9 rad, below the float32 rounding of the result.
_ATAN_P = (-0.333327567246709, 0.19971881280401954, -0.13824474405617282,
           0.07902664199752493)
_TAN_PI_8 = 0.41421356237309503


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _float(bits):
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _sign_bit(x):
    """True where ``x`` has its sign bit set, -0.0 included."""
    return _bits(x) < 0


def negate(x):
    """``-x`` with the sign bit flipped, so that -(+0.0) is -0.0: Mosaic
    lowers ``-x`` on the chip as ``0 - x``, which gives +0.0 there, and
    the sign of a zero picks the log's branch in ``atan2``."""
    if x.dtype == jnp.float64:
        return -x
    return _float(_bits(x) ^ _SIGN)


def log(x):
    """Natural ``log`` of float32 ``x`` within 2 float32 ulps, as numpy's:
    log(+0) = -inf, log(+inf) = +inf, NaN for x < 0 and for NaN.
    Subnormal ``x`` reads as 0, as XLA reads it on the CPU and the TPU.

    x = 2**e * m with m in [sqrt(1/2), sqrt(2)) read from the float's
    exponent and mantissa bits, then log x = e ln 2 + log(1 + f),
    f = m - 1, by a polynomial."""
    if x.dtype == jnp.float64:
        return jnp.log(x)
    bits = _bits(x)
    mant = bits & _MANT
    big = mant > _SQRT2_MANT
    e = ((bits >> _EXP_SHIFT)
         - jnp.where(big, _BIAS_HALF, _BIAS)).astype(jnp.float32)
    f = _float(mant | jnp.where(big, _HALF_BITS, _ONE_BITS)) - 1.0
    z = f * f
    poly = _LOG_P[0]
    for c in _LOG_P[1:]:
        poly = poly * f + c
    y = f * z * poly + e * _LN2_LO - 0.5 * z
    y = (f + y) + e * _LN2_HI
    return jnp.where(x > 0, jnp.where(x < jnp.inf, y, x),
                     jnp.where(x == 0, _NEG_INF, _NAN))


def clog(z):
    """Complex ``log``: log|z| + i arg z, the real part from this
    module's ``log`` of |z|**2 for complex64 (|z| between 1e-19 and
    1e19, where |z|**2 is a normal float32) and the imaginary part from
    XLA's ``atan2``, with the branch cut of ``jnp.log``."""
    if z.dtype != jnp.complex64:
        return jnp.log(z)
    x, y = jnp.real(z), jnp.imag(z)
    return jax.lax.complex(0.5 * log(x * x + y * y), jnp.arctan2(y, x))


def atan2(y, x):
    """``arctan2(y, x)`` in float32 from ops Mosaic lowers: it has no
    ``atan2`` or ``atan``. Within 5e-7 rad of ``np.arctan2``, with its
    conventions exact: (+-0, x < 0) -> +-pi, (+-0, x > 0) -> +-0,
    (y != 0, +-0) -> pi/2 with the sign of y, and (+-0, +-0) finite
    (0, or +-pi for x = -0). The sign of a zero y picks the branch of
    ``log`` on the negative real axis, as the reference's complex log does.

    Range reduction to t = min(|x|, |y|) / max(|x|, |y|) in [0, 1], and
    above tan(pi/8) to (t - 1) / (t + 1) about pi/4, so one division
    and a short polynomial cover it; then the octant and quadrant
    fix-ups. Float64 planes run only in interpret mode (Mosaic has no
    float64), where XLA's own ``atan2`` keeps them float64-accurate.
    """
    if x.dtype == jnp.float64:
        return jnp.arctan2(y, x)
    ax, ay = jnp.abs(x), jnp.abs(y)
    hi, lo = jnp.maximum(ax, ay), jnp.minimum(ax, ay)
    big = lo > _TAN_PI_8 * hi
    num = jnp.where(big, lo - hi, lo)
    den = jnp.where(big, lo + hi, hi)
    u = num / jnp.where(den > 0, den, 1.0)
    s = u * u
    poly = _ATAN_P[-1]
    for c in _ATAN_P[-2::-1]:
        poly = poly * s + c
    a = u + u * s * poly
    a = jnp.where(big, a + np.pi / 4, a)
    a = jnp.where(ay > ax, np.pi / 2 - a, a)
    a = jnp.where(_sign_bit(x), np.pi - a, a)
    # a >= +0 here: take the sign of y by its bit (``-a`` would give +0)
    return _float(_bits(a) | (_bits(y) & _SIGN))
