"""Controls of the correctness check: the answer computed in a precision
below the configuration's, which the limits of ``bench/limits`` have to
refuse. A control run goes through the harness's own check and reads
``correct: false``.

Two kinds, both run by ``bench/calibrate.py --control <name>``, never by
the benchmark's own runs:

- ``direct-bf16``: the direct sum in bfloat16 in the program's place.
  The configurations state float32; the direct sum is elementwise, with
  no contraction, so its nearest lower precision is bfloat16: positions
  and charges rounded to bfloat16, each pairwise term formed in
  bfloat16, the sum over sources accumulated in float32.
  ``direct-f32``, the float32 direct sum, is the precision the
  configurations state, for comparison.
- ``m2l-high`` and ``m2l-bf16``: the program itself with the one matrix
  contraction on its path, the M2L kernel's ``jnp.dot`` (asked for at
  ``Precision.HIGHEST``), computed as a lower precision would: ``high``,
  three bfloat16 passes (bf16_3x), or one pass on operands rounded to
  bfloat16, the chip's default. The step a later change might take to
  speed the kernel up. Emulated by rounding the operands and summing
  exact products, so it reads the same on the CPU as on the chip.
"""
from __future__ import annotations

import contextlib
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

_DTYPES = {"bf16": "bfloat16", "f32": "float32"}
_TARGET_BLOCK = 8


@functools.partial(jax.jit, static_argnames=("dtype",))
def _harmonic(ze, zs, q, dtype):
    dt = jnp.dtype(dtype)
    xs, ys = zs.real.astype(dt), zs.imag.astype(dt)
    qr, qi = q.real.astype(dt), q.imag.astype(dt)

    def block(zb):
        dx = xs[None, :] - zb.real.astype(dt)[:, None]
        dy = ys[None, :] - zb.imag.astype(dt)[:, None]
        den = dx * dx + dy * dy
        ok = den != 0
        safe = jnp.where(ok, den, jnp.ones((), dt))
        # q / d = q * conj(d) / |d|^2
        tr = jnp.where(ok, (qr * dx + qi * dy) / safe, 0)
        ti = jnp.where(ok, (qi * dx - qr * dy) / safe, 0)
        return (tr.astype(jnp.float32).sum(-1)
                + 1j * ti.astype(jnp.float32).sum(-1))

    blocks = ze.reshape(-1, _TARGET_BLOCK)
    return jax.lax.map(block, blocks).reshape(-1)


def direct_potential(z_src, q, targets, kernel: str = "harmonic",
                     precision: str = "bf16") -> np.ndarray:
    """Direct sum at ``z_src[targets]`` over all ``(z_src, q)`` on the
    device, in ``precision`` ("bf16" or "f32"). Returns host complex128."""
    if kernel != "harmonic":
        raise NotImplementedError(f"control of the {kernel!r} kernel")
    targets = np.asarray(targets)
    pad = (-len(targets)) % _TARGET_BLOCK
    idx = np.concatenate([targets, np.repeat(targets[-1:], pad)])
    z_src = jnp.asarray(z_src)
    ze = z_src[jnp.asarray(idx)]
    out = _harmonic(ze, z_src, jnp.asarray(q), _DTYPES[precision])
    return np.asarray(out, np.complex128)[:len(targets)]


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(x.dtype)


def _split(x, passes: int):
    """``x`` as ``passes`` bfloat16 parts whose sum approximates it."""
    parts = []
    for _ in range(passes):
        hi = _bf16(x)
        parts.append(hi)
        x = x - hi
    return parts


def _lower_dot(precision: str):
    """``jnp.dot`` as ``precision`` ("high" or "bf16") computes it: the
    products of bfloat16 parts, each exact in float32, summed in float32."""

    def dot(a, b, **kw):
        kw["precision"] = jax.lax.Precision.HIGHEST
        if precision == "bf16":
            return jnp.dot(_bf16(a), _bf16(b), **kw)
        (a1, a2), (b1, b2) = _split(a, 2), _split(b, 2)
        return (jnp.dot(a1, b1, **kw) + jnp.dot(a1, b2, **kw)
                + jnp.dot(a2, b1, **kw))

    return dot


@contextlib.contextmanager
def m2l_contraction(precision: str):
    """Within the block, programs traced afresh compute the M2L kernel's
    contraction in ``precision`` ("high" or "bf16"). Enter it before the
    solver is built: programs already traced keep their precision."""
    from repro.kernels.m2l import m2l

    proxy = types.SimpleNamespace(**{k: getattr(jnp, k) for k in dir(jnp)
                                     if not k.startswith("__")})
    proxy.dot = _lower_dot(precision)
    saved = m2l.jnp
    m2l.jnp = proxy
    try:
        yield
    finally:
        m2l.jnp = saved
