"""Pallas TPU kernel: direct N-body summation (paper Figs 5.5/5.6 baseline).

Classic tiled all-pairs: targets tiled on the parallel grid axis, sources
streamed tile-by-tile on the arbitrary axis with the (T, S) pairwise block
evaluated in registers. This is the paper's 'task for which GPUs are
generally understood to be well suited' — it bounds the achievable speedup
of the full FMM (their direct speedup 15x vs FMM 11x; here it realizes
the compute roofline, see benchmarks/fig5_5.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import ZERO, resolve_interpret


def _nbody_kernel(tzr, tzi, szr, szi, sqr, sqi, outr, outi):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        outr[...] = jnp.zeros_like(outr)
        outi[...] = jnp.zeros_like(outi)

    dx = szr[0][None, :] - tzr[0][:, None]
    dy = szi[0][None, :] - tzi[0][:, None]
    denom = dx * dx + dy * dy
    ok = denom > 0.0
    inv = jnp.where(ok, 1.0 / jnp.where(ok, denom, 1.0), 0.0)
    qr = sqr[0][None, :]
    qi = sqi[0][None, :]
    outr[...] += ((qr * dx + qi * dy) * inv).sum(axis=1)[None, :]
    outi[...] += ((qi * dx - qr * dy) * inv).sum(axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("t_tile", "s_tile", "interpret"))
def _nbody_pallas(tzr, tzi, szr, szi, sqr, sqi, *, t_tile: int,
                  s_tile: int, interpret: bool):
    nt = tzr.shape[0] // t_tile
    ns = szr.shape[0] // s_tile

    def tmap(i, j):
        return (i, ZERO)

    def smap(i, j):
        return (j, ZERO)

    dt = tzr.dtype
    r2 = lambda a, n: a.reshape(-1, n)
    outr, outi = pl.pallas_call(
        _nbody_kernel,
        name="nbody",
        grid=(nt, ns),
        in_specs=[
            pl.BlockSpec((1, t_tile), tmap),
            pl.BlockSpec((1, t_tile), tmap),
            pl.BlockSpec((1, s_tile), smap),
            pl.BlockSpec((1, s_tile), smap),
            pl.BlockSpec((1, s_tile), smap),
            pl.BlockSpec((1, s_tile), smap),
        ],
        out_specs=[
            pl.BlockSpec((1, t_tile), tmap),
            pl.BlockSpec((1, t_tile), tmap),
        ],
        out_shape=[jax.ShapeDtypeStruct((nt, t_tile), dt)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r2(tzr, t_tile), r2(tzi, t_tile), r2(szr, s_tile), r2(szi, s_tile),
      r2(sqr, s_tile), r2(sqi, s_tile))
    return outr.reshape(-1), outi.reshape(-1)


def nbody_pallas(tzr, tzi, szr, szi, sqr, sqi, *, t_tile: int = 256,
                 s_tile: int = 512, interpret: bool | None = None):
    """All planes are 1-D (padded); returns (outr, outi) at target points.
    ``interpret=None`` auto-selects from the JAX platform."""
    return _nbody_pallas(tzr, tzi, szr, szi, sqr, sqi, t_tile=t_tile,
                         s_tile=s_tile, interpret=resolve_interpret(interpret))
