"""Pallas TPU kernel: direct particle -> local-expansion shifts (P2L).

The Carrier-Greengard swapped-theta pairs at the leaf level route the
*larger* box's particles directly into the *smaller* box's local
expansion (paper §2). The reference implementation is a jnp scan over
list slots (``core/fmm.py:p2l_sweep``); this kernel is its Pallas twin
so the downward pass of the ``pallas`` backend no longer falls back to a
reference sweep.

Grid step = a tile of ``tile_boxes`` target boxes: the (TB, P)
local-coefficient output block stays resident in VMEM across the whole
p2l list; each step stages ``TB * stage_width`` source-box particle rows
(positions + strengths) through scalar-prefetch BlockSpecs. Per staged
row the kernel forms inv = 1/(x - z0_t) and w = rho_t * inv in vector
registers, runs the power recurrence over the p+1 coefficients and
lane-reduces each into its (TB, 1) output column. P2L lives in the
*downward* launch (not the evaluation megakernel) because its output is
local coefficients consumed by L2L/L2P — fusing it into evaluation would
re-introduce the HBM round-trip it exists to avoid (see DESIGN.md §2).
The grid is batch-major — (B, ntile, steps), ``program_id(0)`` selecting
the problem — so ``jax.vmap`` of ``p2l_pallas`` folds B problems into
one launch via the op's custom batching rule.

Both G-kernels: "harmonic" b~_l = rho^l sum q/(x-z0)^(l+1) and "log"
(b~_0 = sum q log(z0-x), b~_l = -rho^l sum q/(l (x-z0)^l)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.mathf import atan2, log
from ..common import (ZERO, launch_name, make_batched_op, pad_boxes,
                      prefetch_row_specs, resolve_interpret, row_view,
                      run_chunked, staged_lists)


def _make_kernel(p: int, P: int, kernel: str, TB: int, SW: int):
    n = TB * SW

    def body(lists_ref, z0r_ref, z0i_ref, rho_ref, *rest):
        xzr_refs, xzi_refs = rest[:n], rest[n:2 * n]
        xqr_refs, xqi_refs = rest[2 * n:3 * n], rest[3 * n:4 * n]
        outr, outi = rest[4 * n], rest[4 * n + 1]
        s = pl.program_id(2)

        @pl.when(s == 0)
        def _init():
            outr[...] = jnp.zeros_like(outr)
            outi[...] = jnp.zeros_like(outi)

        z0r = z0r_ref[...]                    # (TB, 1) target centers
        z0i = z0i_ref[...]
        rho = rho_ref[...]                    # (TB, 1) target radii

        def tile(refs, o):
            return jnp.concatenate([r[...] for r in refs[o:o + TB]], axis=0)

        for w in range(SW):
            o = w * TB
            xr, xi = tile(xzr_refs, o), tile(xzi_refs, o)   # (TB, n_pad)
            qr, qi = tile(xqr_refs, o), tile(xqi_refs, o)
            dxr = xr - z0r                    # x - z0_t
            dxi = xi - z0i
            d2 = dxr * dxr + dxi * dxi
            # d2 > 0 masks padded/dummy lanes (x = 0, q = 0) without a
            # staged validity plane; the cost is that a real source
            # particle EXACTLY at the target box center contributes 0
            # where the reference scan goes singular — a measure-zero
            # geometry (another box's particle at this box's
            # shrink-to-fit midpoint), accepted to keep the operand
            # count down.
            ok = d2 > 0.0
            k = jnp.where(ok, 1.0 / jnp.where(ok, d2, 1.0), 0.0)
            invr = dxr * k                    # 1 / (x - z0_t)
            invi = -dxi * k
            wr = rho * invr                   # rho_t / (x - z0_t)
            wi = rho * invi

            def red(a):                       # lane-reduce -> (TB, 1)
                return a.sum(axis=-1, keepdims=True)

            if kernel == "harmonic":
                pwr = qr * invr - qi * invi
                pwi = qr * invi + qi * invr
                cols_r, cols_i = [], []
                for _ in range(p + 1):
                    cols_r.append(red(pwr))
                    cols_i.append(red(pwi))
                    nr = pwr * wr - pwi * wi
                    ni = pwr * wi + pwi * wr
                    pwr, pwi = nr, ni
            else:
                # b~_0 = sum q log(z0 - x); z0 - x formed as such, not
                # as -(x - z0), so a tie takes the zero's sign (and the
                # log's branch) the reference's complex z0 - x has
                lr = jnp.where(ok, 0.5 * log(jnp.where(ok, d2, 1.0)),
                               0.0)
                li = jnp.where(ok, atan2(z0i - xi, z0r - xr), 0.0)
                cols_r = [red(qr * lr - qi * li)]
                cols_i = [red(qr * li + qi * lr)]
                pwr = qr * wr - qi * wi
                pwi = qr * wi + qi * wr
                for l in range(1, p + 1):
                    cols_r.append(-red(pwr) / l)
                    cols_i.append(-red(pwi) / l)
                    nr = pwr * wr - pwi * wi
                    ni = pwr * wi + pwi * wr
                    pwr, pwi = nr, ni
            zpad = [jnp.zeros_like(cols_r[0])] * (P - p - 1)
            outr[...] += jnp.concatenate(cols_r + zpad, axis=1)
            outi[...] += jnp.concatenate(cols_i + zpad, axis=1)

    return body


@functools.partial(jax.jit, static_argnames=("p", "P", "kernel",
                                             "tile_boxes", "stage_width",
                                             "interpret"))
def _p2l_pallas(lists, z0r, z0i, rho, xzr, xzi, xqr, xqi, *, p: int, P: int,
                kernel: str, tile_boxes: int, stage_width: int,
                interpret: bool):
    """Batch-major core: lists (B, nbox, S), z0r/z0i/rho (B, nbox),
    particle planes (B, nbox+1, n_pad)."""
    B, nbox, _ = lists.shape
    n_pad = xzr.shape[-1]
    TB, SW = tile_boxes, stage_width
    dummy = xzr.shape[-2] - 1

    lists, nchunk, (steps,) = staged_lists([lists], dummy, TB, SW)
    rows = lists.shape[1]

    def col(a):
        return pad_boxes(a.reshape(B, -1, 1), rows)

    n = TB * SW
    xzr, xzi, xqr, xqi = map(row_view, (xzr, xzi, xqr, xqi))
    shared = [*([xzr] * n), *([xzi] * n), *([xqr] * n), *([xqi] * n)]

    def tgt_map(b, i, s, lref):
        return (b, i, ZERO)

    in_specs = ([pl.BlockSpec((None, TB, 1), tgt_map)] * 3
                + prefetch_row_specs(TB, SW, n_pad) * 4)
    dt = xzr.dtype

    def launch(lists, z0r, z0i, rho):
        crows = lists.shape[1]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, crows // TB, steps),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((None, TB, P), tgt_map),
                pl.BlockSpec((None, TB, P), tgt_map),
            ],
        )
        return pl.pallas_call(
            _make_kernel(p, P, kernel, TB, SW),
            name=launch_name("p2l", kernel),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, crows, P), dt)] * 2,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(lists, z0r, z0i, rho, *shared)

    outr, outi = run_chunked(launch, nchunk,
                             [lists, col(z0r), col(z0i), col(rho)])
    return outr[:, :nbox], outi[:, :nbox]


@functools.lru_cache(maxsize=None)
def _p2l_op(p: int, P: int, kernel: str, tile_boxes: int, stage_width: int,
            interpret: bool):
    """Per-problem P2L op; its custom batching rule lowers ``jax.vmap``
    onto the batch-major kernel grid (one launch for B problems)."""
    return make_batched_op(functools.partial(
        _p2l_pallas, p=p, P=P, kernel=kernel, tile_boxes=tile_boxes,
        stage_width=stage_width, interpret=interpret))


def p2l_pallas(lists, z0r, z0i, rho, xzr, xzi, xqr, xqi, *, p: int, P: int,
               kernel: str = "harmonic", tile_boxes: int = 8,
               stage_width: int = 1, interpret: bool | None = None):
    """lists: (nbox, S) int32 p2l list (-1 masked). z0r/z0i/rho: (nbox,)
    target-box center/radius; xzr/xzi/xqr/xqi: (nbox+1, n_pad) dense
    particle planes (dummy row zero). Returns (outr, outi): (nbox, P)
    radius-normalized local-coefficient contributions.
    ``interpret=None`` auto-selects from the JAX platform. Batch-native:
    under ``jax.vmap``, B problems compile to ONE batch-major launch.
    """
    op = _p2l_op(p, P, kernel, tile_boxes, stage_width,
                 resolve_interpret(interpret))
    return op(lists, z0r, z0i, rho, xzr, xzi, xqr, xqi)


def p2l_pallas_batched(lists, z0r, z0i, rho, xzr, xzi, xqr, xqi, *, p: int,
                       P: int, kernel: str = "harmonic", tile_boxes: int = 8,
                       stage_width: int = 1, interpret: bool | None = None):
    """Batch-major entry: all operands carry a leading problem axis B;
    one (B, ntile, steps) launch returns (B, nbox, P) planes."""
    return _p2l_pallas(lists, z0r, z0i, rho, xzr, xzi, xqr, xqi, p=p, P=P,
                       kernel=kernel, tile_boxes=tile_boxes,
                       stage_width=stage_width,
                       interpret=resolve_interpret(interpret))
