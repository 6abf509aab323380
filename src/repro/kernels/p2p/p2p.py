"""Pallas TPU kernel: near-field direct evaluation over leaf P2P lists.

This is the paper's Algorithm 3.7 (43% of GPU runtime, Table 5.1) mapped to
the TPU memory hierarchy. The CUDA version stages source positions for one
interaction box at a time into 48 kB shared memory with one block per target
box; here a grid step owns a *tile* of ``tile_boxes`` target boxes
(DESIGN.md §2): the (TB, n_pad) target planes and the revisited (TB, n_pad)
output block stay resident in VMEM across the whole interaction list, and
each step stages ``tile_boxes * stage_width`` source-box rows from HBM via
*scalar-prefetch indexed BlockSpecs* — the interaction list itself rides in
SMEM and selects which block of the dense leaf array to DMA, so the hot
loop contains no gather at all (the static leaf layout of the asymmetric
tree is what makes this possible). Pallas double-buffers the streaming
source tiles, overlapping the next DMA with the (TB, n_pad, n_pad)
pairwise tile evaluated in VREGs.

Grid: batch-major (B, ceil(nbox/TB), ceil(S/SW)); ``program_id(0)``
selects the problem, the output is revisited across the list axis ->
accumulate in place ("arbitrary" on it). B problems lengthen the grid
without touching the per-step VMEM working set; ``jax.vmap`` of
``p2p_pallas`` lowers onto this grid via the op's custom batching rule.

Both G-kernels: "harmonic" q/(x - z) and "log" q*log(z - x).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import (ZERO, make_batched_op, pad_boxes, pairwise_tile,
                      prefetch_row_specs, resolve_interpret, row_view,
                      run_chunked, staged_lists)


def _make_kernel(kernel: str, TB: int, SW: int):
    def body(lists_ref, tzr_ref, tzi_ref, trk_ref, *rest):
        n = TB * SW
        szr_refs, szi_refs = rest[:n], rest[n:2 * n]
        sqr_refs, sqi_refs = rest[2 * n:3 * n], rest[3 * n:4 * n]
        srk_refs = rest[4 * n:5 * n]
        outr, outi = rest[5 * n], rest[5 * n + 1]
        s = pl.program_id(2)

        @pl.when(s == 0)
        def _init():
            outr[...] = jnp.zeros_like(outr)
            outi[...] = jnp.zeros_like(outi)

        tzr = tzr_ref[...]                     # (TB, n_pad) resident targets
        tzi = tzi_ref[...]
        trk = trk_ref[...]                     # (TB, n_pad) global ranks
        for w in range(SW):
            o = w * TB

            def tile(refs):
                return jnp.concatenate([r[...] for r in refs[o:o + TB]],
                                       axis=0)

            dr, di = pairwise_tile(kernel, tzr, tzi, trk,
                                   tile(szr_refs), tile(szi_refs),
                                   tile(sqr_refs), tile(sqi_refs),
                                   tile(srk_refs))
            outr[...] += dr
            outi[...] += di

    return body


@functools.partial(jax.jit, static_argnames=("kernel", "tile_boxes",
                                             "stage_width", "interpret"))
def _p2p_pallas(lists: jax.Array, tzr, tzi, trk, szr, szi, sqr, sqi, srk, *,
                kernel: str, tile_boxes: int, stage_width: int,
                interpret: bool):
    """Batch-major core: lists (B, nbox, S), planes (B, nbox[+1], n_pad)."""
    B, nbox, _ = lists.shape
    n_pad = tzr.shape[-1]
    TB, SW = tile_boxes, stage_width
    dummy = szr.shape[-2] - 1  # index of the all-zero row

    lists, nchunk, (steps,) = staged_lists([lists], dummy, TB, SW)
    rows = lists.shape[1]
    n = TB * SW
    szr, szi, sqr, sqi, srk = map(row_view, (szr, szi, sqr, sqi, srk))
    shared = [*([szr] * n), *([szi] * n), *([sqr] * n), *([sqi] * n),
              *([srk] * n)]

    def tgt_map(b, i, s, lref):
        return (b, i, ZERO)

    in_specs = ([pl.BlockSpec((None, TB, n_pad), tgt_map)] * 3
                + prefetch_row_specs(TB, SW, n_pad) * 5)
    dt = tzr.dtype

    def launch(lists, tzr, tzi, trk):
        crows = lists.shape[1]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, crows // TB, steps),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((None, TB, n_pad), tgt_map),
                pl.BlockSpec((None, TB, n_pad), tgt_map),
            ],
        )
        return pl.pallas_call(
            _make_kernel(kernel, TB, SW),
            name="p2p",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, crows, n_pad), dt)] * 2,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(lists, tzr, tzi, trk, *shared)

    outr, outi = run_chunked(launch, nchunk, [
        lists, pad_boxes(tzr, rows), pad_boxes(tzi, rows),
        pad_boxes(trk, rows, -1)])
    return outr[:, :nbox], outi[:, :nbox]


@functools.lru_cache(maxsize=None)
def _p2p_op(kernel: str, tile_boxes: int, stage_width: int, interpret: bool):
    """Per-problem P2P op whose custom batching rule lowers ``jax.vmap``
    onto the batch-major kernel grid (one launch for B problems)."""
    return make_batched_op(functools.partial(
        _p2p_pallas, kernel=kernel, tile_boxes=tile_boxes,
        stage_width=stage_width, interpret=interpret))


def p2p_pallas(lists: jax.Array, tzr, tzi, trk, szr, szi, sqr, sqi, srk, *,
               kernel: str = "harmonic", tile_boxes: int = 8,
               stage_width: int = 1, interpret: bool | None = None):
    """lists: (nbox, S) int32 (-1 masked). Dense planes: (nbox[+1], n_pad);
    trk/srk: int32 global-rank planes (-1 in padded slots / dummy row) —
    self-interaction is excluded where source rank == target rank.

    Returns (outr, outi): (nbox, n_pad) potential at the dense leaf slots.
    ``interpret=None`` auto-selects from the JAX platform (compiled on
    TPU). Batch-native: under ``jax.vmap``, B problems compile to ONE
    batch-major launch (see ``p2p_pallas_batched``).
    """
    op = _p2p_op(kernel, tile_boxes, stage_width,
                 resolve_interpret(interpret))
    return op(lists, tzr, tzi, trk, szr, szi, sqr, sqi, srk)


def p2p_pallas_batched(lists: jax.Array, tzr, tzi, trk, szr, szi, sqr, sqi,
                       srk, *, kernel: str = "harmonic", tile_boxes: int = 8,
                       stage_width: int = 1, interpret: bool | None = None):
    """Batch-major entry: all operands carry a leading problem axis B;
    one (B, ntile, steps) launch returns (B, nbox, n_pad) planes."""
    return _p2p_pallas(lists, tzr, tzi, trk, szr, szi, sqr, sqi, srk,
                       kernel=kernel, tile_boxes=tile_boxes,
                       stage_width=stage_width,
                       interpret=resolve_interpret(interpret))
