"""Pallas TPU kernel: local evaluation (L2P) at leaf particles.

One grid step per *tile* of ``tile_boxes`` leaf boxes: the (TB, P)
local-coefficient block and the (TB, n_pad) pre-centered particle tile
live in VMEM; the p-term Horner recurrence runs on full multi-sublane
vector registers with the coefficients read as per-row columns (static
lane indices). The paper uses one thread per evaluation point with 64
threads/block; the TPU analogue is the 8x128 vector lane grid processing
``tile_boxes`` whole boxes at once (DESIGN.md §2). The grid is
batch-major — (B, ntile) with ``program_id(0)`` selecting the problem —
so ``jax.vmap`` of ``l2p_pallas`` folds B problems into one launch.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import (ZERO, l2p_horner, make_batched_op,
                      pad_boxes, resolve_interpret)


def _make_kernel(p: int):
    def kernel(br_ref, bi_ref, tr_ref, ti_ref, outr, outi):
        outr[...], outi[...] = l2p_horner(p, br_ref, bi_ref,
                                          tr_ref[...], ti_ref[...])

    return kernel


@functools.partial(jax.jit, static_argnames=("p", "tile_boxes", "interpret"))
def _l2p_pallas(br, bi, tr, ti, *, p: int, tile_boxes: int, interpret: bool):
    """Batch-major core: br/bi (B, nbox, P), tr/ti (B, nbox, n_pad)."""
    B, nbox, P = br.shape
    n_pad = tr.shape[-1]
    TB = tile_boxes
    ntile = -(-nbox // TB)
    br, bi = pad_boxes(br, ntile * TB), pad_boxes(bi, ntile * TB)
    tr, ti = pad_boxes(tr, ntile * TB), pad_boxes(ti, ntile * TB)

    def row(b, i):
        return (b, i, ZERO)

    dt = tr.dtype
    outr, outi = pl.pallas_call(
        _make_kernel(p),
        name="l2p",
        grid=(B, ntile),
        in_specs=[
            pl.BlockSpec((None, TB, P), row),
            pl.BlockSpec((None, TB, P), row),
            pl.BlockSpec((None, TB, n_pad), row),
            pl.BlockSpec((None, TB, n_pad), row),
        ],
        out_specs=[
            pl.BlockSpec((None, TB, n_pad), row),
            pl.BlockSpec((None, TB, n_pad), row),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, ntile * TB, n_pad), dt)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(br, bi, tr, ti)
    return outr[:, :nbox], outi[:, :nbox]


@functools.lru_cache(maxsize=None)
def _l2p_op(p: int, tile_boxes: int, interpret: bool):
    """Per-problem L2P op; its custom batching rule lowers ``jax.vmap``
    onto the batch-major grid."""
    return make_batched_op(functools.partial(
        _l2p_pallas, p=p, tile_boxes=tile_boxes, interpret=interpret))


def l2p_pallas(br, bi, tr, ti, *, p: int, tile_boxes: int = 8,
               interpret: bool | None = None):
    """br/bi: (nbox, P) local planes; tr/ti: (nbox, n_pad) pre-centered
    particle planes (z - z0). Returns (outr, outi): (nbox, n_pad).
    ``interpret=None`` auto-selects from the JAX platform. Batch-native:
    under ``jax.vmap``, B problems compile to ONE batch-major launch."""
    return _l2p_op(p, tile_boxes, resolve_interpret(interpret))(br, bi,
                                                                tr, ti)


def l2p_pallas_batched(br, bi, tr, ti, *, p: int, tile_boxes: int = 8,
                       interpret: bool | None = None):
    """Batch-major entry: operands carry a leading problem axis B; one
    (B, ntile) launch returns (B, nbox, n_pad) planes."""
    return _l2p_pallas(br, bi, tr, ti, p=p, tile_boxes=tile_boxes,
                       interpret=resolve_interpret(interpret))
