"""Pallas TPU kernel: M2L translation sweep (the paper's Algorithm 3.6).

The CUDA implementation runs the scaled-Horner shift with two threads per
shift in shared memory, one block owning all shifts of a target box (no f64
atomics on Fermi). On TPU we use the factorized form (DESIGN.md §2):

    local += diag((-1/r)^l) · H · diag(r^-k) · mult[src],
    H[l,k] = C(l+k-1, k-1)   (constant Hankel-binomial matrix)

so the inner operation per weak-list slot is a (TB,P)x(P,P) GEMM on the
MXU — a grid step owns a *tile* of ``tile_boxes`` target boxes, so the
contraction runs on full multi-sublane register tiles instead of rank-1
rows — plus two O(p) diagonal scalings computed as in-register column
recurrences (the paper's pre/post-scaling phases, verbatim). Source
coefficient rows are DMA'd HBM->VMEM through scalar-prefetch indexed
BlockSpecs driven by the weak interaction list (``stage_width`` slots per
step, double-buffered by Pallas); accumulation happens in the revisited
(TB, P) output block across the list axis — deterministic, in contrast to
the atomics the paper had to design around.

The box axis is *level-agnostic*: callers may flatten all levels of the
downward pass into one (sum 4^l, W) call with statically offset lists
(see ops.m2l_fused_apply), collapsing L launches into one. The grid is
additionally *batch-major* — (B, ntile, steps) with ``program_id(0)``
selecting the problem — so ``jax.vmap`` of ``m2l_pallas`` folds B
problems into the same single launch (custom batching rule; the Hankel
matrix stays one shared (P, P) constant across the batch).

Both G-kernels: "harmonic" (a_0 = 0, as in all of the paper's
experiments) and "log" (a_0 carries the source strength; the extra
a_0·log r term rides in as precomputed log-plane columns).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import (ZERO, broadcast_unbatched, prefetch_row_specs,
                      resolve_interpret, row_view, run_chunked, slot_spec,
                      slot_view, staged_lists)


def _make_kernel(p: int, P: int, kernel: str, TB: int, SW: int):
    n = TB * SW

    def body(weak_ref, *rest):
        ar_refs, ai_refs = rest[:n], rest[n:2 * n]
        prer_ref, prei_ref, postr_ref, posti_ref = rest[2 * n:2 * n + 4]
        if kernel == "log":
            logr_ref, logi_ref, ht_ref = rest[2 * n + 4:2 * n + 7]
            outr, outi = rest[2 * n + 7], rest[2 * n + 8]
        else:
            ht_ref = rest[2 * n + 4]
            outr, outi = rest[2 * n + 5], rest[2 * n + 6]
        s = pl.program_id(2)

        @pl.when(s == 0)
        def _init():
            outr[...] = jnp.zeros_like(outr)
            outi[...] = jnp.zeros_like(outi)

        def col_pows(br, bi):
            # [(br+i bi)^k for k=0..p] as (TB, P) planes, zero-padded
            rs, is_ = [jnp.ones_like(br)], [jnp.zeros_like(bi)]
            for _ in range(p):
                nr = rs[-1] * br - is_[-1] * bi
                ni = rs[-1] * bi + is_[-1] * br
                rs.append(nr)
                is_.append(ni)
            zpad = [jnp.zeros_like(br)] * (P - p - 1)
            return (jnp.concatenate(rs + zpad, axis=1),
                    jnp.concatenate(is_ + zpad, axis=1))

        ht = ht_ref[...]
        for w in range(SW):
            o = w * TB
            ar = jnp.concatenate([r[...] for r in ar_refs[o:o + TB]], axis=0)
            ai = jnp.concatenate([r[...] for r in ai_refs[o:o + TB]], axis=0)
            # bounded ratio scale factors (radius-normalized coefficients):
            pr, pi = col_pows(prer_ref[:, w:w + 1], prei_ref[:, w:w + 1])
            mr, mi = col_pows(postr_ref[:, w:w + 1], posti_ref[:, w:w + 1])
            ahr = ar * pr - ai * pi
            ahi = ar * pi + ai * pr
            dt = ar.dtype
            # f32 contraction: Mosaic's default precision rounds the
            # operands to bf16, ~1e-2 pointwise FMM error at p = 17
            bhr = jnp.dot(ahr, ht, preferred_element_type=dt,
                          precision=jax.lax.Precision.HIGHEST)
            bhi = jnp.dot(ahi, ht, preferred_element_type=dt,
                          precision=jax.lax.Precision.HIGHEST)
            outr[...] += bhr * mr - bhi * mi
            outi[...] += bhr * mi + bhi * mr
            if kernel == "log":
                # b_0 += a_0 * log(r) (source strength rides in a_0)
                a0r, a0i = ar[:, 0:1], ai[:, 0:1]
                lr = logr_ref[:, w:w + 1]
                li = logi_ref[:, w:w + 1]
                col0 = jax.lax.broadcasted_iota(jnp.int32, (TB, P), 1) == 0
                outr[...] += jnp.where(col0, a0r * lr - a0i * li, 0.0)
                outi[...] += jnp.where(col0, a0r * li + a0i * lr, 0.0)

    return body


@functools.partial(jax.jit, static_argnames=("p", "kernel", "tile_boxes",
                                             "stage_width", "interpret"))
def _m2l_pallas(weak: jax.Array, ar, ai, prer, prei, postr, posti, logr,
                logi, ht, *, p: int, kernel: str, tile_boxes: int,
                stage_width: int, interpret: bool):
    """Batch-major core: weak (B, nbox, W), coefficient planes
    (B, nbox+1, P), ratio planes (B, nbox, W); ht one shared (P, P)."""
    B, nbox, W = weak.shape
    P = ar.shape[-1]
    TB, SW = tile_boxes, stage_width
    dummy = ar.shape[-2] - 1

    weak, nchunk, (steps,) = staged_lists([weak], dummy, TB, SW)
    rows, W_pad = weak.shape[1:]

    def plane(a):
        return jnp.pad(a, ((0, 0), (0, rows - nbox), (0, W_pad - W)))

    planes = [plane(a) for a in (prer, prei, postr, posti)]
    if kernel == "log":
        planes += [plane(logr), plane(logi)]
    n = TB * SW
    rows_ar = [row_view(ar)] * n + [row_view(ai)] * n

    def tgt_map(b, i, s, wref):
        return (b, i, ZERO)

    def const_map(b, i, s, wref):
        return (ZERO, ZERO)

    in_specs = (prefetch_row_specs(TB, SW, P) * 2
                + [slot_spec(TB, SW)] * len(planes)
                + [pl.BlockSpec((P, P), const_map)])
    dt = ar.dtype

    def launch(weak, *planes):
        crows = weak.shape[1]
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
            name="m2l_fused",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, crows, P), dt)] * 2,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(weak, *rows_ar, *(slot_view(a, SW) for a in planes), ht)

    outr, outi = run_chunked(launch, nchunk, [weak, *planes])
    return outr[:, :nbox], outi[:, :nbox]


@functools.lru_cache(maxsize=None)
def _m2l_op(p: int, kernel: str, tile_boxes: int, stage_width: int,
            interpret: bool):
    """Per-problem M2L op; its custom batching rule lowers ``jax.vmap``
    onto the batch-major grid. The log variant carries two extra log(r)
    plane operands; the Hankel matrix ``ht`` is a shared constant and is
    never broadcast along the batch."""
    kw = dict(p=p, kernel=kernel, tile_boxes=tile_boxes,
              stage_width=stage_width, interpret=interpret)
    with_log = kernel == "log"

    def call(weak, ar, ai, prer, prei, postr, posti, logr, logi, ht):
        return _m2l_pallas(weak, ar, ai, prer, prei, postr, posti, logr,
                           logi, ht, **kw)

    def split(args):
        # ht is always last; the log planes precede it on the log kernel
        if with_log:
            return args[:-3], args[-3:-1], args[-1]
        return args[:-1], (None, None), args[-1]

    def placeholder(ar):
        return jnp.zeros((), ar.dtype)

    @jax.custom_batching.custom_vmap
    def op(*args):
        batched, (logr, logi), ht = split(args)
        batched = [a[None] for a in batched]
        logs = ([logr[None], logi[None]] if with_log
                else [placeholder(args[1])] * 2)
        outr, outi = call(*batched, *logs, ht)
        return outr[0], outi[0]

    @op.def_vmap
    def _rule(axis_size, in_batched, *args):
        batched, logs, ht = split(args)
        bflags, lflags, htflag = split(in_batched)
        batched = broadcast_unbatched(batched, bflags, axis_size)
        if with_log:
            logs = broadcast_unbatched(logs, lflags, axis_size)
        else:
            logs = [placeholder(args[1])] * 2
        if htflag:
            # ht is the constant binomial matrix, shared across the
            # batch by construction — a per-problem ht cannot be
            # honored on the shared (P, P) kernel operand, so refuse
            # loudly rather than silently use one problem's matrix.
            raise ValueError(
                "m2l_pallas: the Hankel matrix ht must not carry the "
                "vmapped axis (it is one shared (P, P) constant); pass "
                "it unbatched")
        return call(*batched, *logs, ht), (True, True)

    return op


def m2l_pallas(weak: jax.Array, ar, ai, prer, prei, postr, posti, ht, *,
               p: int, kernel: str = "harmonic", logr=None, logi=None,
               tile_boxes: int = 8, stage_width: int = 1,
               interpret: bool | None = None):
    """weak: (nbox, W) int32 (-1 masked -> redirected to zero dummy row).

    ar/ai: (nbox+1, P) normalized multipole planes; prer/prei and
    postr/posti: (nbox, W) complex ratio planes (rho_s/r and -rho_t/r);
    ht: (P, P) transposed Hankel matrix; logr/logi: (nbox, W) log(r)
    planes (log kernel only). Returns (outr, outi) of shape (nbox, P) —
    the summed normalized local contributions per target box.
    ``interpret=None`` auto-selects from the JAX platform. Batch-native:
    under ``jax.vmap``, B problems compile to ONE batch-major launch.
    """
    if kernel == "log" and (logr is None or logi is None):
        raise ValueError("log kernel needs logr/logi planes")
    op = _m2l_op(p, kernel, tile_boxes, stage_width,
                 resolve_interpret(interpret))
    args = (weak, ar, ai, prer, prei, postr, posti)
    if kernel == "log":
        args += (logr, logi)
    return op(*args, ht)


def m2l_pallas_batched(weak: jax.Array, ar, ai, prer, prei, postr, posti,
                       ht, *, p: int, kernel: str = "harmonic", logr=None,
                       logi=None, tile_boxes: int = 8, stage_width: int = 1,
                       interpret: bool | None = None):
    """Batch-major entry: operands carry a leading problem axis B (``ht``
    stays one shared (P, P) constant); one (B, ntile, steps) launch."""
    if kernel == "log" and (logr is None or logi is None):
        raise ValueError("log kernel needs logr/logi planes")
    if logr is None:
        logr = logi = jnp.zeros((), ar.dtype)  # unused placeholder
    return _m2l_pallas(weak, ar, ai, prer, prei, postr, posti, logr, logi,
                       ht, p=p, kernel=kernel, tile_boxes=tile_boxes,
                       stage_width=stage_width,
                       interpret=resolve_interpret(interpret))
