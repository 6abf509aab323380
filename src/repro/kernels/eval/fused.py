"""Pallas TPU megakernel: the whole FMM evaluation phase in ONE launch.

The paper's evaluation phase (L2P + M2P + P2P; §3.3, ~56% of GPU runtime
in Table 5.1) previously ran as three device sweeps with ``phi`` making
three HBM round-trips: an L2P write, an M2P read-modify-write scan and a
P2P scatter-add. Cruz, Layton & Barba (arXiv:1009.3457) show the win for
FMM GPU kernels is keeping the *target tile resident* while every
interaction type accumulates into it; this kernel is that idea on TPU.

One grid step owns a tile of ``tile_boxes`` leaf boxes of one problem:
the grid is batch-major — (B, ntile, steps), ``program_id(0)`` selects
the problem — and the (TB, n_pad) ``phi`` output block stays resident in
VMEM across the entire fused interaction list and is written to HBM
exactly once:

  s == 0                 seed with the L2P Horner over the (TB, P) local
                         coefficient block (pre-centered particle planes);
  s <  p2p_steps         P2P: pairwise (TB, n_t, n_s) tile against staged
                         particle rows of the s-th strong-list slot;
  s >= p2p_steps         M2P: multipole Horner in w = rho_s/(z - z0_s)
                         against staged (1, P) multipole rows of the
                         (s - p2p_steps)-th m2p-list slot.

Both lists ride in ONE scalar-prefetch operand (``staged_lists``):
the p2p region's columns select particle rows, the m2p region's columns
select multipole rows. Every staged spec family DMAs on every step — in
the foreign region it fetches a (harmless, valid) row that the
``pl.when`` branch never reads — which keeps the grid rectangular and
lets Pallas double-buffer all streams uniformly. B problems only
lengthen the batch-major grid axis — the per-step VMEM working set is
batch-invariant (``autotune.eval_fused_vmem_bytes`` stays valid), and
``jax.vmap`` of ``eval_fused_pallas`` lowers onto this grid through the
op's custom batching rule, so batched serving runs at kernel speed.

Self-interaction in the P2P branch is excluded by global particle rank
(trk/srk planes), not position, so duplicated positions keep their
(singular) mutual term. Both G-kernels: "harmonic" q/(z-x), "log"
q*log(z-x).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.mathf import atan2, log
from ..common import (ZERO, broadcast_unbatched, l2p_horner, launch_name,
                      pad_boxes, pairwise_tile, prefetch_row_specs,
                      resolve_interpret, row_view, run_chunked, slot_spec,
                      slot_view, staged_lists)


def _make_kernel(p: int, P: int, kernel: str, TB: int, SW: int,
                 p2p_steps: int, m2p_steps: int):
    n = TB * SW

    def body(lists_ref, tzr_ref, tzi_ref, trk_ref, tr_ref, ti_ref,
             br_ref, bi_ref, *rest):
        szr_refs, szi_refs = rest[:n], rest[n:2 * n]
        sqr_refs, sqi_refs = rest[2 * n:3 * n], rest[3 * n:4 * n]
        srk_refs = rest[4 * n:5 * n]
        if m2p_steps:
            ar_refs, ai_refs = rest[5 * n:6 * n], rest[6 * n:7 * n]
            mcr_ref, mci_ref, mrho_ref = rest[7 * n:7 * n + 3]
            outr, outi = rest[7 * n + 3], rest[7 * n + 4]
        else:
            outr, outi = rest[5 * n], rest[5 * n + 1]
        s = pl.program_id(2)

        def tile(refs, o):
            return jnp.concatenate([r[...] for r in refs[o:o + TB]], axis=0)

        @pl.when(s == 0)
        def _l2p():
            # seed phi with the local-expansion Horner: the L2P write
            # never leaves VMEM.
            outr[...], outi[...] = l2p_horner(p, br_ref, bi_ref,
                                              tr_ref[...], ti_ref[...])

        tzr = tzr_ref[...]                           # (TB, n_pad) targets
        tzi = tzi_ref[...]

        @pl.when(s < p2p_steps)
        def _p2p():
            trk = trk_ref[...]
            for w in range(SW):
                o = w * TB
                dr, di = pairwise_tile(kernel, tzr, tzi, trk,
                                       tile(szr_refs, o), tile(szi_refs, o),
                                       tile(sqr_refs, o), tile(sqi_refs, o),
                                       tile(srk_refs, o))
                outr[...] += dr
                outi[...] += di

        if m2p_steps:
            @pl.when(s >= p2p_steps)
            def _m2p():
                for w in range(SW):
                    o = w * TB
                    ar, ai = tile(ar_refs, o), tile(ai_refs, o)  # (TB, P)
                    cr = mcr_ref[:, w:w + 1]          # (TB, 1) slot planes
                    ci = mci_ref[:, w:w + 1]
                    rho = mrho_ref[:, w:w + 1]
                    dxr = tzr - cr                    # z - z0_src
                    dxi = tzi - ci
                    d2 = dxr * dxr + dxi * dxi
                    # gate on SLOT validity (masked slots carry rho = 0;
                    # effective radii are floored > 0), never on position:
                    # a target coinciding with the source center goes
                    # singular exactly like the reference sweep instead
                    # of silently dropping the contribution.
                    ok = rho > 0.0
                    k = jnp.where(ok, 1.0 / d2, 0.0)
                    wr = rho * dxr * k                # w = rho / (z - z0)
                    wi = -rho * dxi * k
                    accr = jnp.zeros_like(tzr) + ar[:, p:p + 1]
                    acci = jnp.zeros_like(tzi) + ai[:, p:p + 1]
                    for j in range(p - 1, 0, -1):
                        nr = accr * wr - acci * wi + ar[:, j:j + 1]
                        ni = accr * wi + acci * wr + ai[:, j:j + 1]
                        accr, acci = nr, ni
                    fr = accr * wr - acci * wi        # trailing * w (a_0 off)
                    fi = accr * wi + acci * wr
                    if kernel == "log":
                        # + a_0 * log(z - z0_src)
                        lr = jnp.where(ok, 0.5 * log(d2), 0.0)
                        li = jnp.where(ok, atan2(dxi, dxr), 0.0)
                        a0r, a0i = ar[:, 0:1], ai[:, 0:1]
                        fr = fr + a0r * lr - a0i * li
                        fi = fi + a0r * li + a0i * lr
                    outr[...] += jnp.where(ok, fr, 0.0)
                    outi[...] += jnp.where(ok, fi, 0.0)

    return body


@functools.partial(jax.jit, static_argnames=("p", "kernel", "tile_boxes",
                                             "stage_width", "interpret"))
def _eval_fused_pallas(p2p_lists, m2p_lists, tzr, tzi, trk, tr, ti, br, bi,
                       szr, szi, sqr, sqi, srk, ar, ai, mcr, mci, mrho, *,
                       p: int, kernel: str, tile_boxes: int,
                       stage_width: int, interpret: bool):
    """Batch-major core: lists (B, nbox, S), planes (B, nbox[+1], ...).
    ``m2p_lists=None`` (with None multipole/slot planes) drops the M2P
    region entirely."""
    B, nbox, _ = p2p_lists.shape
    n_pad = tzr.shape[-1]
    TB, SW = tile_boxes, stage_width
    dummy = szr.shape[-2] - 1                # all-zero row in every plane
    with_m2p = m2p_lists is not None
    P = br.shape[-1]

    regions = [p2p_lists] + ([m2p_lists] if with_m2p else [])
    lists, nchunk, steps = staged_lists(regions, dummy, TB, SW)
    p2p_steps = steps[0]
    m2p_steps = steps[1] if with_m2p else 0
    rows = lists.shape[1]

    def tgt(a, fill=0):
        return pad_boxes(a, rows, fill)

    tiled = [lists, tgt(tzr), tgt(tzi), tgt(trk, -1), tgt(tr), tgt(ti),
             tgt(br), tgt(bi)]
    n = TB * SW
    szr, szi, sqr, sqi, srk = map(row_view, (szr, szi, sqr, sqi, srk))
    shared = [*([szr] * n), *([szi] * n), *([sqr] * n), *([sqi] * n),
              *([srk] * n)]
    if with_m2p:
        # slot planes span the whole fused list (zeros in the p2p region)
        total_cols = (p2p_steps + m2p_steps) * SW

        def slot_plane(a):
            a = jnp.pad(a, ((0, 0), (0, 0),
                            (p2p_steps * SW,
                             total_cols - p2p_steps * SW - a.shape[-1])))
            return tgt(a)

        tiled += [slot_plane(mcr), slot_plane(mci), slot_plane(mrho)]
        ar, ai = row_view(ar), row_view(ai)
        shared += [*([ar] * n), *([ai] * n)]

    def tgt_map(b, i, s, lref):
        return (b, i, ZERO)

    part_specs = prefetch_row_specs(TB, SW, n_pad)   # particle/rank rows
    in_specs = ([pl.BlockSpec((None, TB, n_pad), tgt_map)] * 5
                + [pl.BlockSpec((None, TB, P), tgt_map)] * 2
                + part_specs * 5)
    if with_m2p:
        in_specs += prefetch_row_specs(TB, SW, P) * 2    # multipole rows
        in_specs += [slot_spec(TB, SW)] * 3
    dt = tzr.dtype

    def launch(lists, *targets):
        crows = lists.shape[1]
        slots = [slot_view(a, SW) for a in targets[7:]]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, crows // TB, p2p_steps + m2p_steps),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((None, TB, n_pad), tgt_map),
                pl.BlockSpec((None, TB, n_pad), tgt_map),
            ],
        )
        return pl.pallas_call(
            _make_kernel(p, P, kernel, TB, SW, p2p_steps, m2p_steps),
            name=launch_name("eval_fused", kernel),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, crows, n_pad), dt)] * 2,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(lists, *targets[:7], *shared, *slots)

    outr, outi = run_chunked(launch, nchunk, tiled)
    return outr[:, :nbox], outi[:, :nbox]


@functools.lru_cache(maxsize=None)
def _eval_fused_op(p: int, kernel: str, tile_boxes: int, stage_width: int,
                   with_m2p: bool, interpret: bool):
    """Per-problem fused-evaluation op; its custom batching rule lowers
    ``jax.vmap`` onto the batch-major grid, so the evaluation phase of B
    problems is still exactly ONE launch. The ``with_m2p=False`` variant
    has no multipole/slot operands at all."""
    kw = dict(p=p, kernel=kernel, tile_boxes=tile_boxes,
              stage_width=stage_width, interpret=interpret)

    def call(args):
        if with_m2p:
            (p2p_lists, m2p_lists, tzr, tzi, trk, tr, ti, br, bi,
             szr, szi, sqr, sqi, srk, ar, ai, mcr, mci, mrho) = args
        else:
            (p2p_lists, tzr, tzi, trk, tr, ti, br, bi,
             szr, szi, sqr, sqi, srk) = args
            m2p_lists = ar = ai = mcr = mci = mrho = None
        return _eval_fused_pallas(p2p_lists, m2p_lists, tzr, tzi, trk, tr,
                                  ti, br, bi, szr, szi, sqr, sqi, srk,
                                  ar, ai, mcr, mci, mrho, **kw)

    @jax.custom_batching.custom_vmap
    def op(*args):
        outr, outi = call([a[None] for a in args])
        return outr[0], outi[0]

    @op.def_vmap
    def _rule(axis_size, in_batched, *args):
        return (call(broadcast_unbatched(args, in_batched, axis_size)),
                (True, True))

    return op


def eval_fused_pallas(p2p_lists, m2p_lists, tzr, tzi, trk, tr, ti, br, bi,
                      szr, szi, sqr, sqi, srk, ar=None, ai=None, mcr=None,
                      mci=None, mrho=None, *, p: int,
                      kernel: str = "harmonic", tile_boxes: int = 8,
                      stage_width: int = 1, interpret: bool | None = None):
    """One launch for the whole evaluation phase (L2P + M2P + P2P).

    p2p_lists/m2p_lists: (nbox, S) int32 leaf interaction lists (-1
    masked; ``m2p_lists=None`` drops the M2P region entirely — the
    ``use_p2l_m2p=False`` configuration). Dense planes: tzr/tzi absolute
    target positions, trk/srk int32 global ranks (-1 padded), tr/ti
    pre-centered normalized positions for the L2P Horner, br/bi (nbox, P)
    local-coefficient planes, szr/szi/sqr/sqi/srk (nbox+1, n_pad) source
    planes, ar/ai (nbox+1, P) leaf multipole planes, mcr/mci/mrho
    (nbox, S_m2p) per-slot source-center/radius planes (masked slots 0).

    Returns (outr, outi): (nbox, n_pad) — the full evaluation-phase
    potential at the dense leaf slots, written to HBM once. Batch-native:
    under ``jax.vmap``, B problems compile to ONE batch-major launch
    (see ``eval_fused_pallas_batched``).
    """
    with_m2p = m2p_lists is not None
    if with_m2p and (ar is None or mcr is None):
        raise ValueError("m2p region needs multipole and slot planes")
    op = _eval_fused_op(p, kernel, tile_boxes, stage_width, with_m2p,
                        resolve_interpret(interpret))
    args = (p2p_lists,)
    if with_m2p:
        args += (m2p_lists,)
    args += (tzr, tzi, trk, tr, ti, br, bi, szr, szi, sqr, sqi, srk)
    if with_m2p:
        args += (ar, ai, mcr, mci, mrho)
    return op(*args)


def eval_fused_pallas_batched(p2p_lists, m2p_lists, tzr, tzi, trk, tr, ti,
                              br, bi, szr, szi, sqr, sqi, srk, ar=None,
                              ai=None, mcr=None, mci=None, mrho=None, *,
                              p: int, kernel: str = "harmonic",
                              tile_boxes: int = 8, stage_width: int = 1,
                              interpret: bool | None = None):
    """Batch-major entry: all operands carry a leading problem axis B;
    one (B, ntile, steps) launch returns (B, nbox, n_pad) planes."""
    if m2p_lists is not None and (ar is None or mcr is None):
        raise ValueError("m2p region needs multipole and slot planes")
    return _eval_fused_pallas(
        p2p_lists, m2p_lists, tzr, tzi, trk, tr, ti, br, bi,
        szr, szi, sqr, sqi, srk, ar, ai, mcr, mci, mrho,
        p=p, kernel=kernel, tile_boxes=tile_boxes, stage_width=stage_width,
        interpret=resolve_interpret(interpret))
