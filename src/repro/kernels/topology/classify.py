"""Pallas TPU kernel: leaf-level strong/weak/swapped-theta classification.

The leaf level holds 3/4 of all boxes, so its classification dominates
the connect phase. One grid step classifies a ``tile_boxes`` tile of
target boxes against their full (4S-wide) candidate row and emits the
five *keyed* arrays (strong, weak, p2p, p2l, m2p: kept entries carry the
candidate id, dropped entries INT32_MAX) that ``build_connectivity``
feeds to its single batched compaction sort.

Candidate geometry (center and radius of every candidate box) is
gathered by XLA before the launch, with the reference path's own
``_gather_geometry``, and streamed in as (TB, Cp) planes beside the
candidate ids: Mosaic lowers no general in-kernel gather, so the kernel
body stays purely elementwise. The predicates are the exact plane-form
formulas of ``core.topology.connectivity._theta_masks`` /
``_swapped_masks`` — the two paths must agree bit-for-bit, which the
parity sweep in tests/test_topology.py checks on every distribution.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.topology.connectivity import _gather_geometry
from ..common import ZERO, pad_rows, resolve_interpret, round_up

_INT_MAX = np.int32(np.iinfo(np.int32).max)


def _make_kernel(theta: float, use_p2l_m2p: bool):
    def body(cand_ref, ccx_ref, ccy_ref, rc_ref, tbx_ref, tby_ref, tbr_ref,
             ks_ref, kw_ref, kp_ref, kl_ref, km_ref):
        cand = cand_ref[...]                      # (TB, Cp) int32, -1 invalid
        valid = cand >= 0
        ccx = ccx_ref[...]                        # (TB, Cp) candidate geometry
        ccy = ccy_ref[...]
        rc = rc_ref[...]
        tbx = tbx_ref[...]                        # (TB, 1) target geometry
        tby = tby_ref[...]
        rb = tbr_ref[...]
        d = jnp.hypot(tbx - ccx, tby - ccy)
        big = jnp.maximum(rb, rc)
        small = jnp.minimum(rb, rc)
        wellsep = (big + theta * small) <= (theta * d)
        weak_m = valid & wellsep
        strong_m = valid & ~wellsep
        if use_p2l_m2p:
            swapped = (small + theta * big) <= (theta * d)
            p2l_m = strong_m & swapped & (rc > rb)
            m2p_m = strong_m & swapped & (rc < rb)
            p2p_m = strong_m & ~(p2l_m | m2p_m)
        else:
            p2p_m = strong_m
            p2l_m = m2p_m = jnp.zeros_like(strong_m)

        def key(mask):
            return jnp.where(mask, cand, _INT_MAX)

        ks_ref[...] = key(strong_m)
        kw_ref[...] = key(weak_m)
        kp_ref[...] = key(p2p_m)
        kl_ref[...] = key(p2l_m)
        km_ref[...] = key(m2p_m)

    return body


@functools.partial(jax.jit, static_argnames=("theta", "use_p2l_m2p",
                                             "tile_boxes", "interpret"))
def _classify_pallas(cand, ccx, ccy, rc, tbx, tby, tbr, *, theta: float,
                     use_p2l_m2p: bool, tile_boxes: int, interpret: bool):
    nb, C = cand.shape
    TB = tile_boxes
    ntile = -(-nb // TB)
    Cp = round_up(C, 128)

    def row(a, fill=0):
        a = jnp.pad(a, ((0, 0), (0, Cp - C)), constant_values=fill)
        return pad_rows(a, ntile * TB, fill)

    def col(a):
        return pad_rows(a.reshape(-1, 1), ntile * TB)

    def tgt_map(i):
        return (i, ZERO)

    outs = pl.pallas_call(
        _make_kernel(theta, use_p2l_m2p),
        name="leaf_classify",
        grid=(ntile,),
        in_specs=[pl.BlockSpec((TB, Cp), tgt_map)] * 4
        + [pl.BlockSpec((TB, 1), tgt_map)] * 3,
        out_specs=[pl.BlockSpec((TB, Cp), tgt_map)] * 5,
        out_shape=[jax.ShapeDtypeStruct((ntile * TB, Cp), jnp.int32)] * 5,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(row(cand, -1), row(ccx), row(ccy), row(rc), col(tbx), col(tby),
      col(tbr))
    return tuple(o[:nb, :C] for o in outs)


def leaf_classify_pallas(cand, valid, centers, radii, cfg,
                         interpret: bool | None = None):
    """Pallas twin of ``leaf_classify_reference`` (the
    ``leaf_classify_impl`` topology hook).

    ``cand``/``valid``: (4**L, 4S) candidates; ``centers``/``radii``: the
    leaf-level box geometry. Returns the five keyed (4**L, 4S) int32
    arrays. The kernel tile is ``tile_boxes`` rounded up to the 8-row
    sublane tile, so every block is aligned for Mosaic.
    ``interpret=None`` auto-selects from the JAX platform.
    """
    rdt = cfg.real_dtype
    ccx, ccy, rc = _gather_geometry(cand, valid, centers, radii)
    cand = jnp.where(valid, cand, -1).astype(jnp.int32)
    with jax.named_scope("leaf_classify"):
        return _classify_pallas(
            cand, ccx.astype(rdt), ccy.astype(rdt), rc.astype(rdt),
            jnp.real(centers).astype(rdt), jnp.imag(centers).astype(rdt),
            radii.astype(rdt), theta=cfg.theta,
            use_p2l_m2p=cfg.use_p2l_m2p,
            tile_boxes=round_up(cfg.tile_boxes, 8),
            interpret=resolve_interpret(interpret))
