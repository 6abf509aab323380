"""Jit'd wrappers wiring the fused evaluation + P2L kernels into the FMM.

``eval_fused_apply`` is the ``eval_fused_impl`` hook: it stages the dense
leaf planes once, issues exactly ONE ``pallas_call`` for the whole
evaluation phase (L2P + M2P + P2P with the phi tile VMEM-resident) and
scatters the result back to rank order — replacing the three separate
sweeps (and their three phi HBM round-trips) of the unfused path.

``p2l_apply`` is the ``p2l_impl`` hook for the downward pass: one
``pallas_call`` over (tile_boxes, P) local-coefficient blocks replacing
the ``p2l_sweep`` jnp scan.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...core.config import FmmConfig
from ..common import (dense_leaf_arrays, dense_rank_planes, launch_name,
                      round_up, scatter_from_leaves, staged_grid_steps)
from .fused import eval_fused_pallas
from .p2l import p2l_pallas


def _coeff_planes(coeffs, P: int, rdt, extra_row: bool):
    """(nbox, p+1) complex -> real/imag (nbox[+1], P) planes, zero-padded."""
    pad = P - coeffs.shape[1]
    rows = (0, 1) if extra_row else (0, 0)
    br = jnp.pad(jnp.real(coeffs), (rows, (0, pad))).astype(rdt)
    bi = jnp.pad(jnp.imag(coeffs), (rows, (0, pad))).astype(rdt)
    return br, bi


def eval_fused_apply(local, mult_leaf, tree, conn, cfg: FmmConfig,
                     idx: np.ndarray, interpret: bool | None = None):
    """Drop-in ``eval_fused_impl`` for ``repro.core.fmm.fmm_evaluate``.

    local: (nbox, p+1) leaf local expansions; mult_leaf: (nbox, p+1) leaf
    multipoles (M2P sources). Returns the (n,) complex evaluation-phase
    potential (L2P + M2P + P2P) in rank order.
    """
    from ...core.fmm import effective_radii

    idx = np.asarray(idx)
    n_pad = round_up(idx.shape[1], 128)
    rdt = cfg.real_dtype
    zr, zi, qr, qi, _ = dense_leaf_arrays(tree.z, tree.q, idx, n_pad)
    rk = dense_rank_planes(idx, n_pad)

    c = tree.centers[cfg.nlevels]
    rho = effective_radii(tree, cfg)[cfg.nlevels]
    tr = ((zr[:-1] - jnp.real(c)[:, None]) / rho[:, None]).astype(rdt)
    ti = ((zi[:-1] - jnp.imag(c)[:, None]) / rho[:, None]).astype(rdt)

    P = round_up(cfg.p + 1, 128)
    br, bi = _coeff_planes(local, P, rdt, extra_row=False)

    kwargs = {}
    m2p_lists = None
    if cfg.use_p2l_m2p:
        m2p_lists = conn.m2p
        ar, ai = _coeff_planes(mult_leaf, P, rdt, extra_row=True)
        mask = m2p_lists >= 0
        src = jnp.where(mask, m2p_lists, 0)
        mcr = jnp.where(mask, jnp.real(c)[src], 0.0).astype(rdt)
        mci = jnp.where(mask, jnp.imag(c)[src], 0.0).astype(rdt)
        mrho = jnp.where(mask, rho[src], 0.0).astype(rdt)
        kwargs = {"ar": ar, "ai": ai, "mcr": mcr, "mci": mci, "mrho": mrho}

    with jax.named_scope(launch_name("eval_fused", cfg.kernel)):
        outr, outi = eval_fused_pallas(
            conn.p2p, m2p_lists, zr[:-1], zi[:-1], rk[:-1], tr, ti, br, bi,
            zr, zi, qr, qi, rk, p=cfg.p, kernel=cfg.kernel,
            tile_boxes=cfg.tile_boxes, stage_width=cfg.stage_width,
            interpret=interpret, **kwargs)
    return scatter_from_leaves(outr + 1j * outi, idx, cfg.n)


def eval_grid_steps(conn, cfg: FmmConfig):
    """``(steps, empty)`` of each region of the grid ``eval_fused_apply``
    launches: the P2P region, then the M2P region where the
    configuration has one (``common.staged_grid_steps``)."""
    regions = [conn.p2p] + ([conn.m2p] if cfg.use_p2l_m2p else [])
    return staged_grid_steps([r[None] for r in regions], conn.p2p.shape[0],
                             cfg.tile_boxes, cfg.stage_width)


def p2l_grid_steps(conn, cfg: FmmConfig):
    """``(steps, empty)`` of the grid ``p2l_apply`` launches."""
    return staged_grid_steps([conn.p2l[None]], conn.p2l.shape[0],
                             cfg.tile_boxes, cfg.stage_width)[0]


def p2l_apply(tree, conn, cfg: FmmConfig, idx: np.ndarray, rho,
              interpret: bool | None = None):
    """Drop-in ``p2l_impl`` for the downward pass: returns the (nbox, p+1)
    complex radius-normalized P2L local-coefficient contribution (added
    to ``local`` by the caller)."""
    idx = np.asarray(idx)
    n_pad = round_up(idx.shape[1], 128)
    rdt = cfg.real_dtype
    zr, zi, qr, qi, _ = dense_leaf_arrays(tree.z, tree.q, idx, n_pad)
    c = tree.centers[cfg.nlevels]
    P = round_up(cfg.p + 1, 128)
    with jax.named_scope(launch_name("p2l", cfg.kernel)):
        outr, outi = p2l_pallas(
            conn.p2l, jnp.real(c).astype(rdt), jnp.imag(c).astype(rdt),
            rho.astype(rdt), zr, zi, qr, qi, p=cfg.p, P=P,
            kernel=cfg.kernel, tile_boxes=cfg.tile_boxes,
            stage_width=cfg.stage_width, interpret=interpret)
    return (outr + 1j * outi)[:, : cfg.p + 1].astype(cfg.complex_dtype)
