"""Jit'd wrappers wiring the M2L Pallas kernel into the FMM downward pass.

Two entry points share one kernel:

  m2l_level_apply  — one level (the ``m2l_impl`` per-level hook contract);
  m2l_fused_apply  — *all* levels of the downward pass flattened into a
                     single (sum 4^l, W) kernel call with static per-level
                     offsets (the ``m2l_fused_impl`` hook), replacing L
                     separate launches: each level's M2L depends only on
                     the upward pass, so the whole sweep is one grid.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...core import expansions as E
from ...core.config import FmmConfig
from ...core.mathf import clog
from ..common import round_up, staged_grid_steps
from .m2l import m2l_pallas


def _hankel_t(cfg: FmmConfig, P: int):
    h = np.zeros((P, P))
    h[: cfg.p + 1, : cfg.p + 1] = E.m2l_matrix(cfg.p)
    return jnp.asarray(h.T, dtype=cfg.real_dtype)


def _m2l_call(mult, weak, centers, cfg: FmmConfig, rho, interpret):
    """One kernel invocation over a (level-agnostic) flat box axis.

    mult: (nbox, p+1) complex *radius-normalized* coefficients; weak:
    (nbox, W) int32; centers/rho: (nbox,). The pre/post scale factors
    (rho_s/r and -rho_t/r — bounded ratios, see expansions.py) are computed
    here as complex planes; the kernel runs the power recurrences on them.
    Returns (nbox, p+1) complex normalized local contributions.
    """
    P = round_up(cfg.p + 1, 128)
    rdt = cfg.real_dtype

    pad = P - (cfg.p + 1)
    ar = jnp.pad(jnp.real(mult), ((0, 1), (0, pad))).astype(rdt)
    ai = jnp.pad(jnp.imag(mult), ((0, 1), (0, pad))).astype(rdt)

    mask = weak >= 0
    src = jnp.where(mask, weak, 0)
    r = jnp.where(mask, centers[:, None] - centers[src], 1.0)
    pre = jnp.where(mask, rho[src], 0.0) / r             # rho_s / r
    post = -rho[:, None] / r                             # -rho_t / r

    kwargs = {}
    if cfg.kernel == "log":
        with jax.named_scope("log_planes"):
            logr = clog(r)                               # masked slots: log 1
            kwargs = {"logr": jnp.real(logr).astype(rdt),
                      "logi": jnp.imag(logr).astype(rdt)}

    with jax.named_scope("m2l_fused"):
        outr, outi = m2l_pallas(
            weak, ar, ai,
            jnp.real(pre).astype(rdt), jnp.imag(pre).astype(rdt),
            jnp.real(post).astype(rdt), jnp.imag(post).astype(rdt),
            _hankel_t(cfg, P), p=cfg.p, kernel=cfg.kernel,
            tile_boxes=cfg.tile_boxes, stage_width=cfg.stage_width,
            interpret=interpret, **kwargs)
    return (outr + 1j * outi)[:, : cfg.p + 1].astype(mult.dtype)


def m2l_level_apply(mult, weak, centers, cfg: FmmConfig, rho,
                    interpret: bool | None = None):
    """Drop-in ``m2l_impl`` for ``repro.core.fmm.downward_with``."""
    return _m2l_call(mult, weak, centers, cfg, rho, interpret)


def fused_levels(cfg: FmmConfig) -> list[int]:
    """Levels the fused downward M2L covers (1..L; just the root if L=0)."""
    return list(range(1, cfg.nlevels + 1)) if cfg.nlevels > 0 else [0]


def m2l_fused_apply(mult, weak, centers, cfg: FmmConfig, rho,
                    interpret: bool | None = None):
    """Drop-in ``m2l_fused_impl`` for ``repro.core.fmm.downward_fused``.

    mult/weak/centers/rho are the *per-level* sequences (index = level).
    Concatenates every level's boxes into one flat axis — the weak lists
    are level-local, so each level's entries are shifted by its static
    offset — and issues exactly one ``pallas_call`` for the whole
    downward M2L. Returns the per-level (4**l, p+1) contributions.
    """
    levels = fused_levels(cfg)
    weak_flat, offs = _flat_weak(weak, cfg)
    mult_flat = jnp.concatenate([mult[l] for l in levels], axis=0)
    centers_flat = jnp.concatenate([centers[l] for l in levels])
    rho_flat = jnp.concatenate([rho[l] for l in levels])
    out = _m2l_call(mult_flat, weak_flat, centers_flat, cfg, rho_flat,
                    interpret)
    return [out[int(offs[i]): int(offs[i + 1])] for i in range(len(levels))]


def _flat_weak(weak, cfg: FmmConfig):
    """The fused levels' weak lists on one flat box axis, each level's
    entries shifted by its static box offset; returns (list, offsets)."""
    levels = fused_levels(cfg)
    offs = np.concatenate([[0], np.cumsum([4**l for l in levels])])
    return jnp.concatenate(
        [jnp.where(weak[l] >= 0, weak[l] + int(offs[i]), -1)
         for i, l in enumerate(levels)], axis=0), offs


def m2l_grid_steps(weak, cfg: FmmConfig):
    """``(steps, empty)`` of the grid ``m2l_fused_apply`` launches on the
    per-level weak lists ``weak`` (``common.staged_grid_steps``)."""
    weak_flat, _ = _flat_weak(weak, cfg)
    return staged_grid_steps([weak_flat[None]], weak_flat.shape[0],
                             cfg.tile_boxes, cfg.stage_width)[0]
