"""Pure-jnp oracles for the fused evaluation kernels.

The end-to-end oracle for ``eval_fused_apply`` is the unfused core path
(``l2p`` + ``m2p_sweep`` + ``p2p_sweep``), which the parity tests use
directly; ``m2p_ref`` is the dense-plane oracle for the megakernel's M2P
branch in isolation.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.mathf import clog


def m2p_ref(lists, tzr, tzi, ar, ai, mcr, mci, mrho, p: int,
            kernel: str = "harmonic"):
    """Dense-plane M2P: Horner in w = rho_s/(z - z0_s) per list slot.

    lists: (nbox, S) int32 (-1 masked); tzr/tzi: (nbox, n_pad) targets;
    ar/ai: (nbox+1, P) multipole planes (dummy row zero); mcr/mci/mrho:
    (nbox, S) per-slot source center/radius planes (masked slots zero).
    Returns (outr, outi): (nbox, n_pad).
    """
    dummy = ar.shape[0] - 1
    srcs = jnp.where(lists >= 0, lists, dummy)
    a = (ar + 1j * ai)[srcs]                   # (nbox, S, P)
    tz = tzr + 1j * tzi
    dz = tz[:, None, :] - (mcr + 1j * mci)[..., None]   # (nbox, S, n_pad)
    # slot-validity gate (masked slots carry rho = 0); a target at the
    # source center goes singular, as in the core m2p_sweep
    ok = (mrho > 0)[..., None]
    w = jnp.where(ok, mrho[..., None] / dz, 0.0)
    acc = jnp.zeros_like(w) + a[..., p:p + 1]
    for j in range(p - 1, 0, -1):
        acc = acc * w + a[..., j:j + 1]
    acc = acc * w
    if kernel == "log":
        acc = acc + a[..., 0:1] * jnp.where(
            ok, clog(jnp.where(ok, dz, 1.0)), 0.0)
    phi = jnp.where(ok, acc, 0.0).sum(axis=1)
    return jnp.real(phi), jnp.imag(phi)
