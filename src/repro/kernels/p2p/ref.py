"""Pure-jnp oracle for the P2P kernel (both G-kernels, dense leaf layout)."""
from __future__ import annotations

import jax.numpy as jnp

from ...core.mathf import clog


def p2p_ref(lists, tzr, tzi, trk, szr, szi, sqr, sqi, srk,
            kernel: str = "harmonic"):
    """Same contract as p2p_pallas; returns (outr, outi) of (nbox, n_pad).

    Self-interaction is excluded by global rank identity (trk/srk planes,
    -1 in padded slots), not by position coincidence: distinct particles
    at duplicated positions contribute their (singular) mutual term.
    """
    nbox, S = lists.shape
    dummy = szr.shape[0] - 1
    lists = jnp.where(lists >= 0, lists, dummy)
    tz = tzr + 1j * tzi                      # (nbox, n_pad)
    sz = (szr + 1j * szi)[lists]             # (nbox, S, n_pad)
    sq = (sqr + 1j * sqi)[lists]
    srkL = srk[lists]                        # (nbox, S, n_pad)
    diff = sz[:, None, :, :] - tz[:, :, None, None]   # (nbox, n_t, S, n_s)
    ok = ((srkL[:, None, :, :] >= 0)
          & (srkL[:, None, :, :] != trk[:, :, None, None]))
    if kernel == "harmonic":
        c = jnp.where(ok, sq[:, None, :, :], 0.0) / jnp.where(ok, diff, 1.0)
    else:
        c = jnp.where(ok, sq[:, None, :, :]
                      * clog(jnp.where(ok, -diff, 1.0)), 0.0)
    phi = c.sum(axis=(2, 3))
    return jnp.real(phi), jnp.imag(phi)
