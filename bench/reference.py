"""The plain reference: the O(N^2) direct sum in float64 on the host.

phi(y_i) = sum_{x_j != y_i} G(y_i, x_j), with G the harmonic kernel
q_j / (x_j - y_i) or the logarithmic kernel q_j log(x_j - y_i) of
Goude & Engblom (arXiv:1205.4611, eqs. 1.1, 1.2 and 5.1). Coincident
points are excluded (x_j != y_i). Independent of the code under test:
it imports only numpy.

``error_normwise`` is the number the correctness check compares:
max_i |phi_i - ref_i| / max_i |ref_i| over the checked targets.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: Targets per block: a block of B targets holds B * N complex128 terms.
_BLOCK_TERMS = 1 << 21


def _block(ze, zs, qs, kernel):
    d = zs[None, :] - ze[:, None]
    ok = d != 0
    safe = np.where(ok, d, 1.0)
    if kernel == "harmonic":
        t = qs[None, :] / safe
    elif kernel == "log":
        t = qs[None, :] * np.log(-safe)
    else:
        raise ValueError(kernel)
    return np.where(ok, t, 0.0).sum(axis=1)


def direct_potential(z_eval, z_src, q, kernel: str = "harmonic",
                     threads: int = min(12, os.cpu_count() or 1)) -> np.ndarray:
    """float64 direct sum at ``z_eval`` over all sources ``(z_src, q)``.

    Blocks of targets run on a few threads (numpy releases the GIL in
    its ufuncs), so a few hundred targets over 2**20 sources take
    seconds."""
    ze = np.asarray(z_eval, np.complex128).ravel()
    zs = np.asarray(z_src, np.complex128).ravel()
    qs = np.asarray(q, np.complex128).ravel()
    step = max(1, _BLOCK_TERMS // max(len(zs), 1))
    blocks = [ze[i:i + step] for i in range(0, len(ze), step)]
    with ThreadPoolExecutor(max_workers=threads) as pool:
        parts = list(pool.map(lambda b: _block(b, zs, qs, kernel), blocks))
    return np.concatenate(parts) if parts else np.zeros(0, np.complex128)


def error_normwise(phi, ref) -> float:
    """max |phi - ref| / max |ref| (inf-norm error relative to the
    reference's inf-norm)."""
    phi = np.asarray(phi, np.complex128)
    ref = np.asarray(ref, np.complex128)
    return float(np.abs(phi - ref).max() / np.abs(ref).max())
