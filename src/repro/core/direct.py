"""Direct O(N^2) evaluation (paper eq. (1.1)/(1.2)) — oracle + baseline.

``direct_potential`` is the chunked jnp implementation used both as the
accuracy oracle for the FMM and as the break-even baseline of Fig. 5.5.
Coincident points are excluded, matching the ``x_j != y_i`` convention of
eq. (1.2) (and the FMM's own P2P convention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .mathf import clog


@functools.partial(jax.jit, static_argnames=("kernel", "chunk"))
def direct_potential(z_eval: jax.Array, z_src: jax.Array, q: jax.Array,
                     kernel: str = "harmonic", chunk: int = 2048) -> jax.Array:
    """Phi(y_i) = sum_{x_j != y_i} G(y_i, x_j)."""
    n = z_eval.shape[0]
    pad = (-n) % chunk
    ze = jnp.pad(z_eval, (0, pad))

    def body(carry, zc):
        diff = z_src[None, :] - zc[:, None]
        ok = diff != 0
        safe = jnp.where(ok, diff, 1.0)
        if kernel == "harmonic":
            c = jnp.where(ok, q[None, :] / safe, 0.0)
        else:
            c = jnp.where(ok, q[None, :] * clog(-safe), 0.0)
        return carry, c.sum(axis=-1)

    _, phi = jax.lax.scan(body, 0, ze.reshape(-1, chunk))
    return phi.reshape(-1)[:n]


def direct_potential_numpy(z_eval, z_src, q, kernel: str = "harmonic"):
    """float64 numpy oracle (independent of jax) for small-N tests."""
    import numpy as np

    ze = np.asarray(z_eval, dtype=np.complex128)
    zs = np.asarray(z_src, dtype=np.complex128)
    qs = np.asarray(q, dtype=np.complex128)
    out = np.zeros_like(ze)
    for i in range(len(ze)):
        d = zs - ze[i]
        ok = d != 0
        if kernel == "harmonic":
            out[i] = (qs[ok] / d[ok]).sum()
        else:
            out[i] = (qs[ok] * np.log(-d[ok])).sum()
    return out


def rel_error_inf(phi, phi_ref) -> float:
    """Paper eq. (5.3): || (phi - ref) / ref ||_inf  (on nonzero refs)."""
    import numpy as np

    phi = np.asarray(phi)
    ref = np.asarray(phi_ref)
    ok = np.abs(ref) > 0
    return float(np.max(np.abs((phi[ok] - ref[ok]) / ref[ok])))
