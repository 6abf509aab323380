"""Inputs from a seed: particle sets and vortex patches.

Copied from the program's generators (``repro.data.particles`` and the
vortex-patch set-up of ``examples/vortex_dynamics.py``) so that the
yardstick cannot move with them. Each stream is a pure function of
``(seed, stream)``: the same seed gives the same inputs.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of a run's seed (any int >= 0)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def particles(gen: np.random.Generator, dist: str, n: int,
              sigma: float = 0.1):
    """Positions in the unit square and normal random charges (complex128).

    ``uniform``: uniform in the unit square; ``normal``: N(0.5, sigma^2)
    per coordinate, rejected to the unit square (the paper's Fig. 5.8
    distributions)."""
    if dist == "uniform":
        z = gen.uniform(0, 1, n) + 1j * gen.uniform(0, 1, n)
    elif dist == "normal":
        z = np.empty(0, np.complex128)
        while z.size < n:
            m = 2 * (n - z.size) + 16
            c = (0.5 + gen.normal(0, sigma, m)) + 1j * (0.5 + gen.normal(0, sigma, m))
            ok = (c.real >= 0) & (c.real <= 1) & (c.imag >= 0) & (c.imag <= 1)
            z = np.concatenate([z, c[ok]])
        z = z[:n]
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    q = gen.normal(size=n) + 0j
    return z, q


def vortex_patches(gen: np.random.Generator, n: int, patches: list):
    """Gaussian vortex patches: ``patches`` is a list of
    ``{"center": [x, y], "sigma": s, "circulation": G}``; the N
    vortices are split evenly, each patch's circulation shared equally
    among its vortices. Returns positions and circulations (complex128)."""
    counts = [n // len(patches)] * len(patches)
    counts[-1] += n - sum(counts)
    zs, gs = [], []
    for patch, m in zip(patches, counts):
        cx, cy = patch["center"]
        s = patch["sigma"]
        zs.append(cx + 1j * cy + s * (gen.normal(size=m) + 1j * gen.normal(size=m)))
        gs.append(np.full(m, patch["circulation"] / m))
    return np.concatenate(zs), np.concatenate(gs) + 0j
