"""Deterministic synthetic data.

Streams are *stateless*: batch contents are a pure function of
(seed, step), so any worker can regenerate any batch after a
restart/re-shard — no data-loader state in checkpoints, which is the
fault-tolerance-friendly design for 1000+ nodes (exercised by the
``Prefetcher``/runtime tests).

``particles`` reproduces the paper's three source distributions
(Fig. 5.8): uniform in the unit square, N(0, 1/100) and the 'layer'
distribution, all rejected to fit the unit square exactly as in the paper.
"""
from __future__ import annotations

import dataclasses
import queue
import threading

import numpy as np
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab: int
    batch: int
    seq: int
    seed: int = 0


def lm_batch(dc: DataConfig, step: int):
    """Synthetic token batch, deterministic in (seed, step); used by the
    data-pipeline/prefetcher tests."""
    rng = np.random.default_rng(np.random.PCG64((dc.seed, step)))
    useful_vocab = min(dc.vocab, 1024)
    a = rng.integers(0, useful_vocab, (dc.batch, 1))
    b = rng.integers(1, 17, (dc.batch, 1))
    t = np.arange(dc.seq + 1)[None, :]
    toks = (a + b * t) % useful_vocab
    return {
        "tokens": jnp.asarray(toks[:, :-1], jnp.int32),
        "labels": jnp.asarray(toks[:, 1:], jnp.int32),
    }


# ---------------------------------------------------------------------------
# particle distributions (paper Fig. 5.8)
# ---------------------------------------------------------------------------

def particles(dist: str, n: int, seed: int = 0):
    """Complex positions in the unit square + normal random charges.

    Host numpy arrays (complex128): the caller casts to its config's
    ``complex_dtype`` before anything goes to the device — a TPU holds
    no complex128 arrays."""
    rng = np.random.default_rng(seed)

    def rejected(gen):
        out = np.empty(0, np.complex128)
        while out.size < n:
            z = gen(2 * (n - out.size) + 16)
            ok = (z.real >= 0) & (z.real <= 1) & (z.imag >= 0) & (z.imag <= 1)
            out = np.concatenate([out, z[ok]])
        return out[:n]

    if dist == "uniform":
        z = rng.uniform(0, 1, n) + 1j * rng.uniform(0, 1, n)
    elif dist == "normal":
        z = rejected(lambda m: (0.5 + rng.normal(0, 0.1, m))
                     + 1j * (0.5 + rng.normal(0, 0.1, m)))
    elif dist == "layer":
        z = rejected(lambda m: rng.uniform(0, 1, m)
                     + 1j * (0.5 + rng.normal(0, 0.1, m)))
    else:
        raise ValueError(dist)
    q = rng.normal(size=n)
    return z, q + 0j


def ragged_requests(num: int, *, seed: int = 0, median_n: int = 256,
                    sigma: float = 0.8, n_min: int = 4,
                    n_max: int | None = None, poison_rate: float = 0.0,
                    dist: str = "uniform"):
    """Synthetic ragged serving workload: ``num`` requests whose sizes
    follow a log-normal distribution (the classic heavy-tailed traffic
    shape), with a configurable fraction of *poison* requests.

    Yields ``(n, z, q, kind)`` tuples, deterministic per ``(seed, i)``
    (stateless, like every stream in this module — any consumer can
    regenerate any request). ``kind`` is ``"ok"`` or the poison flavor:

      "nan-q"     one charge is NaN (non-finite input)
      "inf-z"     one position is Inf
      "real-z"    positions handed over as a real array (dtype confusion)
      "empty"     zero-length arrays

    Shared by the serving soak (``repro.testing.serve_faults``), the
    serving benchmark (``benchmarks/serving.py``) and the serve tests so
    all three exercise the *same* traffic distribution.
    """
    if not 0.0 <= poison_rate <= 1.0:
        raise ValueError(f"poison_rate must be in [0, 1]; got {poison_rate}")
    poisons = ("nan-q", "inf-z", "real-z", "empty")
    for i in range(num):
        rng = np.random.default_rng(np.random.PCG64((seed, i)))
        n = int(np.clip(np.round(rng.lognormal(np.log(median_n), sigma)),
                        n_min, n_max if n_max is not None else np.inf))
        z, q = particles(dist, n, seed=int(rng.integers(1 << 30)))
        z = np.asarray(z)
        q = np.asarray(q)
        kind = "ok"
        if poison_rate and rng.uniform() < poison_rate:
            kind = poisons[int(rng.integers(len(poisons)))]
            if kind == "nan-q":
                q = q.copy()
                q[int(rng.integers(n))] = np.nan
            elif kind == "inf-z":
                z = z.copy()
                z[int(rng.integers(n))] = np.inf + 0j
            elif kind == "real-z":
                z = z.real.copy()
            elif kind == "empty":
                z = z[:0]
                q = q[:0]
        yield n, z, q, kind


class Prefetcher:
    """Background-thread batch prefetch (depth-k queue)."""

    def __init__(self, fn, start_step: int = 0, depth: int = 2):
        self._fn = fn
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._step = start_step
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        s = self._step
        while not self._stop.is_set():
            try:
                self._q.put((s, self._fn(s)), timeout=0.5)
                s += 1
            except queue.Full:
                continue

    def get(self):
        return self._q.get()

    def close(self):
        self._stop.set()
