"""What every loop of the benchmark shares.

A traffic file names its loop under ``"loop"``: the file
``bench/loops/<loop>.py``, whose class ``Loop`` (a subclass of ``Entry``)
the harness loads by that name; the rest of the traffic file is the
loop's parameters. A loop builds its inputs from the seed and the solver
from the configuration in ``setup``, runs one unit of work (one call, or
one time step) in ``unit`` and waits for it, and afterwards compares a
sample of what the window produced with the float64 direct sum in
``readings``. A new kind of traffic is a new file under ``bench/loops``.

The loops hold the program's outputs from a few units of the window,
drawn by reservoir sampling from the seed, so that the check covers the
window's own results without keeping every output on the device.
"""
from __future__ import annotations

import numpy as np

from . import control, generate, reference


class Reservoir:
    """Uniform sample of at most ``k`` of the items offered (Algorithm R)."""

    def __init__(self, k: int, gen: np.random.Generator):
        self.k, self.gen = k, gen
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.gen.integers(self.seen))
            if j < self.k:
                self.items[j] = item


def fmm_config(config: dict):
    """The program's ``FmmConfig`` from a configuration file's ``fmm``."""
    from repro.core.config import FmmConfig

    return FmmConfig(**config["fmm"])


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Entry:
    unit_name = "call"

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.traffic, self.seed = traffic, seed
        self.cfg = fmm_config(config)
        self.backend = config["backend"]
        self.kept = Reservoir(traffic["retain"], generate.rng(seed, 2))
        self._refs: dict = {}

    def build_solver(self, entry: str):
        """The configuration's solver; refuses one whose ``entry`` would
        run another backend than the configuration states."""
        from repro.solver import FmmSolver

        solver = FmmSolver.build(self.cfg, self.backend)
        if solver.dispatched[entry] != self.backend:
            raise RuntimeError(f"{entry} dispatched {solver.dispatched[entry]!r}, "
                               f"the configuration states {self.backend!r}")
        return solver

    def setup(self) -> None:
        raise NotImplementedError

    def unit(self, i: int) -> int:
        """Run unit ``i`` of the window and wait for it; returns how many
        of its parts failed."""
        raise NotImplementedError

    def samples(self):
        """``(key, phi, z, q)`` on the host for each problem the check
        covers: the program's potential and the positions and charges it
        was computed from."""
        raise NotImplementedError

    def _targets(self, n: int, key: int) -> np.ndarray:
        count = self.traffic["targets"]
        if count >= n:
            return np.arange(n)
        return np.sort(generate.rng(self.seed, 3, key).choice(
            n, count, replace=False))

    def _errors(self, phi_at) -> float:
        """err_norm of ``phi_at(phi, z, q, targets)`` against the direct
        sum, worst over the checked problems."""
        errs = []
        for key, phi, z, q in self.samples():
            t = self._targets(len(z), key)
            if key not in self._refs:
                self._refs[key] = reference.direct_potential(
                    z[t], z, q, self.cfg.kernel)
            errs.append(reference.error_normwise(phi_at(phi, z, q, t),
                                                 self._refs[key]))
        return max(errs)

    def readings(self, direct: str | None = None) -> dict:
        """The numbers compared. ``direct`` ("bf16" or "f32") puts the
        direct sum in that precision (``bench.control``) in the program's
        place at the same targets: a control run."""
        if direct is None:
            return {"err_norm": self._errors(lambda phi, z, q, t: phi[t])}
        return {"err_norm": self._errors(
            lambda phi, z, q, t: control.direct_potential(
                z, q, t, self.cfg.kernel, direct))}
