"""Closed loop over ``FmmSolver.apply`` (traffic ``"entry": "apply"``, one
problem per call) or ``FmmSolver.apply_batched`` (``"entry":
"apply_batched"``, ``batch`` problems per call).

Problem i of a call takes the distribution ``dists[i % len(dists)]``.
Set-up makes the inputs of ``problems`` distinct calls, puts them on the
device and makes ``warm`` calls; the calls after them, the window's, take
the next inputs in turn, so with ``problems`` more than the warm-up and
the window's calls together, no call repeats an input.
"""
from __future__ import annotations

import numpy as np

from bench import generate
from bench.entry import Entry, annotate


class Loop(Entry):

    def setup(self) -> None:
        import jax

        t = self.traffic
        n, rows = self.cfg.n, t.get("batch", 1)
        shape = (rows, n) if "batch" in t else (n,)
        gen = generate.rng(self.seed, 0)
        dtype = self.cfg.complex_dtype
        self.host = []
        for _ in range(t["problems"]):
            zq = [generate.particles(gen, t["dists"][i % len(t["dists"])], n,
                                     t.get("sigma", 0.1)) for i in range(rows)]
            self.host.append(tuple(np.stack(x).astype(dtype).reshape(shape)
                                   for x in zip(*zq)))
        with annotate("bench.inputs"):
            self.dev = jax.device_put(self.host)
        self.solver = self.build_solver(t["entry"])
        self._call = getattr(self.solver, t["entry"])
        self.calls = 0
        for i in range(t["warm"]):
            self.unit(i, keep=False)

    def unit(self, i: int, keep: bool = True) -> int:
        k = self.calls % len(self.dev)
        self.calls += 1
        with annotate("bench.call"):
            phi = self._call(*self.dev[k])
        with annotate("bench.block"):
            phi.block_until_ready()
        if keep:
            self.kept.offer((k, phi))
        return 0

    def _rows(self, k: int):
        return (x.reshape(-1, self.cfg.n) for x in self.host[k])

    def samples(self):
        for k, phi in self.kept.items:
            z, q = self._rows(k)
            phi = np.asarray(phi).reshape(z.shape)
            for b in range(len(z)):
                yield k * len(z) + b, phi[b], z[b], q[b]

    def readings(self, direct: str | None = None) -> dict:
        """Also ``overflow``: the interactions the caps would drop on the
        inputs of the checked calls, by the program's own connectivity
        count (0 where the configuration's caps hold, as it guarantees)."""
        overflow = max(int(self.solver.refresh(zr, qr).conn.overflow)
                       for k in sorted({k for k, _ in self.kept.items})
                       for zr, qr in zip(*self._rows(k)))
        return {**super().readings(direct), "overflow": overflow}
