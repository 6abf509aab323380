"""Point-vortex dynamics: one unit is one RK2 step, each of its two stages
``GuardedSolver.refresh_guarded`` then ``apply_plan`` on the current
positions, with u - iv = phi / (2 pi i). The vortices start as the
traffic's Gaussian patches. A guard report that is not ok, or that
walked a degrade or direct rung, counts as a failed stage.
"""
from __future__ import annotations

import numpy as np

from bench import generate
from bench.entry import Entry, annotate


class Loop(Entry):
    unit_name = "step"

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        t = self.traffic
        z, g = generate.vortex_patches(generate.rng(self.seed, 0),
                                       self.cfg.n, t["patches"])
        dtype = self.cfg.complex_dtype
        self.g_host = g.astype(dtype)
        self.z0 = z
        with annotate("bench.inputs"):
            self.z = jax.device_put(z.astype(dtype))
            self.g = jax.device_put(self.g_host)
        self.guard = self.build_solver("apply").guarded(
            max_cap_doublings=t["max_cap_doublings"])
        self.dt = float(t["dt"])
        self._advance = jax.jit(
            lambda z, phi, h: z + h * jnp.conj(phi / (2j * jnp.pi)))
        # A stage runs every program a step does: ``warm`` stages from the
        # initial state, whose results are dropped.
        self.retries, self.min_margin = 0, None
        for _ in range(t["warm"]):
            with annotate("bench.block"):
                self._stage(self.z, self.z, 0.5 * self.dt)[0].block_until_ready()
        self.retries, self.min_margin = 0, None

    def _stage(self, z_eval, z_from, h: float):
        """One RK2 stage: the velocity at ``z_eval`` advances ``z_from`` by
        ``h``. Returns the new positions, phi and whether the guard failed."""
        with annotate("bench.refresh_guarded"):
            plan, report = self.guard.refresh_guarded(z_eval, self.g)
        with annotate("bench.apply_plan"):
            phi = self.guard.apply_plan(plan)
        with annotate("bench.advect"):
            z_new = self._advance(z_from, phi, h)
        bad = (not report.ok) or any(
            a.rung.startswith("degrade:") or a.rung == "direct"
            for a in report.attempts)
        self.retries += report.retries
        if report.margins:
            least = min(report.margins.values())
            self.min_margin = (least if self.min_margin is None
                               else min(self.min_margin, least))
        return z_new, phi, int(bad)

    def unit(self, i: int) -> int:
        z = self.z
        zm, phi1, bad1 = self._stage(z, z, 0.5 * self.dt)
        z1, phi2, bad2 = self._stage(zm, z, self.dt)
        with annotate("bench.block"):
            z1.block_until_ready()
        self.kept.offer((z, phi1))
        self.kept.offer((zm, phi2))
        self.z = z1
        return bad1 + bad2

    def samples(self):
        for j, (z, phi) in enumerate(self.kept.items):
            yield j, np.asarray(phi), np.asarray(z), self.g_host

    def readings(self, direct: str | None = None) -> dict:
        """Also, for the log and not compared: the guard's cap doublings in
        the window, the fewest list slots any evaluation left free, and how
        far the vortices moved from their start (median and largest)."""
        moved = np.abs(np.asarray(self.z) - self.z0)
        return {**super().readings(direct), "guard_retries": self.retries,
                "min_margin": self.min_margin,
                "moved_median": float(np.median(moved)),
                "moved_max": float(moved.max())}
