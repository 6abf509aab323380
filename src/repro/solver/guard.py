"""Guarded execution: detect → recover → degrade, never silently corrupt.

The paper's adaptive discretization is only correct while the
connectivity caps hold; production inputs drift (time-stepping advects
particles, serving traffic changes distribution), and a drifted input
silently drops interactions on the trusting jit path. This module is
the robustness layer over ``FmmSolver``:

  detect    the in-graph health plane (``core.fmm.Health``) rides along
            every launch: per-class cap margins + non-finite flags, read
            with ONE ``device_get`` — no second eager topology build
  recover   ``apply_guarded`` escalates through a bounded, precompiled
            lattice of neighboring plans: per-class cap doubling (the
            margins say *which* cap to grow) with bounded recompile
            retries — the ``FmmSolver.build`` LRU is the lattice, so a
            rung compiles once and is a cache hit ever after
  degrade   a non-finite output (kernel fault) degrades per-phase: first
            the evaluation-phase hooks fall back to the reference
            sweeps, then the whole backend; the final rung is the
            O(N^2) ``core.direct`` summation, which cannot drop
            interactions and has no caps to overflow
  report    every attempt is recorded in a structured ``GuardReport``
            (rungs walked, margins seen, retries, degradations, final
            backend), and failures raise the typed errors of
            ``repro.errors`` — never a bare RuntimeError, never a
            silently wrong phi

Cf. Holm et al. (arXiv:1311.1006) — re-planning online from measured
feedback — and Agullo et al. (pipelined FMM over a runtime system) —
runtime monitors keeping long pipelines healthy. DESIGN.md §9 documents
the failure model and the cost of each rung.

Host spans of the profiler (``jax.profiler.TraceAnnotation``):
``fmm.guard.refresh`` around ``refresh_guarded``, ``fmm.guard.rung`` around
each rung of a ladder walk (its name as the span's ``rung`` argument),
``fmm.guard.read_margins`` and ``fmm.guard.health`` around the host reads
of the margins and of the health plane.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.config import FmmConfig
from ..core.direct import direct_potential
from ..core.fmm import HEALTH_CLASSES, FmmPlan
from ..errors import (CapOverflowError, NonFiniteInputError,
                      RecoveryExhaustedError)
from .backends import Backend, get_backend, register_backend
from .solver import FmmSolver, host_health

#: Interaction-list classes whose padded width is ``strong_cap``.
_STRONG_CLASSES = ("strong", "p2p", "p2l", "m2p")


@dataclasses.dataclass(frozen=True)
class GuardAttempt:
    """One rung of a ladder walk: what ran and what the health plane saw."""

    rung: str                  # "primary" | "caps*2^k" | "degrade:*" | "direct"
    backend: str
    strong_cap: int
    weak_cap: int
    ok: bool
    overflow: int = 0
    margins: Optional[dict] = None          # HEALTH_CLASSES -> slots left
    nonfinite_input: bool = False
    nonfinite_output: bool = False
    note: str = ""


@dataclasses.dataclass(frozen=True)
class GuardReport:
    """Structured record of one guarded call (DESIGN.md §9).

    ``attempts`` is the full walk in order; ``retries`` counts the extra
    attempts beyond the primary; ``degradations`` the backend-degrading
    rungs taken. ``ok`` means the returned phi is trustworthy: computed
    with zero dropped interactions and finite throughout.
    """

    entry: str                                # "apply" | "apply_batched" | ...
    attempts: tuple[GuardAttempt, ...]
    final_backend: Optional[str] = None
    final_rung: Optional[str] = None

    @property
    def ok(self) -> bool:
        return bool(self.attempts) and self.attempts[-1].ok

    @property
    def retries(self) -> int:
        return max(0, len(self.attempts) - 1)

    @property
    def degradations(self) -> tuple[str, ...]:
        return tuple(a.rung for a in self.attempts
                     if a.rung.startswith("degrade:") or a.rung == "direct")

    @property
    def margins(self) -> Optional[dict]:
        return self.attempts[-1].margins if self.attempts else None

    def summary(self) -> str:
        path = " -> ".join(a.rung for a in self.attempts) or "(empty)"
        state = "ok" if self.ok else "FAILED"
        return (f"[guard:{self.entry}] {path} ({state}, "
                f"backend={self.final_backend}, retries={self.retries})")


def grow_caps(cfg: FmmConfig, margins: Optional[dict] = None) -> FmmConfig:
    """One cap-escalation step, targeted by the per-class margins: only
    the cap families that actually overflowed double (``strong_cap``
    backs the strong/p2p/p2l/m2p lists, ``weak_cap`` the M2L lists).
    The weak cap is clamped to its structural bound ``4*strong_cap``
    (weak candidates are children of the parent's strong set). With no
    margins, both caps double."""
    need_strong = (margins is None
                   or any(margins.get(c, 0) < 0 for c in _STRONG_CLASSES))
    need_weak = margins is None or margins.get("weak", 0) < 0
    strong = cfg.strong_cap * 2 if need_strong else cfg.strong_cap
    weak = cfg.weak_cap * 2 if need_weak else cfg.weak_cap
    return dataclasses.replace(cfg, strong_cap=strong,
                               weak_cap=min(weak, 4 * strong))


def degraded_eval_backend(be: Backend) -> Optional[Backend]:
    """The per-phase degradation rung: ``be`` with its evaluation-phase
    hooks (fused evaluation, P2P, L2P, downward P2L) dropped back to the
    reference sweeps, keeping the topology and M2L hooks. Registered
    under ``"<name>+ref-eval"`` so ``FmmSolver.build`` can cache its
    compiled programs like any backend. None if ``be`` has nothing to
    degrade (already the reference path)."""
    if (be.eval_fused is None and be.p2p is None and be.l2p is None
            and be.p2l is None):
        return None
    name = f"{be.name}+ref-eval"
    degraded = dataclasses.replace(be, name=name, eval_fused=None,
                                   p2p=None, l2p=None, p2l=None)
    return register_backend(degraded)


class GuardedSolver:
    """``FmmSolver`` behind the recovery ladder (module docstring).

    The guarded entry points return ``(result, GuardReport)``. A
    successful cap escalation *promotes* the escalated solver to be the
    new primary (``self.solver``), so a time-stepping loop that drifted
    past its tuned caps re-plans once and stays on the fast path —
    instead of raising (or silently corrupting) every subsequent step.

      guarded = GuardedSolver(cfg, "auto")
      phi, report = guarded.apply_guarded(z, q)
      plan, report = guarded.refresh_guarded(z, q)   # time-stepping
      phi = guarded.apply_plan(plan)

    ``max_cap_doublings`` bounds the recompile retries of the cap rung;
    ``degrade``/``direct`` gate the backend-degradation and O(N^2)
    last-resort rungs.
    """

    def __init__(self, cfg: FmmConfig, backend: str = "auto", *,
                 max_cap_doublings: int = 3, degrade: bool = True,
                 direct: bool = True):
        if max_cap_doublings < 0:
            raise ValueError("max_cap_doublings must be >= 0")
        self.backend_name = backend
        self.max_cap_doublings = max_cap_doublings
        self.allow_degrade = degrade
        self.allow_direct = direct
        self.solver = FmmSolver.build(cfg, backend)

    @property
    def cfg(self) -> FmmConfig:
        """Config of the *current* primary (escalations promote)."""
        return self.solver.cfg

    @property
    def trace_counts(self) -> dict:
        return self.solver.trace_counts

    def apply_plan(self, plan: FmmPlan) -> jax.Array:
        return self.solver.apply_plan(plan)

    # -- ladder machinery ---------------------------------------------------

    def _attempt(self, solver: FmmSolver, z, q, rung: str, attempts: list,
                 batched: bool, note: str = ""):
        """Run one rung's health-instrumented apply; record the result."""
        with TraceAnnotation("fmm.guard.rung", rung=rung):
            if batched:
                phi, health = solver.apply_batched_with_health(z, q)
            else:
                phi, health = solver.apply_with_health(z, q)
            with TraceAnnotation("fmm.guard.health"):
                h = host_health(health)
        ok = not (h["overflow"] or h["nonfinite_input"]
                  or h["nonfinite_output"])
        attempts.append(GuardAttempt(
            rung=rung, backend=solver.dispatched["apply"],
            strong_cap=solver.cfg.strong_cap, weak_cap=solver.cfg.weak_cap,
            ok=ok, overflow=h["overflow"], margins=h["margins"],
            nonfinite_input=h["nonfinite_input"],
            nonfinite_output=h["nonfinite_output"], note=note))
        return phi, h, ok

    def _report(self, entry: str, attempts: list) -> GuardReport:
        last = attempts[-1] if attempts else None
        return GuardReport(entry=entry, attempts=tuple(attempts),
                           final_backend=last.backend if last else None,
                           final_rung=last.rung if last else None)

    def _direct_rung(self, z, q, attempts: list, batched: bool):
        """Last resort: the O(N^2) direct summation — no caps to
        overflow, no expansions to go non-finite on finite input."""
        kernel = self.solver.cfg.kernel

        def one(zi, qi):
            return direct_potential(zi, zi, qi, kernel=kernel)

        with TraceAnnotation("fmm.guard.rung", rung="direct"):
            phi = (jax.vmap(one) if batched else one)(z, q)
            finite = bool(np.all(np.isfinite(np.asarray(phi))))
        attempts.append(GuardAttempt(
            rung="direct", backend="direct",
            strong_cap=self.solver.cfg.strong_cap,
            weak_cap=self.solver.cfg.weak_cap, ok=finite,
            nonfinite_output=not finite,
            note="O(N^2) reference summation (exact, capless)"))
        return phi, finite

    def _ladder(self, z, q, entry: str, batched: bool):
        attempts: list[GuardAttempt] = []
        phi, h, ok = self._attempt(self.solver, z, q, "primary", attempts,
                                   batched)
        if ok:
            return phi, self._report(entry, attempts)
        if h["nonfinite_input"]:
            # garbage in: nothing downstream can recover — fail loud now
            raise NonFiniteInputError(
                f"{entry}: z or q contain NaN/Inf; no recovery rung can "
                "repair a non-finite input "
                f"({self._report(entry, attempts).summary()})")

        # rung 1: cap escalation through the precompiled plan lattice.
        # The per-class margins pick which cap doubles; each rung is an
        # FmmSolver.build hit after its first compile.
        solver = self.solver
        if h["overflow"]:
            for _ in range(self.max_cap_doublings):
                cfg = grow_caps(solver.cfg, h["margins"])
                solver = FmmSolver.build(cfg, self.backend_name)
                phi, h, ok = self._attempt(
                    solver, z, q, f"caps*{cfg.strong_cap}/{cfg.weak_cap}",
                    attempts, batched)
                if ok:
                    self.solver = solver      # promote: re-planned
                    return phi, self._report(entry, attempts)
                if not h["overflow"]:
                    break                     # caps fixed; other fault left

        # rung 2: per-phase degradation — only a non-finite output can be
        # cured by swapping compute paths (a reference sweep at the same
        # caps would drop the same interactions).
        if self.allow_degrade and not h["overflow"] and h["nonfinite_output"]:
            for variant in filter(None, (degraded_eval_backend(solver.backend),
                                         get_backend("reference"))):
                if variant.name == solver.backend.name:
                    continue
                deg = FmmSolver.build(solver.cfg, variant.name)
                phi, h, ok = self._attempt(
                    deg, z, q, f"degrade:{variant.name}", attempts, batched,
                    note="non-finite output: phase hooks -> reference")
                if ok:
                    return phi, self._report(entry, attempts)

        # rung 3: direct summation
        if self.allow_direct:
            phi, finite = self._direct_rung(z, q, attempts, batched)
            if finite:
                return phi, self._report(entry, attempts)

        report = self._report(entry, attempts)
        raise RecoveryExhaustedError(
            f"{entry}: every recovery rung failed — {report.summary()}",
            report=report)

    # -- guarded entry points -----------------------------------------------

    def apply_guarded(self, z: jax.Array, q: jax.Array):
        """``apply`` behind the full recovery ladder. Returns
        ``(phi, GuardReport)``; phi is never a silently-truncated or
        non-finite answer — recovery failure raises instead."""
        return self._ladder(z, q, "apply", batched=False)

    def apply_batched_guarded(self, z: jax.Array, q: jax.Array):
        """``apply_batched`` behind the ladder: health is reduced across
        the batch, so one unhealthy row escalates the whole batch (the
        batch shares one cap budget). Returns ``(phi (B, N), report)``."""
        return self._ladder(z, q, "apply_batched", batched=True)

    def refresh_guarded(self, z: jax.Array, q: jax.Array):
        """``refresh`` with automatic re-planning: when the plan's
        margins show cap overflow (particles drifted past the tuned
        budget), escalate caps — bounded doublings, each a compiled-
        once lattice neighbor — promote the escalated solver, and
        return its healthy plan. Returns ``(plan, GuardReport)``; feed
        the plan to ``apply_plan``. The steady-state cost over plain
        ``refresh`` is one host read of the margins vector."""
        with TraceAnnotation("fmm.guard.refresh"):
            attempts: list[GuardAttempt] = []
            solver = self.solver
            for _ in range(self.max_cap_doublings + 1):
                rung = ("primary" if solver is self.solver
                        else f"caps*{solver.cfg.strong_cap}/{solver.cfg.weak_cap}")
                with TraceAnnotation("fmm.guard.rung", rung=rung):
                    plan = solver.refresh(z, q)
                    with TraceAnnotation("fmm.guard.read_margins"):
                        margins, overflow = jax.device_get(
                            (plan.conn.margins, plan.conn.overflow))
                m = {c: int(v) for c, v in zip(HEALTH_CLASSES, margins)}
                ok = int(overflow) == 0
                attempts.append(GuardAttempt(
                    rung=rung, backend=solver.dispatched["apply"],
                    strong_cap=solver.cfg.strong_cap,
                    weak_cap=solver.cfg.weak_cap, ok=ok,
                    overflow=int(overflow), margins=m))
                if ok:
                    if solver is not self.solver:
                        self.solver = solver       # promote the re-plan
                    return plan, self._report("refresh", attempts)
                solver = FmmSolver.build(grow_caps(solver.cfg, m),
                                         self.backend_name)
            report = self._report("refresh", attempts)
            raise CapOverflowError(
                f"refresh: caps still overflow after {self.max_cap_doublings} "
                f"doublings — {report.summary()}",
                margins=attempts[-1].margins, overflow=attempts[-1].overflow)

    # -- lattice warm-up ----------------------------------------------------

    def precompile(self, z: jax.Array, q: jax.Array) -> list[str]:
        """Compile the ladder's neighboring plans ahead of the fault:
        the cap-doubling chain and the degradation variants all become
        ``FmmSolver.build`` cache hits, so mid-run recovery pays a plan
        switch, not a compile. Returns the list of warmed rung names."""
        warmed = []
        cfg = self.solver.cfg
        chain = [(cfg, self.backend_name)]
        for _ in range(self.max_cap_doublings):
            cfg = grow_caps(cfg)
            chain.append((cfg, self.backend_name))
        if self.allow_degrade:
            deg = degraded_eval_backend(self.solver.backend)
            if deg is not None:
                chain.append((self.solver.cfg, deg.name))
            chain.append((self.solver.cfg, "reference"))
        for rung_cfg, backend in chain:
            solver = FmmSolver.build(rung_cfg, backend)
            jax.block_until_ready(solver.apply_with_health(z, q)[0])
            warmed.append(f"{backend}@{rung_cfg.strong_cap}/"
                          f"{rung_cfg.weak_cap}")
        return warmed
