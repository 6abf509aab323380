"""`FmmSolver` — the production front-end over the FMM pipeline.

One object wraps the whole paper pipeline (sort + connect + upward +
downward + evaluate) behind a jit-able entry point:

    solver = FmmSolver.build(cfg, backend="auto")   # cached per config
    phi = solver.apply(z, q)                        # one problem
    phib = solver.apply_batched(zb, qb)             # (B, N) -> (B, N)
    solver = solver.tune(z_sample)                  # fit the list caps

Time-stepping workloads (vortex methods: particles move a little each
step, topology must be refreshed thousands of times) split ``apply`` at
the topology/evaluation seam:

    plan = solver.refresh(z, q)     # device-resident sort + connect only
    phi = solver.apply_plan(plan)   # upward/downward/evaluation

``build`` memoizes solvers by ``(FmmConfig, backend)`` so repeated calls
share one compiled program — the plan cache. ``apply_batched`` vmaps the
single-problem pipeline over a leading batch axis: because *all*
adaptivity lives in the contents of statically-shaped padded lists,
B independent problems of the same config are one XLA program with a
batch dimension — the "millions of users" serving shape. On the pallas
backend the kernels are *batch-native*: their custom batching rules
lower the vmap onto batch-major (B, ...) kernel grids, so the batched
entry point keeps the fused-launch pipeline (one launch per phase for
the whole batch) instead of downgrading to the jnp sweeps. The batch
shares one connectivity-cap budget; size it with ``tune`` on a 2-D
sample; ``apply_batched_checked`` max-reduces the overflow scalar
across the batch.

Backends (``repro.solver.backends``) swap the hot phases between the
Pallas TPU kernels and the pure-jnp reference sweeps per phase.

Each entry point runs under a host span of the profiler
(``jax.profiler.TraceAnnotation``) named ``fmm.<entry>``, with
``fmm.validate`` around its argument checks and ``fmm.dispatch`` around
the jitted call; the compiled programs carry the phase scopes of
``repro.core.fmm``. Without a running profiler the spans cost nothing
measurable.
"""
from __future__ import annotations

import copy
import warnings
from collections import OrderedDict
from typing import NamedTuple, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.config import FmmConfig
from ..core.fmm import (HEALTH_CLASSES, FmmPlan, Health, fmm_build,
                        fmm_evaluate, health_of, unsort)
from ..core.topology import connectivity_stats
from ..errors import (BackendDowngradeWarning, CapOverflowError, DTypeError,
                      NonFiniteInputError, NonFiniteOutputError, ShapeError)
from ..kernels.eval.ops import eval_grid_steps, p2l_grid_steps
from ..kernels.m2l.ops import m2l_grid_steps
from .autotune import TuneResult, tune_caps, tune_tiles
from .backends import Backend, get_backend

# LRU of compiled solvers, keyed by (cfg, resolved backend name) — so
# "auto" shares the entry of whatever backend it resolves to. Bounded:
# per-workload tuning in a long-lived service mints fresh configs, and
# each solver pins up to six compiled XLA programs (entry points +
# health twins). Eviction (and cache_clear) releases those programs via
# ``_release_executables`` so they cannot strand device memory; evicted
# instances stay usable by existing holders — the next call re-traces.
# Hit/miss/eviction traffic is observable via ``FmmSolver.cache_info()``
# (the keyed-executable-cache seam the serving plane builds on,
# ``repro.serve.cache``).
_CACHE: OrderedDict = OrderedDict()
_CACHE_MAX = 64
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


class CacheInfo(NamedTuple):
    """``FmmSolver.cache_info()`` snapshot (functools.lru_cache idiom)."""

    hits: int
    misses: int
    maxsize: int
    currsize: int
    evictions: int


def host_health(health: Health) -> dict:
    """ONE ``device_get`` of the in-graph health plane, reduced across a
    leading batch axis if present: margins min per class, overflow max,
    non-finite flags any. Returns plain-python values."""
    margins, overflow, nf_in, nf_out = (np.asarray(x) for x in
                                        jax.device_get(health))
    if margins.ndim == 2:       # batched: worst row per class
        margins = margins.min(axis=0)
    return {
        "margins": {c: int(m) for c, m in zip(HEALTH_CLASSES, margins)},
        "overflow": int(overflow.max()),
        "nonfinite_input": bool(nf_in.any()),
        "nonfinite_output": bool(nf_out.any()),
    }


def raise_unhealthy(h: dict, cfg: FmmConfig, entry: str = "apply") -> None:
    """Raise the typed error matching a ``host_health`` dict (no-op when
    healthy). Order: garbage input first, then dropped interactions,
    then non-finite output — the most actionable diagnosis wins."""
    if h["nonfinite_input"]:
        raise NonFiniteInputError(
            f"{entry}: z or q contain NaN/Inf — refusing to compute on "
            "non-finite input")
    if h["overflow"]:
        neg = {c: m for c, m in h["margins"].items() if m < 0}
        raise CapOverflowError(
            f"{entry}: connectivity caps overflow by {h['overflow']} "
            f"(strong_cap={cfg.strong_cap}, weak_cap={cfg.weak_cap}; "
            f"negative margins {neg}); re-tune on this workload",
            margins=h["margins"], overflow=h["overflow"])
    if h["nonfinite_output"]:
        raise NonFiniteOutputError(
            f"{entry}: phi contains NaN/Inf on finite input — kernel or "
            "expansion fault (degrade the evaluation phase to the "
            "reference backend, or use apply_guarded)")


class FmmSolver:
    """Compiled FMM evaluator for one ``FmmConfig`` + backend choice.

    Prefer ``FmmSolver.build`` over the constructor: ``build`` returns
    the cached instance (and its already-compiled XLA program) for a
    config seen before.
    """

    def __init__(self, cfg: FmmConfig, backend: str = "auto"):
        self.cfg = cfg
        self.backend_name = backend
        self.backend: Backend = get_backend(backend, cfg)
        if not self.backend.supports(cfg):
            raise NotImplementedError(
                f"backend {self.backend.name!r} does not support "
                f"kernel={cfg.kernel!r}")
        self._impls = self.backend.phase_impls(cfg)
        self._topo = self.backend.topology_impls(cfg)
        # Batched path (the three-way batched-dispatch contract, see
        # repro.solver.backends): "native" hooks lower jax.vmap onto
        # batch-major kernel grids, "vmap" hooks batch as plain jnp —
        # both serve batches through the backend's own hooks. Only a
        # "fallback" backend downgrades to the reference sweeps (same
        # answer, jnp path).
        if self.backend.batched_dispatch == "fallback":
            ref = get_backend("reference")
            batched_impls = ref.phase_impls(cfg)
            batched_topo = ref.topology_impls(cfg)
            batched_name = ref.name
        else:
            batched_impls, batched_topo = self._impls, self._topo
            batched_name = self.backend.name
        # Record what each entry point ACTUALLY runs, so benchmark and
        # serving numbers cannot silently be attributed to the wrong
        # backend (the batched downgrade also warns once, below).
        self.dispatched = {
            "apply": self.backend.name,
            "apply_batched": batched_name,
        }
        self._warned_batched_fallback = False
        # trace counters: the refresh/apply entry points are compiled
        # once per solver; re-tracing on a steady-shape time-stepping
        # loop would be a plan-cache bug (asserted in tests).
        self.trace_counts = {"build": 0, "evaluate": 0}
        self._apply = jax.jit(self._make_core(self._impls, self._topo))
        self._apply_batched = jax.jit(jax.vmap(
            self._make_core(batched_impls, batched_topo)))
        # health twins: same pipeline, plus the in-graph health plane —
        # ONE launch serves phi AND the overflow/non-finite diagnosis,
        # so the checked/guarded entry points never pay a second build.
        self._apply_health = jax.jit(
            self._make_core(self._impls, self._topo, with_health=True))
        self._apply_batched_health = jax.jit(jax.vmap(
            self._make_core(batched_impls, batched_topo, with_health=True)))
        self._refresh = jax.jit(self._make_build(self._topo))
        self._apply_plan = jax.jit(self._make_evaluate(self._impls))
        self._grid_steps = jax.jit(self._make_grid_steps())
        self.tune_result: Optional[TuneResult] = None

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, cfg: FmmConfig, backend: str = "auto") -> "FmmSolver":
        """Cached constructor: one solver (and compiled plan) per
        ``(cfg, resolved backend)``."""
        key = (cfg, get_backend(backend, cfg).name)
        solver = _CACHE.get(key)
        if solver is None:
            _CACHE_STATS["misses"] += 1
            solver = _CACHE[key] = cls(cfg, backend)
            while len(_CACHE) > _CACHE_MAX:
                _, evicted = _CACHE.popitem(last=False)
                _CACHE_STATS["evictions"] += 1
                evicted._release_executables()
        else:
            _CACHE_STATS["hits"] += 1
            _CACHE.move_to_end(key)
        return solver

    @classmethod
    def cache_clear(cls) -> None:
        for solver in _CACHE.values():
            solver._release_executables()
        _CACHE.clear()
        _CACHE_STATS.update(hits=0, misses=0, evictions=0)

    @classmethod
    def cache_size(cls) -> int:
        return len(_CACHE)

    def _release_executables(self) -> None:
        """Drop this solver's compiled XLA programs (ALL jitted entry
        points, health twins included). Called on LRU eviction and on
        ``cache_clear`` so an evicted solver cannot strand device
        memory behind jit's trace cache: an evicted instance stays
        *usable* by existing holders — the next call just re-traces.
        """
        for fn in (self._apply, self._apply_batched, self._apply_health,
                   self._apply_batched_health, self._refresh,
                   self._apply_plan, self._grid_steps):
            fn.clear_cache()

    def _compiled_program_count(self) -> int:
        """How many compiled programs this solver currently pins across
        its jitted entry points (the eviction-release regression gate)."""
        return sum(fn._cache_size() for fn in
                   (self._apply, self._apply_batched, self._apply_health,
                    self._apply_batched_health, self._refresh,
                    self._apply_plan, self._grid_steps))

    @classmethod
    def cache_info(cls) -> CacheInfo:
        """Hit/miss/eviction counters of the ``build`` plan cache (the
        ``functools.lru_cache`` idiom). Ragged production traffic that
        churns configs shows up here as eviction pressure."""
        return CacheInfo(hits=_CACHE_STATS["hits"],
                         misses=_CACHE_STATS["misses"],
                         maxsize=_CACHE_MAX, currsize=len(_CACHE),
                         evictions=_CACHE_STATS["evictions"])

    def _make_build(self, topo: dict):
        cfg = self.cfg

        def build(z: jax.Array, q: jax.Array) -> FmmPlan:
            self.trace_counts["build"] += 1
            return fmm_build(z, q, cfg, **topo)

        return build

    def _make_evaluate(self, impls: dict):
        cfg = self.cfg

        def evaluate(plan: FmmPlan) -> jax.Array:
            self.trace_counts["evaluate"] += 1
            return unsort(fmm_evaluate(plan, cfg, **impls), plan.tree.perm)

        return evaluate

    def _make_grid_steps(self):
        """``(steps, empty)`` of each staged Pallas grid that this
        solver's ``apply`` launches on a plan's lists (see
        ``kernels.common.staged_grid_steps``): the fused M2L, the fused
        evaluation's P2P and M2P regions, and P2L, as far as the backend
        runs those kernels."""
        cfg, be = self.cfg, self.backend

        def grid_steps(conn) -> dict:
            out = {}
            if be.m2l_fused is not None:
                out["m2l"] = m2l_grid_steps(conn.weak, cfg)
            if be.eval_fused is not None:
                regions = eval_grid_steps(conn, cfg)
                out.update(zip(("eval_p2p", "eval_m2p"), regions))
            if be.p2l is not None and cfg.use_p2l_m2p and cfg.nlevels > 0:
                out["p2l"] = p2l_grid_steps(conn, cfg)
            return out

        return grid_steps

    def _make_core(self, impls: dict, topo: dict, with_health: bool = False):
        cfg = self.cfg

        def core(z: jax.Array, q: jax.Array) -> jax.Array:
            plan = fmm_build(z, q, cfg, **topo)
            phi = unsort(fmm_evaluate(plan, cfg, **impls), plan.tree.perm)
            if with_health:
                return phi, health_of(plan, z, q, phi)
            return phi

        return core

    # -- evaluation ---------------------------------------------------------

    def apply(self, z: jax.Array, q: jax.Array) -> jax.Array:
        """phi_i = sum_{j != i} G(z_i, x_j) for one problem; input order.

        Trusts the caps (pure jit path): an input whose interaction
        lists exceed ``strong_cap``/``weak_cap`` silently drops
        interactions. Size the caps with ``tune`` on a representative
        sample, and use ``apply_checked``/``apply_guarded`` (or monitor
        ``stats``) when production inputs may drift from it.
        """
        with TraceAnnotation("fmm.apply"):
            with TraceAnnotation("fmm.validate"):
                self._validate(z, q, "apply")
            with TraceAnnotation("fmm.dispatch"):
                return self._apply(z, q)

    def apply_with_health(self, z: jax.Array, q: jax.Array):
        """``apply`` plus the in-graph health plane: ``(phi, Health)``
        from ONE compiled launch — overflow margins per interaction-list
        class and non-finite input/output flags ride alongside phi, so
        checking execution health costs one ``device_get``, not a second
        eager topology build. The guarded ladder (``repro.solver.guard``)
        builds on this entry point."""
        with TraceAnnotation("fmm.apply_with_health"):
            with TraceAnnotation("fmm.validate"):
                self._validate(z, q, "apply_with_health")
            with TraceAnnotation("fmm.dispatch"):
                return self._apply_health(z, q)

    def apply_checked(self, z: jax.Array, q: jax.Array) -> jax.Array:
        """``apply`` with execution-health validation on the same launch.

        Raises the typed errors of ``repro.errors`` instead of silently
        returning a wrong answer: ``CapOverflowError`` when interactions
        were dropped, ``NonFiniteInputError``/``NonFiniteOutputError``
        for NaN/Inf in, resp. out. Costs one ``device_get`` over
        ``apply`` — the health plane is computed in-graph."""
        phi, health = self.apply_with_health(z, q)
        raise_unhealthy(host_health(health), self.cfg, "apply_checked")
        return phi

    def apply_batched(self, z: jax.Array, q: jax.Array) -> jax.Array:
        """Evaluate B independent problems in one call.

        ``z``/``q``: (B, N) with the same ``FmmConfig`` (one shared cap
        budget). Returns (B, N) potentials, each row in its input order.

        Serves through the backend's own hooks — on the pallas backend
        the custom batching rules lower the vmap onto batch-major kernel
        grids, so B problems are still one launch per fused phase. Only
        a ``batched_dispatch="fallback"`` backend downgrades to the
        reference sweeps; the downgrade is recorded in
        ``self.dispatched["apply_batched"]`` and warned about once per
        solver.

        Like ``apply``, trusts the caps: an overflowing batch member
        silently drops interactions. ``apply_batched_checked`` adds the
        batch-wide overflow guard.
        """
        with TraceAnnotation("fmm.apply_batched"):
            with TraceAnnotation("fmm.validate"):
                self._validate_batched(z, q)
            self._warn_batched_fallback()
            with TraceAnnotation("fmm.dispatch"):
                return self._apply_batched(z, q)

    def apply_batched_with_health(self, z: jax.Array, q: jax.Array):
        """``apply_batched`` plus the per-row health plane:
        ``(phi (B, N), Health)`` with every health field carrying a
        leading B axis — one compiled launch, reduce with
        ``host_health``."""
        with TraceAnnotation("fmm.apply_batched_with_health"):
            with TraceAnnotation("fmm.validate"):
                self._validate_batched(z, q)
            self._warn_batched_fallback()
            with TraceAnnotation("fmm.dispatch"):
                return self._apply_batched_health(z, q)

    def apply_batched_checked(self, z: jax.Array, q: jax.Array) -> jax.Array:
        """``apply_batched`` with execution-health validation across the
        whole batch, on the same launch. Health is reduced over the B
        problems (overflow max, margins min, non-finite any), so a
        single unhealthy batch member raises the same typed error
        ``apply_checked`` gives one problem — instead of silently
        returning truncated potentials for that row."""
        phi, health = self.apply_batched_with_health(z, q)
        raise_unhealthy(host_health(health), self.cfg,
                        "apply_batched_checked")
        return phi

    def _warn_batched_fallback(self) -> None:
        if (self.dispatched["apply_batched"] != self.backend.name
                and not self._warned_batched_fallback):
            self._warned_batched_fallback = True
            warnings.warn(
                f"backend {self.backend.name!r} declares "
                "batched_dispatch='fallback': apply_batched dispatches "
                f"the {self.dispatched['apply_batched']!r} sweeps instead "
                "(same answer; do not attribute batched timings to "
                f"{self.backend.name!r})", BackendDowngradeWarning,
                stacklevel=3)

    # -- argument validation (typed errors, repro.errors) -------------------

    def _validate_dtypes(self, z, q, entry: str) -> None:
        zd = np.dtype(getattr(z, "dtype", np.asarray(z).dtype))
        qd = np.dtype(getattr(q, "dtype", np.asarray(q).dtype))
        want = np.dtype(self.cfg.complex_dtype)
        if not np.issubdtype(zd, np.complexfloating):
            raise DTypeError(
                f"{entry} wants complex positions z = x + iy; got real "
                f"{zd.name} — a real-valued position array is a "
                "complex-vs-real confusion (pass z.astype(complex))")
        if not np.issubdtype(qd, np.complexfloating):
            raise DTypeError(
                f"{entry} wants complex charges q (the potential is "
                f"complex); got {qd.name} — add 0j (q.astype(complex))")
        if zd.itemsize < want.itemsize or qd.itemsize < want.itemsize:
            raise DTypeError(
                f"{entry}: {zd.name}/{qd.name} input into a "
                f"dtype={self.cfg.dtype!r} config would silently lose the "
                f"configured precision; cast to {want.name} (or build an "
                "f32 config)")

    def _validate(self, z, q, entry: str) -> None:
        n = self.cfg.n
        zs, qs = getattr(z, "shape", ()), getattr(q, "shape", ())
        if zs != (n,) or qs != (n,):
            raise ShapeError(
                f"{entry} wants z and q of shape ({n},); got z{zs} q{qs}")
        self._validate_dtypes(z, q, entry)

    def _validate_batched(self, z: jax.Array, q: jax.Array) -> None:
        if getattr(z, "ndim", 0) != 2:
            raise ShapeError(
                f"apply_batched wants (B, N); got {getattr(z, 'shape', ())}")
        if z.shape[-1] != self.cfg.n:
            raise ShapeError(f"N={z.shape[-1]} != cfg.n={self.cfg.n}")
        if q.shape != z.shape:
            raise ShapeError(
                f"apply_batched wants q of shape {z.shape}; got {q.shape}")
        self._validate_dtypes(z, q, "apply_batched")

    def refresh(self, z: jax.Array, q: jax.Array) -> FmmPlan:
        """Rebuild tree + connectivity for moved particles — the cheap
        per-step topology update of a time-stepping workload.

        Compiled once per solver (same static caps/tiling as ``apply``):
        after the first call, refreshing perturbed positions costs one
        device-resident sort+connect launch sequence — no re-trace, no
        re-compile (``trace_counts["build"]`` pins this in tests).
        Feed the plan to ``apply_plan``; check ``plan.conn.overflow``
        (one scalar) to monitor cap drift as particles move.
        """
        with TraceAnnotation("fmm.refresh"):
            with TraceAnnotation("fmm.validate"):
                self._validate(z, q, "refresh")
            with TraceAnnotation("fmm.dispatch"):
                return self._refresh(z, q)

    def apply_plan(self, plan: FmmPlan) -> jax.Array:
        """Evaluate on a prebuilt plan (from ``refresh``); input order.

        ``refresh`` + ``apply_plan`` is ``apply`` split at the
        topology/evaluation seam, so a time-stepper can rebuild the plan
        every step, inspect it (overflow, stats) without extra builds,
        or evaluate one plan several times."""
        with TraceAnnotation("fmm.apply_plan"), \
                TraceAnnotation("fmm.dispatch"):
            return self._apply_plan(plan)

    def plan(self, z: jax.Array, q: jax.Array) -> FmmPlan:
        """Topological phase only (tree + connectivity) for inspection."""
        return self.refresh(z, q)   # shares refresh's shape validation

    def stats(self, z: jax.Array, q: jax.Array) -> dict:
        """Connectivity stats (incl. ``overflow`` and the margins) for one
        problem, and under ``"grid_steps"`` the grid of each staged
        Pallas kernel ``apply`` launches on it: ``{kernel: {"steps": n,
        "empty": k}}``, ``k`` of the ``n`` steps staging only the dummy
        row (cap and tile padding). One ``device_get`` for all of it."""
        conn = self.plan(z, q).conn
        conn, grid = jax.device_get((conn, self._grid_steps(conn)))
        stats = connectivity_stats(conn)
        stats["grid_steps"] = {k: {"steps": int(n), "empty": int(e)}
                               for k, (n, e) in grid.items()}
        return stats

    def guarded(self, **kwargs) -> "GuardedSolver":  # noqa: F821
        """Wrap this solver's config/backend in the guarded-execution
        recovery ladder (``repro.solver.guard.GuardedSolver``): detect
        via the in-graph health plane, recover by cap escalation /
        per-phase degradation / direct summation, never silently
        corrupt. Keyword args forward to ``GuardedSolver``."""
        from .guard import GuardedSolver  # local: guard imports solver
        return GuardedSolver(self.cfg, self.backend_name, **kwargs)

    # -- autotuning ---------------------------------------------------------

    def tune(self, z_sample: jax.Array, q_sample: jax.Array | None = None,
             *, margin: float = 1.25, round_to: int = 8,
             max_grow: int = 6, tiles: bool = True,
             tile_timer=None) -> "FmmSolver":
        """Fit ``strong_cap``/``weak_cap`` — and the Pallas kernel tiling
        (``tile_boxes``/``stage_width``) — to a workload sample.

        ``z_sample`` may be (N,) or (B, N) — a batch tunes the shared cap
        budget to its worst row. With ``tiles=True`` the tile knobs are
        tuned at the tuned caps (timing sweep on a compiling backend,
        lane heuristic otherwise; ``tile_timer`` injects a custom
        ``(z, q, cfg) -> seconds`` measurement). Returns the (cached)
        solver for the tuned config, with ``tune_result`` attached —
        ``tune_result.cfg`` carries the tile settings alongside the caps,
        ``tune_result.tile_trials`` the sweep.
        """
        result = tune_caps(z_sample, q_sample, self.cfg, margin=margin,
                           round_to=round_to, max_grow=max_grow)
        if tiles:
            tiled_cfg, tile_trials = tune_tiles(
                z_sample, q_sample, result.cfg,
                backend=self.backend_name, timer=tile_timer)
            result = result._replace(cfg=tiled_cfg,
                                     tile_trials=tuple(tile_trials))
        # Shallow copy: shares the cached compiled programs but carries
        # this caller's tune_result — concurrent tuners that land on the
        # same tuned config must not clobber each other's stats.
        tuned = copy.copy(FmmSolver.build(result.cfg, self.backend_name))
        result = result._replace(
            dispatched=tuple(sorted(tuned.dispatched.items())))
        tuned.tune_result = result
        return tuned
