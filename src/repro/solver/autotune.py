"""Workload autotuning: fit the padded-list budgets and kernel tiles.

The connectivity lists are padded to static caps (``strong_cap`` /
``weak_cap``) so every shape is compile-time constant — the paper's
central design point. The caps are therefore a *performance* parameter:
too small and interactions overflow (dropped -> wrong answer, caught by
``Connectivity.overflow``); too large and every sweep pays for dead
padding. Holm, Engblom, Goude & Holmgren (arXiv:1311.1006) make the case
that such parameters should be tuned per workload at runtime rather than
hard-coded; this module is that idea for the TPU port.

``tune_caps`` runs the cheap topological phase (sort + connect, ~31% of
one evaluation) a handful of times on a sample of the workload:

  1. *grow*: double ``strong_cap`` until nothing overflows;
  2. *shrink*: read the actual per-box occupancy maxima from the
     overflow-free build and re-pad to ``margin`` times that, rounded up
     to ``round_to`` (lane-friendly);
  3. *verify*: one final build confirms ``overflow == 0`` at the shrunk
     caps.

``tune_tiles`` picks the Pallas kernel tiling (``tile_boxes`` /
``stage_width``, DESIGN.md §2) for the tuned caps: a timing sweep of the
evaluation (upward, downward, evaluation — the phases the tiles drive)
on one plan built for the whole sweep, when the backend compiles (on
TPU); a lane-geometry heuristic otherwise (interpret-mode timings are
noise). Both tuners share one compiled tree build per (N, depth, dtype).

A 2-D sample ``(B, N)`` tunes a shared cap budget across all B problems
(the ``apply_batched`` serving shape): caps are sized to the worst row.
On a backend that serves batches through its own hooks (the batched-
dispatch contract, ``repro.solver.backends``) the tile sweep then times
the *batched* evaluation — the batch-major kernel grids are what
production serves, and the best tile can differ once B problems share
the launch — while a "fallback" backend times one row as before.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core import fmm as _fmm
from ..core.config import FmmConfig, max_leaf_size
from ..core.topology import build_tree, connectivity_stats
from ..kernels.common import default_interpret
from .backends import get_backend


class TuneResult(NamedTuple):
    """Outcome of a tuning run (caps, and optionally tiles)."""

    cfg: FmmConfig          # tuned config (overflow-free on the sample)
    stats: dict             # connectivity stats at the tuned caps
    trials: list            # [(strong_cap, weak_cap, overflow), ...]
    tile_trials: tuple = ()  # ((tile_boxes, stage_width, seconds|None), ...)
    dispatched: tuple = ()   # (("apply", backend), ("apply_batched", ...)):
    #                          what the tuned solver ACTUALLY runs per
    #                          entry point (see FmmSolver.dispatched)


def _round_up(x: int, m: int) -> int:
    return max(m, (x + m - 1) // m * m)


@functools.lru_cache(maxsize=8)
def _tree_builder(n: int, nlevels: int, dtype: str):
    """``build_tree`` compiled once per (n, nlevels, dtype): the tree does
    not depend on the caps or tiles a tuning run varies, so every trial
    shares one compiled sort (minutes of TPU compile at N ~ 1e6)."""
    cfg = FmmConfig(n=n, nlevels=nlevels, dtype=dtype)
    return jax.jit(lambda z, q: build_tree(z, q, cfg))


def _build_plan(z: jax.Array, q: jax.Array, cfg: FmmConfig) -> _fmm.FmmPlan:
    """``fmm_build`` for tuning: the shared compiled tree, then the
    (cap-dependent, cheap) connectivity run eagerly."""
    tree = _tree_builder(cfg.n, cfg.nlevels, cfg.dtype)(z, q)
    return _fmm.FmmPlan(tree=tree, conn=_fmm.build_connectivity(tree, cfg))


def probe_caps(z: jax.Array, q: jax.Array, cfg: FmmConfig) -> tuple[int, dict]:
    """Build tree+connectivity once; return (overflow, stats).

    ``z``/``q`` may be ``(N,)`` for one problem or ``(B, N)`` for a batch
    sharing one cap budget — stats then aggregate the worst row.
    """
    if z.ndim == 1:
        z, q = z[None], q[None]
    overflow, stats = 0, None
    for b in range(z.shape[0]):
        s = connectivity_stats(_build_plan(z[b], q[b], cfg).conn)
        overflow = max(overflow, s["overflow"])
        if stats is None:
            stats = s
        else:
            # worst row per counter; the per-class margins aggregate min
            # (fewest slots left across the batch)
            stats = {k: ({c: min(stats[k][c], s[k][c]) for c in stats[k]}
                         if isinstance(stats[k], dict)
                         else max(stats[k], s[k]))
                     for k in stats}
    return overflow, stats


def tune_caps(z: jax.Array, q: jax.Array | None, cfg: FmmConfig, *,
              margin: float = 1.25, round_to: int = 8,
              max_grow: int = 6) -> TuneResult:
    """Fit ``strong_cap``/``weak_cap`` to the sample; see module docstring.

    ``margin`` head-room (>= 1) absorbs drift between the tuning sample
    and production inputs; ``round_to`` keeps caps lane-friendly.
    """
    if margin < 1.0:
        raise ValueError("margin must be >= 1")
    z = jnp.asarray(z)
    q = jnp.ones(z.shape, cfg.complex_dtype) if q is None else jnp.asarray(q)

    trials: list = []
    cur = cfg
    for attempt in range(max_grow + 1):
        overflow, stats = probe_caps(z, q, cur)
        trials.append((cur.strong_cap, cur.weak_cap, overflow))
        if overflow == 0:
            break
        if attempt == max_grow:
            raise RuntimeError(
                f"connectivity still overflows by {overflow} at "
                f"strong_cap={cur.strong_cap} (after {max_grow} doublings); "
                "the sample distribution defeats the theta-criterion caps")
        cur = dataclasses.replace(cur, strong_cap=2 * cur.strong_cap,
                                  weak_cap=0)  # 0 -> 4*strong (post_init)

    strong = _round_up(int(stats["strong_max"] * margin), round_to)
    weak = _round_up(int(stats["weak_max"] * margin), round_to)
    tuned = dataclasses.replace(cur, strong_cap=strong, weak_cap=weak)

    overflow, stats = probe_caps(z, q, tuned)
    trials.append((tuned.strong_cap, tuned.weak_cap, overflow))
    if overflow != 0:  # cannot happen: caps >= measured maxima
        raise RuntimeError("tuned caps overflow; file a bug")
    return TuneResult(cfg=tuned, stats=stats, trials=trials)


# ---------------------------------------------------------------------------
# kernel-tile tuning (tile_boxes / stage_width, DESIGN.md §2)
# ---------------------------------------------------------------------------

# Budget for the fused evaluation kernel's VMEM working set. TPU cores
# carry ~16 MB of VMEM; half is left for Pallas double-buffer headroom
# and the compiler's own scratch.
EVAL_VMEM_BUDGET = 8 * 2**20


def eval_fused_vmem_bytes(cfg: FmmConfig, tile_boxes: int | None = None,
                          stage_width: int | None = None) -> int:
    """VMEM working-set estimate of the fused evaluation kernel.

    Per grid step the kernel holds resident: 5 (TB, n_pad) target planes
    (positions, ranks, pre-centered), 2 (TB, P) local blocks and the
    2 (TB, n_pad) revisited phi blocks; it streams TB*SW staged source
    rows of every plane family (5 particle + 2 multipole) plus 3 (TB, SW)
    slot planes, double-buffered by Pallas (x2). The (TB, n_t, n_s)
    pairwise P2P tile lives in vector registers and is excluded.

    The estimate is *batch-invariant*: the batch-major grid gives every
    (b, i, s) step the same per-step blocks — B problems only lengthen
    the grid (DESIGN.md §2) — so this budget (and the
    ``tile_candidates`` filter built on it) holds unchanged for
    ``apply_batched``.
    """
    TB = cfg.tile_boxes if tile_boxes is None else tile_boxes
    SW = cfg.stage_width if stage_width is None else stage_width
    n_pad = -(-max_leaf_size(cfg) // 128) * 128
    P = -(-(cfg.p + 1) // 128) * 128
    itemsize = 8 if cfg.dtype == "f64" else 4
    resident = TB * (7 * n_pad + 2 * P)
    staged = TB * SW * (5 * n_pad + 2 * P) + 3 * TB * SW
    return (resident + 2 * staged) * itemsize


#: ``tile_boxes`` the sweep offers: whole multiples of the 8-row f32
#: sublane tile. Mosaic refuses a (TB, width) target block with TB not a
#: multiple of 8 (unless it spans the whole box axis), so smaller tiles
#: compile only in interpret mode.
TILE_CANDIDATES = (8, 16)


#: Most source rows (``tile_boxes * stage_width``) one grid step may
#: stage per plane family. At 64 (16 x 4) the TPU v5e compile of the
#: N = 2**20 evaluation runs out of VMEM; 32 compiles.
MAX_STAGED_ROWS = 32


def tile_candidates(cfg: FmmConfig,
                    vmem_budget: int = EVAL_VMEM_BUDGET) -> list[int]:
    """``TILE_CANDIDATES`` up to the leaf-level box count (the smallest
    always stays), filtered to tiles whose fused-evaluation working set
    fits the VMEM budget (large-leaf configs cap the useful tile)."""
    cands = [t for t in TILE_CANDIDATES
             if t <= max(cfg.nboxes, TILE_CANDIDATES[0])]
    fit = [t for t in cands
           if eval_fused_vmem_bytes(cfg, tile_boxes=t) <= vmem_budget]
    return fit or cands[:1]


def heuristic_tiles(cfg: FmmConfig) -> FmmConfig:
    """Lane-geometry default when timing is unavailable: the smallest
    candidate tile (one 8-row sublane tile of boxes) fills the f32 vector
    registers; one staged slot keeps the working set minimal."""
    tb = tile_candidates(cfg)[0]
    return dataclasses.replace(cfg, tile_boxes=tb, stage_width=1)


def _evaluation_timer(backend: str, repeats: int,
                      batched: bool = False) -> Callable:
    """Time the jitted evaluation (upward, downward, evaluation) of one
    plan per config, in seconds. The tiles drive the evaluation-phase
    kernels; the plan (tree + connectivity, whose sort dominates compile
    time) is built once and shared by every candidate.

    With ``batched=True`` the sample is (B, N) and the measured program
    is ``jax.vmap`` of the evaluation — the batch-major kernel grids the
    serving entry point actually runs."""
    plans: dict = {}

    def plan_for(z, q, cfg: FmmConfig):
        key = (cfg.strong_cap, cfg.weak_cap)
        if key not in plans:
            if batched:
                rows = [_build_plan(z[b], q[b], cfg)
                        for b in range(z.shape[0])]
                plans[key] = jax.tree.map(lambda *a: jnp.stack(a), *rows)
            else:
                plans[key] = _build_plan(z, q, cfg)
        return plans[key]

    def timer(z, q, cfg: FmmConfig) -> float:
        impls = get_backend(backend, cfg).phase_impls(cfg)

        def one(plan):
            return _fmm.fmm_evaluate(plan, cfg, **impls)

        run = jax.jit(jax.vmap(one) if batched else one)
        plan = plan_for(z, q, cfg)
        jax.block_until_ready(run(plan))           # compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(run(plan))
            best = min(best, time.perf_counter() - t0)
        return best

    return timer


def tune_tiles(z: jax.Array, q: jax.Array | None, cfg: FmmConfig, *,
               backend: str = "auto", repeats: int = 3,
               timer: Optional[Callable] = None
               ) -> tuple[FmmConfig, list]:
    """Pick ``tile_boxes``/``stage_width`` for this workload.

    When the resolved backend compiles Pallas kernels (pallas on a real
    TPU) — or a ``timer(z, q, cfg) -> seconds`` is injected — each
    candidate is measured on the evaluation of one shared plan: first the
    ``tile_boxes`` sweep at ``stage_width=1``, then the stage-width sweep
    at the winning tile. Otherwise (reference backend, or interpret mode
    where timings are noise) a lane-geometry heuristic picks the tile.

    A (B, N) sample stays batched when the backend serves batches
    through its own hooks (``batched_dispatch`` != "fallback"): the
    timer then measures the vmapped evaluation — i.e. the batch-major
    kernel grids of ``apply_batched`` — so the tile is tuned for the
    shape production runs. On a "fallback" backend the sweep times one
    row, as the batched entry would not run these kernels anyway.

    Returns ``(tuned_cfg, trials)`` with trials
    ``[(tile_boxes, stage_width, seconds|None), ...]``.
    """
    be = get_backend(backend, cfg)
    measurable = timer is not None or (be.name == "pallas"
                                       and not default_interpret())
    if not measurable:
        tuned = heuristic_tiles(cfg)
        return tuned, [(tuned.tile_boxes, tuned.stage_width, None)]

    z = jnp.asarray(z)
    batched = z.ndim == 2 and be.batched_dispatch != "fallback"
    if z.ndim == 2 and not batched:       # fallback backend: time one row
        z = z[0]
        q = None if q is None else jnp.asarray(q)[0]
    q = jnp.ones(z.shape, cfg.complex_dtype) if q is None else jnp.asarray(q)
    timer = timer or _evaluation_timer(be.name, repeats, batched=batched)

    trials: list = []

    def measure(tb: int, sw: int) -> float:
        c = dataclasses.replace(cfg, tile_boxes=tb, stage_width=sw)
        t = float(timer(z, q, c))
        trials.append((tb, sw, t))
        return t

    best_tb = min(tile_candidates(cfg), key=lambda tb: measure(tb, 1))
    # sw=1 was already measured in the tile sweep; reuse that time
    sw_times = {1: min(t for tb, sw, t in trials
                       if tb == best_tb and sw == 1)}
    for sw in (2, 4):
        # staged slots multiply the streamed rows: respect both the
        # staged-row bound and the fused-eval VMEM budget
        if (best_tb * sw <= MAX_STAGED_ROWS
                and eval_fused_vmem_bytes(cfg, best_tb, sw)
                <= EVAL_VMEM_BUDGET):
            sw_times[sw] = measure(best_tb, sw)
    best_sw = min(sw_times, key=sw_times.get)
    return (dataclasses.replace(cfg, tile_boxes=best_tb,
                                stage_width=best_sw), trials)
