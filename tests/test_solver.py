"""FmmSolver front-end: plan caching, backend dispatch, batched
evaluation vs a per-problem loop, and cap autotuning."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import FmmConfig, fmm_potential
from repro.data.synthetic import particles
from repro.solver import (FmmSolver, available_backends, get_backend,
                          probe_caps, tune_caps)

CFG64 = FmmConfig(n=256, nlevels=2, p=10, dtype="f64")


def _batch(b, n, dist="uniform", seed0=0):
    zs, qs = [], []
    for i in range(b):
        z, q = particles(dist, n, seed0 + i)
        zs.append(np.asarray(z))
        qs.append(np.asarray(q))
    return jnp.asarray(np.stack(zs)), jnp.asarray(np.stack(qs))


# ---------------------------------------------------------------------------
# single-problem apply + plan cache
# ---------------------------------------------------------------------------

def test_apply_matches_fmm_potential():
    z, q = particles("normal", CFG64.n, 3)
    z, q = jnp.asarray(z), jnp.asarray(q)
    solver = FmmSolver.build(CFG64, "reference")
    np.testing.assert_allclose(np.asarray(solver.apply(z, q)),
                               np.asarray(fmm_potential(z, q, CFG64)),
                               rtol=1e-12, atol=1e-12)


def test_build_is_cached_per_config_and_backend():
    a = FmmSolver.build(CFG64, "reference")
    assert FmmSolver.build(CFG64, "reference") is a
    # "auto" shares the cache entry of whatever backend it resolves to
    # (reference on CPU: interpret-mode pallas is not a fast path)
    resolved = get_backend("auto", CFG64).name
    assert (FmmSolver.build(CFG64, "auto") is a) == (resolved == "reference")
    import dataclasses
    other = dataclasses.replace(CFG64, p=CFG64.p + 1)
    assert FmmSolver.build(other, "reference") is not a


def test_apply_checked_raises_on_overflow():
    import dataclasses
    tiny = dataclasses.replace(CFG64, strong_cap=2, weak_cap=2)
    z, q = particles("normal", CFG64.n, 5)
    z, q = jnp.asarray(z), jnp.asarray(q)
    solver = FmmSolver(tiny, "reference")
    with pytest.raises(RuntimeError, match="overflow"):
        solver.apply_checked(z, q)
    # ...while on an in-cap input it returns the plain-apply answer
    ok = FmmSolver.build(CFG64, "reference")
    np.testing.assert_array_equal(np.asarray(ok.apply_checked(z, q)),
                                  np.asarray(ok.apply(z, q)))


def test_unknown_backend_raises():
    with pytest.raises(KeyError):
        FmmSolver.build(CFG64, "cuda")
    assert set(available_backends()) >= {"reference", "pallas", "auto"}


def test_pallas_backend_supports_log_kernel(monkeypatch):
    cfg = FmmConfig(n=64, nlevels=1, p=6, kernel="log", dtype="f32")
    assert get_backend("pallas", cfg).supports(cfg)
    # "auto" must dispatch log-kernel configs somewhere that supports them
    assert get_backend("auto", cfg).supports(cfg)
    # ...and on a TPU platform it picks pallas (no silent reference
    # fallback for log configs)
    from repro.solver import backends
    monkeypatch.setattr(backends, "_platform", lambda: "tpu")
    assert get_backend("auto", cfg).name == "pallas"
    monkeypatch.setattr(backends, "_platform", lambda: "cpu")
    assert get_backend("auto", cfg).name == "reference"


@pytest.mark.parametrize("backend", ["auto", "pallas"])
def test_f64_config_on_tpu_raises_dtype_error_at_build(monkeypatch, backend):
    """f64 has no kernel path on the chip: build refuses it with the typed
    error naming the dtype (no compiler trace, no silent reference)."""
    from repro.errors import DTypeError
    from repro.solver import backends
    monkeypatch.setattr(backends, "_platform", lambda: "tpu")
    cfg = FmmConfig(n=64, nlevels=1, p=6, dtype="f64")
    with pytest.raises(DTypeError, match="f64"):
        FmmSolver.build(cfg, backend)
    f32 = FmmConfig(n=64, nlevels=1, p=6, dtype="f32")
    assert get_backend(backend, f32).name == "pallas"


# ---------------------------------------------------------------------------
# batched evaluation
# ---------------------------------------------------------------------------

def test_apply_batched_matches_per_problem_loop():
    B = 8
    solver = FmmSolver.build(CFG64, "reference")
    zb, qb = _batch(B, CFG64.n)
    got = np.asarray(solver.apply_batched(zb, qb))
    ref = np.stack([np.asarray(solver.apply(zb[i], qb[i]))
                    for i in range(B)])
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() / scale < 1e-6
    # and each row is a genuinely different problem
    assert np.abs(got[0] - got[1]).max() / scale > 1e-3


def test_apply_batched_shape_validation():
    solver = FmmSolver.build(CFG64, "reference")
    z, q = _batch(2, CFG64.n)
    with pytest.raises(ValueError):
        solver.apply_batched(z[0], q[0])
    with pytest.raises(ValueError):
        solver.apply_batched(z[:, :100], q[:, :100])


def test_apply_batched_pallas_backend_dispatches_natively():
    """The pallas kernels are batch-native (custom batching rules lower
    jax.vmap onto batch-major grids): the batched entry serves through
    the pallas hooks — no downgrade, no warning — and agrees with the
    reference batched answer."""
    import warnings as W
    cfg = FmmConfig(n=256, nlevels=2, p=8, dtype="f32",
                    strong_cap=40, weak_cap=64)
    zb, qb = _batch(2, cfg.n, dist="normal")
    solver = FmmSolver.build(cfg, "pallas")
    assert solver.dispatched["apply_batched"] == "pallas"
    with W.catch_warnings():
        W.simplefilter("error")
        got = np.asarray(solver.apply_batched(zb, qb))
    ref = np.asarray(FmmSolver.build(cfg, "reference").apply_batched(zb, qb))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_dispatched_backend_is_recorded_and_fallback_warns_once():
    """The solver records what each entry point actually runs — a
    batched_dispatch="fallback" backend downgrades the batched entry to
    the reference sweeps — and warns exactly once per solver about the
    downgrade. The pallas backend is batch-native and never downgrades."""
    import warnings as W
    from repro.solver.backends import (Backend, _REGISTRY, get_backend,
                                       register_backend)
    cfg = FmmConfig(n=128, nlevels=1, p=6, dtype="f64",
                    strong_cap=40, weak_cap=64)
    pallas = get_backend("pallas", cfg)
    assert pallas.batched_dispatch == "native"
    assert FmmSolver(cfg, "pallas").dispatched == {
        "apply": "pallas", "apply_batched": "pallas"}
    # a third-party backend without batching rules declares "fallback"
    register_backend(Backend(name="unbatchable",
                             batched_dispatch="fallback"))
    try:
        solver = FmmSolver(cfg, "unbatchable")
        assert solver.dispatched == {"apply": "unbatchable",
                                     "apply_batched": "reference"}
        zb, qb = _batch(2, cfg.n)
        with pytest.warns(RuntimeWarning, match="apply_batched dispatches"):
            solver.apply_batched(zb, qb)
        with W.catch_warnings():        # one-time: silent on repeat
            W.simplefilter("error")
            solver.apply_batched(zb, qb)
    finally:
        _REGISTRY.pop("unbatchable", None)
    ref = FmmSolver(cfg, "reference")
    assert ref.dispatched == {"apply": "reference",
                              "apply_batched": "reference"}


def test_backend_rejects_unknown_batched_dispatch():
    from repro.solver.backends import Backend
    with pytest.raises(ValueError, match="batched_dispatch"):
        Backend(name="bogus", batched_dispatch="maybe")


def test_tune_result_records_dispatched_backends():
    solver = FmmSolver.build(CFG64, "reference")
    z, q = particles("normal", CFG64.n, 5)
    tuned = solver.tune(jnp.asarray(z), jnp.asarray(q), tiles=False)
    assert dict(tuned.tune_result.dispatched) == {
        "apply": "reference", "apply_batched": "reference"}


# ---------------------------------------------------------------------------
# backend agreement: pallas (interpret) vs reference
# ---------------------------------------------------------------------------

def test_pallas_and_reference_backends_agree():
    cfg = FmmConfig(n=512, nlevels=2, p=8, dtype="f32",
                    strong_cap=40, weak_cap=64)
    z, q = particles("normal", cfg.n, 11)
    z, q = jnp.asarray(z), jnp.asarray(q)
    ref = np.asarray(FmmSolver.build(cfg, "reference").apply(z, q))
    got = np.asarray(FmmSolver.build(cfg, "pallas").apply(z, q))
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() / scale < 5e-4  # f32 kernel tolerance


# ---------------------------------------------------------------------------
# cap autotuning
# ---------------------------------------------------------------------------

def test_tune_returns_overflow_free_shrunk_caps():
    solver = FmmSolver.build(CFG64, "reference")
    zb, qb = _batch(4, CFG64.n)
    tuned = solver.tune(zb, qb)
    res = tuned.tune_result
    assert res.stats["overflow"] == 0
    assert res.trials[-1][2] == 0
    # generous seed caps (48/192) shrink to the workload
    assert tuned.cfg.strong_cap <= CFG64.strong_cap
    assert tuned.cfg.weak_cap <= CFG64.weak_cap
    assert tuned.cfg.strong_cap >= res.stats["strong_max"]
    assert tuned.cfg.weak_cap >= res.stats["weak_max"]
    # tuned solver computes the same answer
    np.testing.assert_allclose(np.asarray(tuned.apply(zb[0], qb[0])),
                               np.asarray(solver.apply(zb[0], qb[0])),
                               rtol=1e-10, atol=1e-10)


def test_tune_grows_undersized_caps():
    import dataclasses
    tiny = dataclasses.replace(CFG64, strong_cap=2, weak_cap=2)
    z, q = particles("normal", CFG64.n, 5)
    z, q = jnp.asarray(z), jnp.asarray(q)
    assert probe_caps(z, q, tiny)[0] > 0  # genuinely undersized
    res = tune_caps(z, q, tiny)
    assert res.stats["overflow"] == 0
    assert res.cfg.strong_cap > tiny.strong_cap
    # growth trials were recorded before the overflow-free shrink
    assert any(t[2] > 0 for t in res.trials)


def test_tune_unsorts_margin_validation():
    with pytest.raises(ValueError):
        tune_caps(jnp.zeros(4), None, CFG64, margin=0.5)


# ---------------------------------------------------------------------------
# tile autotuning (tile_boxes / stage_width)
# ---------------------------------------------------------------------------

def test_tune_returns_tile_settings_alongside_caps():
    """Off-TPU (no meaningful timings) the lane heuristic picks the tile;
    the result still carries tile settings next to the caps."""
    solver = FmmSolver.build(CFG64, "reference")
    z, q = particles("normal", CFG64.n, 5)
    tuned = solver.tune(jnp.asarray(z), jnp.asarray(q))
    res = tuned.tune_result
    assert res.tile_trials, "tune() must report tile trials"
    assert tuned.cfg.tile_boxes == res.tile_trials[-1][0]
    assert 1 <= tuned.cfg.tile_boxes <= CFG64.nboxes
    assert tuned.cfg.stage_width >= 1
    # tiles can be switched off
    res_off = solver.tune(jnp.asarray(z), jnp.asarray(q),
                          tiles=False).tune_result
    assert res_off.tile_trials == ()


def test_tune_tiles_timing_sweep_picks_fastest():
    """With an injected timer (the TPU measurement path), tune() sweeps
    tile_boxes then stage_width and picks the fastest combination."""
    measured = []

    def timer(z, q, cfg):
        measured.append((cfg.tile_boxes, cfg.stage_width))
        # fastest at tile_boxes=16, stage_width=2
        return (abs(cfg.tile_boxes - 16) + 1) * (1.5 - 0.5 *
                                                 (cfg.stage_width == 2))

    solver = FmmSolver.build(CFG64, "reference")
    z, q = particles("normal", CFG64.n, 5)
    tuned = solver.tune(jnp.asarray(z), jnp.asarray(q), tile_timer=timer)
    assert tuned.cfg.tile_boxes == 16
    assert tuned.cfg.stage_width == 2
    assert len(tuned.tune_result.tile_trials) == len(measured)
    # the tile sweep ran at stage_width=1 over the sublane-multiple
    # candidates Mosaic compiles
    assert {t for t, s in measured if s == 1} == {8, 16}


def test_tune_tiles_batched_sample_times_batched_path():
    """A (B, N) sample keeps its batch axis through the tile-timing
    sweep on a backend that serves batches through its own hooks
    (batched_dispatch != "fallback"): the measured program is the
    vmapped batch-major pipeline, i.e. what apply_batched runs."""
    shapes = []

    def timer(z, q, cfg):
        shapes.append(z.shape)
        return float(cfg.tile_boxes)

    solver = FmmSolver.build(CFG64, "reference")
    zb, qb = _batch(3, CFG64.n)
    solver.tune(zb, qb, tile_timer=timer)
    assert shapes and all(s == (3, CFG64.n) for s in shapes)


@pytest.mark.parametrize("batched", [False, True])
def test_default_tile_timer_times_evaluation_on_one_shared_plan(batched):
    """The compiling-backend timer (exercised here on the reference
    backend) returns seconds per candidate and builds the plan once for
    the whole sweep: the tree is compiled once, not per tile."""
    from repro.solver import autotune
    timer = autotune._evaluation_timer("reference", repeats=1,
                                       batched=batched)
    if batched:
        z, q = _batch(2, CFG64.n)
    else:
        z, q = (jnp.asarray(a) for a in particles("normal", CFG64.n, 5))
    builds = []
    real = autotune._build_plan
    try:
        autotune._build_plan = lambda *a: builds.append(1) or real(*a)
        times = [timer(z, q, dataclasses.replace(CFG64, tile_boxes=tb))
                 for tb in (8, 16)]
    finally:
        autotune._build_plan = real
    assert all(t > 0 for t in times)
    assert len(builds) == (2 if batched else 1)


def test_tile_candidates_respect_fused_eval_vmem_budget():
    """Large-leaf configs must cap tile_boxes: the fused evaluation
    kernel's VMEM working set scales with tile_boxes * n_pad."""
    from repro.solver.autotune import eval_fused_vmem_bytes, tile_candidates
    big_leaves = FmmConfig(n=1 << 15, nlevels=2, p=10, dtype="f32")
    tight = 2 << 20
    cands = tile_candidates(big_leaves, vmem_budget=tight)
    assert cands and max(cands) < 16
    assert all(eval_fused_vmem_bytes(big_leaves, tile_boxes=t) <= tight
               for t in cands)
    # the default budget always leaves at least one candidate
    assert tile_candidates(big_leaves)
    # small-leaf configs keep the full sublane-multiple sweep
    assert tile_candidates(CFG64) == [8, 16]


def test_solver_stats_reports_overflow_scalar():
    z, q = particles("uniform", CFG64.n, 1)
    stats = FmmSolver.build(CFG64, "reference").stats(jnp.asarray(z),
                                                      jnp.asarray(q))
    assert stats["overflow"] == 0
    assert stats["p2p_pairs"] > 0


# ---------------------------------------------------------------------------
# plan refresh (time-stepping workloads)
# ---------------------------------------------------------------------------

def _perturbed(z, seed, eps=1e-4):
    rng = np.random.default_rng(seed)
    zd = np.asarray(z) + eps * (rng.normal(size=z.shape)
                                + 1j * rng.normal(size=z.shape))
    # clamp per component: complex np.clip compares lexicographically
    return jnp.asarray(np.clip(zd.real, 0, 1) + 1j * np.clip(zd.imag, 0, 1))


def test_refresh_plus_apply_plan_matches_apply():
    z, q = particles("normal", CFG64.n, 7)
    z, q = jnp.asarray(z), jnp.asarray(q)
    solver = FmmSolver.build(CFG64, "reference")
    plan = solver.refresh(z, q)
    np.testing.assert_allclose(np.asarray(solver.apply_plan(plan)),
                               np.asarray(solver.apply(z, q)),
                               rtol=1e-12, atol=1e-12)


def test_refresh_does_not_retrace_on_perturbed_positions():
    """The time-stepping contract: after the first step, refreshing moved
    particles reuses the compiled build/evaluate programs (trace-count
    asserted; a re-trace would pay compilation per step)."""
    z, q = particles("uniform", CFG64.n, 8)
    z, q = jnp.asarray(z), jnp.asarray(q)
    solver = FmmSolver(CFG64, "reference")   # fresh instance: clean counters
    for step in range(3):
        plan = solver.refresh(_perturbed(z, step), q)
        phi = solver.apply_plan(plan)
        assert phi.shape == (CFG64.n,)
        assert int(plan.conn.overflow) == 0
    assert solver.trace_counts == {"build": 1, "evaluate": 1}


def test_refresh_validates_shape():
    solver = FmmSolver.build(CFG64, "reference")
    z, q = particles("uniform", CFG64.n, 9)
    with pytest.raises(ValueError, match="refresh"):
        solver.refresh(jnp.asarray(z)[: CFG64.n // 2],
                       jnp.asarray(q)[: CFG64.n // 2])


def test_refresh_overflow_scalar_monitors_cap_drift():
    """plan.conn.overflow is the cheap per-step cap monitor: a config
    whose caps are too small for the refreshed layout must flag it."""
    z, q = particles("normal", 256, 10)
    tight = dataclasses_replace_caps(CFG64, strong_cap=2)
    solver = FmmSolver.build(tight, "reference")
    plan = solver.refresh(jnp.asarray(z), jnp.asarray(q))
    assert int(plan.conn.overflow) > 0


def dataclasses_replace_caps(cfg, **kw):
    import dataclasses
    kw.setdefault("weak_cap", 0)
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# argument validation: typed errors on the unbatched entry points
# ---------------------------------------------------------------------------

def test_apply_rejects_real_positions_with_typed_error():
    from repro.errors import DTypeError, ValidationError
    solver = FmmSolver.build(CFG64, "reference")
    z, q = particles("uniform", CFG64.n, 2)
    with pytest.raises(DTypeError, match="complex-vs-real"):
        solver.apply(jnp.real(jnp.asarray(z)), jnp.asarray(q))
    with pytest.raises(DTypeError, match="complex"):
        solver.apply(jnp.asarray(z), jnp.real(jnp.asarray(q)))
    # the taxonomy keeps legacy except-clauses working
    assert issubclass(DTypeError, (TypeError, ValidationError, ValueError))


def test_apply_rejects_narrower_dtype_than_config():
    from repro.errors import DTypeError
    solver = FmmSolver.build(CFG64, "reference")   # f64 config
    z, q = particles("uniform", CFG64.n, 2)
    z32 = jnp.asarray(np.asarray(z), jnp.complex64)
    q32 = jnp.asarray(np.asarray(q), jnp.complex64)
    with pytest.raises(DTypeError, match="precision"):
        solver.apply(z32, q32)
    # ...but higher-precision input into an f32 config is fine (it is
    # what the x64-enabled test suite does everywhere)
    f32 = FmmConfig(n=256, nlevels=2, p=6, dtype="f32")
    assert FmmSolver.build(f32, "reference").apply(
        jnp.asarray(z), jnp.asarray(q)).shape == (f32.n,)


def test_apply_and_refresh_reject_mismatched_lengths():
    from repro.errors import ShapeError
    solver = FmmSolver.build(CFG64, "reference")
    z, q = particles("uniform", CFG64.n, 2)
    with pytest.raises(ShapeError, match="apply wants"):
        solver.apply(jnp.asarray(z), jnp.asarray(q)[:-3])
    with pytest.raises(ShapeError, match="refresh wants"):
        solver.refresh(jnp.asarray(z)[None], jnp.asarray(q)[None])


# ---------------------------------------------------------------------------
# bounded plan cache: LRU eviction + observability
# ---------------------------------------------------------------------------

def test_cache_info_counts_hits_misses_and_evictions(monkeypatch):
    import dataclasses
    from repro.solver import solver as solver_mod
    FmmSolver.cache_clear()
    monkeypatch.setattr(solver_mod, "_CACHE_MAX", 2)
    cfgs = [dataclasses.replace(CFG64, p=p) for p in (3, 4, 5)]
    a = FmmSolver.build(cfgs[0], "reference")
    assert FmmSolver.build(cfgs[0], "reference") is a          # hit
    FmmSolver.build(cfgs[1], "reference")
    FmmSolver.build(cfgs[2], "reference")                      # evicts a
    info = FmmSolver.cache_info()
    assert info.hits == 1 and info.misses == 3
    assert info.evictions == 1 and info.currsize == 2 == info.maxsize
    # the evicted solver re-builds as a fresh instance (old one stays
    # usable by existing holders)
    assert FmmSolver.build(cfgs[0], "reference") is not a
    assert FmmSolver.cache_info().misses == 4
    FmmSolver.cache_clear()
    zeroed = FmmSolver.cache_info()
    assert (zeroed.hits, zeroed.misses, zeroed.evictions,
            zeroed.currsize) == (0, 0, 0, 0)


def test_eviction_releases_compiled_programs(monkeypatch):
    """Regression: LRU eviction under _CACHE_MAX pressure must release
    the evicted solver's compiled programs — health twins included —
    instead of stranding them behind jit's trace cache; and
    cache_clear() must reset them too."""
    import dataclasses
    from repro.solver import solver as solver_mod
    FmmSolver.cache_clear()
    monkeypatch.setattr(solver_mod, "_CACHE_MAX", 1)

    cfg_a = dataclasses.replace(CFG64, p=3)
    cfg_b = dataclasses.replace(CFG64, p=4)
    z, q = particles("uniform", CFG64.n, 1)
    z, q = jnp.asarray(z), jnp.asarray(q)

    a = FmmSolver.build(cfg_a, "reference")
    a.apply(z, q)                      # plain program
    a.apply_with_health(z, q)          # health twin
    assert a._compiled_program_count() >= 2

    FmmSolver.build(cfg_b, "reference")    # evicts a
    assert FmmSolver.cache_info().evictions == 1
    assert a._compiled_program_count() == 0, \
        "eviction stranded compiled programs (health twin leak)"

    # the evicted instance stays usable — the next call re-traces
    np.testing.assert_allclose(np.asarray(a.apply(z, q)),
                               np.asarray(fmm_potential(z, q, cfg_a)),
                               rtol=1e-12, atol=1e-12)
    assert a._compiled_program_count() == 1

    # cache_clear releases programs of everything still cached
    b = FmmSolver.build(cfg_b, "reference")
    b.apply(z, q)
    assert b._compiled_program_count() >= 1
    FmmSolver.cache_clear()
    assert b._compiled_program_count() == 0
    assert a._compiled_program_count() == 1    # uncached holder untouched
