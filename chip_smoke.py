"""Smoke run of the adaptive FMM on one TPU chip, at paper scale.

    python chip_smoke.py

Drives the main path through the entry points a user calls, in one
process, on one chip:

  1. ``FmmSolver.build(cfg, "auto").tune(z, q).apply(z, q)`` at
     N = 2**20, p = 17, f32, harmonic kernel, for uniform and clustered
     (normal) particles;
  2. ``apply_batched`` on B = 8 problems of N = 3584 (near the
     FMM/direct break-even);
  3. one wave of ragged requests through a ``ServePlane``;
  4. the logarithmic kernel on 2**18 clustered charges: the Pallas
     backend against the reference backend on complex phi, which sees
     the kernels' ``atan2`` and the log's branches, and Re phi against
     the direct sum.

Every phase checks its answer against an independent O(N**2) sum in f64
numpy on the host, and that it ran the Pallas kernels (no silent
fallback). Earlier lines print one JSON record per phase: host
wall-clock seconds (compile and steady), the tuned caps and tiles, the
error against the direct sum and the device's peak memory. They are
smoke readings, not benchmark metrics. The last line is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

and it is printed only when every phase passed. Without a TPU the
script exits non-zero before any phase runs.
"""
from __future__ import annotations

import collections
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: The f32 accuracy bound of the FMM at p = 17 against the direct sum
#: (tests/test_fmm_accuracy.py, ``test_f32_reaches_single_precision_floor``).
F32_TOL = 5e-4
#: Pallas against the reference backend on complex phi, logarithmic
#: kernel, as a share of max |phi| (tests/test_log_kernel.py): both run
#: the same expansions with the same float32 ``log`` and take its branch
#: at the same points, so they differ by rounding; a branch taken the
#: other way moves Im phi by 2 pi |q| of one charge.
LOG_AGREE = 1e-5


def _say(record: dict) -> None:
    print(json.dumps(record), flush=True)


def host_problem(dist: str, n: int, seed: int, dtype=np.complex64):
    """Positions and charges on the host, already in the config's dtype."""
    from repro.data import particles

    z, q = particles(dist, n, seed=seed)
    return np.asarray(z, dtype), np.asarray(q, dtype)


def direct_errors(phi, z, q, targets) -> dict:
    """Error of ``phi[targets]`` against the f64 host direct sum over all
    sources: the repo's pointwise ``rel_error_inf`` (paper eq. 5.3) and
    the normwise ||err||_inf / ||ref||_inf."""
    from repro.core.direct import direct_potential_numpy, rel_error_inf

    ref = direct_potential_numpy(z[targets], z, q)
    got = np.asarray(phi, np.complex128)[targets]
    return {"pointwise": rel_error_inf(got, ref),
            "normwise": float(np.abs(got - ref).max() / np.abs(ref).max())}


def _check_err(err: dict, where: str) -> None:
    if not err["pointwise"] <= F32_TOL:
        raise AssertionError(
            f"{where}: error {err['pointwise']:.3e} against the direct sum "
            f"exceeds {F32_TOL:g}")


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def phase_main(dist: str, n: int, *, seed: int = 0, samples: int = 512,
               backend: str = "auto") -> dict:
    """tune -> apply -> apply at one problem size; checked against direct."""
    import jax.numpy as jnp

    from repro.configs.fmm2d import fmm_config
    from repro.kernels.common import default_interpret
    from repro.solver import FmmSolver

    cfg = fmm_config(n)
    zh, qh = host_problem(dist, n, seed, cfg.complex_dtype)
    z, q = jnp.asarray(zh), jnp.asarray(qh)

    t0 = time.perf_counter()
    solver = FmmSolver.build(cfg, backend).tune(z, q)
    t_tune = time.perf_counter() - t0
    if solver.dispatched["apply"] != "pallas":
        raise AssertionError(f"apply dispatched {solver.dispatched}")
    trials = solver.tune_result.tile_trials
    timed = bool(trials) and all(t[2] is not None for t in trials)
    if not default_interpret() and not timed:
        raise AssertionError(f"tile sweep did not time kernels: {trials}")

    phi, t_first = _timed(lambda: solver.apply(z, q))
    phi, t_steady = _timed(lambda: solver.apply(z, q))
    targets = np.random.default_rng(seed + 1).choice(
        n, min(samples, n), replace=False)
    err = direct_errors(phi, zh, qh, targets)
    record = {
        "phase": f"main/{dist}", "n": n, "p": cfg.p, "dtype": cfg.dtype,
        "nlevels": cfg.nlevels, "dispatched": solver.dispatched,
        "caps": [solver.cfg.strong_cap, solver.cfg.weak_cap],
        "tiles": [solver.cfg.tile_boxes, solver.cfg.stage_width],
        "tile_trials_host_s": trials, "host_s_tune": t_tune,
        "host_s_first_apply": t_first, "host_s_steady_apply": t_steady,
        "err_vs_direct": err, "direct_targets": len(targets),
        "peak_bytes_in_use": _peak_bytes()}
    _say(record)
    _check_err(err, record["phase"])
    return record


def phase_log(n: int, *, seed: int = 200, samples: int = 256,
              backend: str = "auto") -> dict:
    """The logarithmic kernel on normal (sigma 0.1) positions and real
    charges: caps tuned to the problem, then ``apply`` on ``backend`` and
    on the reference backend at the same configuration. Complex phi must
    agree to ``LOG_AGREE`` of its largest value; Re phi (Im phi is
    defined up to multiples of 2 pi q) is checked against the direct sum
    at ``samples`` targets."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs.fmm2d import fmm_config
    from repro.core.direct import direct_potential_numpy
    from repro.solver import FmmSolver

    cfg = dataclasses.replace(fmm_config(n), kernel="log")
    zh, qh = host_problem("normal", n, seed, cfg.complex_dtype)
    qh = qh.real.astype(qh.dtype)
    z, q = jnp.asarray(zh), jnp.asarray(qh)
    solver = FmmSolver.build(cfg, backend).tune(z, q, tiles=False)
    if solver.dispatched["apply"] != "pallas":
        raise AssertionError(f"apply dispatched {solver.dispatched}")
    phi, t_steady = _timed(lambda: solver.apply(z, q))
    phi = np.asarray(phi, np.complex128)
    ref = np.asarray(FmmSolver.build(solver.cfg, "reference").apply(z, q),
                     np.complex128)
    diff = np.abs(phi - ref)
    agree = {"normwise": float(diff.max() / np.abs(ref).max()),
             "real": float(np.abs(phi.real - ref.real).max()),
             "imag": float(np.abs(phi.imag - ref.imag).max())}
    targets = np.random.default_rng(seed + 1).choice(
        n, min(samples, n), replace=False)
    direct = direct_potential_numpy(zh[targets], zh, qh, kernel="log").real
    err_re = float(np.abs(phi.real[targets] - direct).max()
                   / np.abs(direct).max())
    record = {
        "phase": "log/normal", "n": n, "p": cfg.p, "dtype": cfg.dtype,
        "caps": [solver.cfg.strong_cap, solver.cfg.weak_cap],
        "host_s_apply": t_steady, "pallas_vs_reference": agree,
        "re_err_vs_direct": err_re, "direct_targets": len(targets),
        "peak_bytes_in_use": _peak_bytes()}
    _say(record)
    if not agree["normwise"] < LOG_AGREE:
        raise AssertionError(f"log: pallas and reference differ by "
                             f"{agree['normwise']:.3e} of max |phi|")
    if not err_re <= F32_TOL:
        raise AssertionError(f"log: Re phi error {err_re:.3e} against the "
                             f"direct sum exceeds {F32_TOL:g}")
    return record


def phase_batched(n: int, b: int, *, seed: int = 100,
                  backend: str = "auto") -> dict:
    """``apply_batched`` on B problems of size N; every row checked
    against its own direct sum."""
    import jax.numpy as jnp

    from repro.configs.fmm2d import fmm_config
    from repro.solver import FmmSolver

    cfg = fmm_config(n)
    rows = [host_problem("normal" if i % 2 else "uniform", n, seed + i,
                         cfg.complex_dtype) for i in range(b)]
    zh = np.stack([r[0] for r in rows])
    qh = np.stack([r[1] for r in rows])
    zb, qb = jnp.asarray(zh), jnp.asarray(qh)

    t0 = time.perf_counter()
    solver = FmmSolver.build(cfg, backend).tune(zb, qb, tiles=False)
    t_tune = time.perf_counter() - t0
    if solver.dispatched["apply_batched"] != "pallas":
        raise AssertionError(f"apply_batched dispatched {solver.dispatched}")
    phi, t_first = _timed(lambda: solver.apply_batched(zb, qb))
    phi, t_steady = _timed(lambda: solver.apply_batched(zb, qb))
    phi = np.asarray(phi)
    errs = [direct_errors(phi[i], zh[i], qh[i], np.arange(n))
            for i in range(b)]
    record = {
        "phase": "batched", "n": n, "b": b, "dispatched": solver.dispatched,
        "caps": [solver.cfg.strong_cap, solver.cfg.weak_cap],
        "tiles": [solver.cfg.tile_boxes, solver.cfg.stage_width],
        "host_s_tune": t_tune, "host_s_first_apply": t_first,
        "host_s_steady_apply": t_steady, "err_vs_direct_rows": errs,
        "peak_bytes_in_use": _peak_bytes()}
    _say(record)
    for i, err in enumerate(errs):
        _check_err(err, f"batched row {i}")
    return record


def _wave_shapes(lattice, sizes, max_batch: int):
    """The (bucket, batch width) executables one ``ServePlane.serve``
    wave of these request sizes dispatches: full chunks of ``max_batch``
    per bucket, the remainder rounded up to a power of two."""
    from repro.serve.plane import _batch_width

    per_bucket = collections.Counter(lattice.bucket_for(n) for n in sizes)
    shapes = set()
    for bucket, count in per_bucket.items():
        full, rest = divmod(count, max_batch)
        if full:
            shapes.add((bucket, max_batch))
        if rest:
            shapes.add((bucket, _batch_width(rest, max_batch)))
    return sorted(shapes)


def phase_serving(n_min: int, n_max: int, num: int, *, seed: int = 7,
                  max_batch: int = 8, checked: int = 4,
                  backend: str = "auto") -> dict:
    """One wave of ragged requests through a warmed ``ServePlane``:
    every report must be ``ok`` on the pallas backend with no degrade
    or direct rung; the first ``checked`` answers match the direct sum."""
    from repro.data import ragged_requests
    from repro.serve import BucketLattice, Request, ServePlane

    lattice = BucketLattice.geometric(n_min, n_max)
    plane = ServePlane(lattice, backend=backend, max_batch=max_batch)
    wave = [(z.astype(np.complex64), q.astype(np.complex64))
            for _, z, q, _ in ragged_requests(
                num, seed=seed, median_n=(n_min + n_max) // 3, sigma=0.6,
                n_min=4, n_max=lattice.max_size)]
    shapes = _wave_shapes(lattice, [len(z) for z, _ in wave], max_batch)
    t0 = time.perf_counter()
    for bucket, width in shapes:
        plane.warm(buckets=[bucket], batches=[width])
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = plane.serve([Request(z, q) for z, q in wave])
    t_wave = time.perf_counter() - t0

    bad = [r.report.summary() for r in results
           if r.report.status != "ok" or r.report.backend != "pallas"
           or any(p.startswith("degrade:") or p == "direct"
                  for p in r.report.path)]
    errs = [direct_errors(r.phi, z, q, np.arange(len(z)))
            for r, (z, q) in list(zip(results, wave))[:checked]]
    record = {
        "phase": "serving", "buckets": list(lattice.sizes),
        "requests": len(wave), "executables": shapes,
        "host_s_warm": t_warm, "host_s_wave": t_wave,
        "statuses": collections.Counter(
            r.report.status for r in results),
        "backends": collections.Counter(
            str(r.report.backend) for r in results),
        "err_vs_direct_first": errs, "peak_bytes_in_use": _peak_bytes()}
    _say(record)
    if bad:
        raise AssertionError("served off the fast path:\n" + "\n".join(bad))
    for i, err in enumerate(errs):
        _check_err(err, f"serving request {i}")
    return record


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache(ROOT)}", flush=True)
    jax.config.update("jax_enable_x64", True)   # as the benchmarks run

    t0 = time.perf_counter()
    phases = [(f"main/{dist}", phase_main, (dist, 1 << 20))
              for dist in ("uniform", "normal")]
    phases += [("log", phase_log, (1 << 18,))]
    phases += [("batched", phase_batched, (3584, 8)),
               ("serving", phase_serving, (512, 2048, 16))]
    for name, phase, args in phases:
        print(f"# {name} starts at host +{time.perf_counter() - t0:.1f} s",
              flush=True)
        phase(*args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
