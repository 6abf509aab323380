"""Quickstart: evaluate the harmonic potential of 100k particles with the
adaptive FMM through the `FmmSolver` front-end, check it against direct
summation on a sample, then serve a batched (B, N) workload through
`apply_batched` — one call, one compiled program, B problems.

    PYTHONPATH=src python examples/quickstart.py [--n 100000] [--p 17]
                                                 [--batch 4]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np
import jax
jax.config.update("jax_enable_x64", True)  # f64 = the paper's precision
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache

use_compile_cache(".")

from repro.configs.fmm2d import fmm_config
from repro.core import direct_potential, rel_error_inf
from repro.solver import FmmSolver


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--p", type=int, default=17)
    ap.add_argument("--dist", default="normal",
                    choices=["uniform", "normal", "layer"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "reference", "pallas"])
    ap.add_argument("--batch", type=int, default=4,
                    help="problems per apply_batched call (0 skips the "
                         "batched-serving section)")
    args = ap.parse_args()

    from repro.data.synthetic import particles
    # the TPU kernels compute in f32 (an f64 config is refused there)
    dtype = "f32" if jax.default_backend() == "tpu" else "f64"
    tol = 5e-4 if dtype == "f32" else 1e-4
    cfg = fmm_config(args.n, p=args.p, dtype=dtype)

    def sample(seed):
        z, q = particles(args.dist, args.n, seed=seed)
        return (jnp.asarray(np.asarray(z), cfg.complex_dtype),
                jnp.asarray(np.asarray(q), cfg.complex_dtype))

    z, q = sample(0)
    print(f"[quickstart] N={args.n} ({args.dist}), p={args.p}, {dtype}, "
          f"levels={cfg.nlevels} ({4**cfg.nlevels} leaf boxes)")

    # tune() fits the padded-list caps to this workload (overflow-free,
    # shrunk padding); build() caches the compiled plan per config.
    solver = FmmSolver.build(cfg, args.backend).tune(z, q)
    print(f"[quickstart] tuned caps: strong={solver.cfg.strong_cap} "
          f"weak={solver.cfg.weak_cap} "
          f"(from {cfg.strong_cap}/{cfg.weak_cap})")

    t0 = time.perf_counter()
    phi = solver.apply(z, q)
    phi.block_until_ready()
    t_compile = time.perf_counter() - t0

    t0 = time.perf_counter()
    phi = solver.apply(z, q)
    phi.block_until_ready()
    t_run = time.perf_counter() - t0
    print(f"[quickstart] fmm: {t_run*1e3:.0f} ms/eval "
          f"(+{t_compile - t_run:.1f} s compile)")

    # spot-check 512 points against O(N^2) truth
    idx = np.random.default_rng(0).choice(args.n, 512, replace=False)
    ref = direct_potential(jnp.asarray(np.asarray(z)[idx]), z, q)
    err = rel_error_inf(np.asarray(phi)[idx], np.asarray(ref))
    print(f"[quickstart] rel err vs direct (512-pt sample): {err:.2e}")
    assert err < tol, "accuracy regression"

    if args.batch > 0:
        # batched serving: build once, evaluate B independent problems
        # per call. The solver reports which backend the batched entry
        # point ACTUALLY runs — on the pallas backend the custom
        # batching rules keep the batch on batch-major kernel grids
        # (one fused launch per phase for all B problems).
        B = args.batch
        rows = [(z, q)] + [sample(s) for s in range(1, B)]
        zb = jnp.stack([r[0] for r in rows])
        qb = jnp.stack([r[1] for r in rows])
        # the batch shares ONE cap budget: tune it on the (B, N) sample
        # (sized to the worst row), then serve with the batch-wide
        # overflow guard — an overflowing member raises instead of
        # silently returning truncated potentials.
        solver = solver.tune(zb, qb, tiles=False)
        phib = solver.apply_batched_checked(zb, qb)
        phib.block_until_ready()
        t0 = time.perf_counter()
        phib = solver.apply_batched(zb, qb)
        phib.block_until_ready()
        t_b = time.perf_counter() - t0
        print(f"[quickstart] batched: {B} problems/call, "
              f"{t_b*1e3:.0f} ms/call ({t_b/B*1e3:.0f} ms/problem), "
              f"dispatched={solver.dispatched['apply_batched']}")
        assert np.allclose(np.asarray(phib[0]), np.asarray(phi),
                           rtol=1e-6, atol=1e-6), "batched row 0 != apply"
    print("[quickstart] OK")


if __name__ == "__main__":
    main()
