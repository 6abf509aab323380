"""End-to-end driver: 2D point-vortex dynamics with FMM velocity evaluation
— the application domain the paper's code was built for (vortex methods;
Goude's wind-turbine wake simulations).

Each RK2 step evaluates the induced velocity field

    u - i v = (1 / 2*pi*i) * sum_j G_j / (z - z_j)

via the adaptive FMM (the paper's eq. (5.1) summation), advects the
vortices, and tracks the flow invariants (circulation and linear impulse
sum G_j z_j are conserved exactly by point-vortex dynamics, so their drift
measures integration+FMM error).

    PYTHONPATH=src python examples/vortex_dynamics.py --n 20000 --steps 20
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.compile_cache import use_compile_cache

use_compile_cache(".")

import numpy as np
import jax.numpy as jnp

from repro.configs.fmm2d import fmm_config
from repro.solver import FmmSolver


def velocity(z, gamma, guard):
    """u + iv at each vortex (harmonic-kernel FMM, Biot-Savart in 2D).

    Splits the evaluation at the topology/evaluation seam
    (``refresh_guarded`` + ``apply_plan``): the guarded refresh reads
    the plan's cap margins (one host read, no extra builds) and — when
    advection drifts the layout past the t=0-tuned caps — re-plans at
    escalated caps instead of dropping interactions or dying mid-run.
    Returns (velocity, GuardReport)."""
    plan, report = guard.refresh_guarded(z, gamma.astype(z.dtype))
    phi = guard.apply_plan(plan)
    # phi_i = sum_j G_j/(z_j - z_i);  u - iv = phi/(2 pi i) -> conj
    return jnp.conj(phi / (2j * jnp.pi)), report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dt", type=float, default=2e-4)
    ap.add_argument("--p", type=int, default=12)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    n2 = args.n // 2
    # two counter-rotating Lamb-like clusters -> a translating vortex pair
    z0 = np.concatenate([
        0.35 + 0.5j + 0.08 * (rng.normal(size=n2) + 1j * rng.normal(size=n2)),
        0.65 + 0.5j + 0.08 * (rng.normal(size=args.n - n2)
                              + 1j * rng.normal(size=args.n - n2)),
    ])
    gamma = np.concatenate([np.full(n2, 1.0 / n2),
                            np.full(args.n - n2, -1.0 / (args.n - n2))])
    z = jnp.asarray(z0)
    g = jnp.asarray(gamma + 0j)
    cfg = fmm_config(args.n, p=args.p)
    # tune once on the initial layout; the caps keep head-room (margin)
    # for the advected positions so every step stays on the jit path
    solver = FmmSolver.build(cfg, "auto").tune(z, g, margin=1.5)
    # guarded refresh: every step reads the health margins; cap drift
    # re-plans through the escalation lattice instead of aborting
    guard = solver.guarded(max_cap_doublings=3)
    print(f"[vortex] N={args.n} vortices, {args.steps} RK2 steps, "
          f"p={args.p}, levels={cfg.nlevels}, "
          f"caps={guard.cfg.strong_cap}/{guard.cfg.weak_cap}")

    imp0 = complex(np.sum(gamma * z0))
    t0 = time.perf_counter()
    replans = 0
    for s in range(args.steps):
        u1, rep1 = velocity(z, g, guard)
        zm = z + 0.5 * args.dt * u1              # RK2 midpoint
        u2, rep2 = velocity(zm, g, guard)
        z = z + args.dt * u2
        replans += rep1.retries + rep2.retries
        if rep1.retries or rep2.retries:
            print(f"[vortex] step {s:3d}  re-planned: "
                  f"{(rep2 if rep2.retries else rep1).summary()}  "
                  f"caps now {guard.cfg.strong_cap}/{guard.cfg.weak_cap}")
        if s % 5 == 0 or s == args.steps - 1:
            imp = complex(np.sum(gamma * np.asarray(z)))
            drift = abs(imp - imp0) / max(abs(imp0), 1e-12)
            print(f"[vortex] step {s:3d}  impulse drift {drift:.2e}  "
                  f"replans {replans}  "
                  f"({(time.perf_counter()-t0)/(s+1):.2f} s/step avg)")
    assert guard.trace_counts["build"] == 1 or replans > 0, \
        "refresh re-traced mid-run without a cap re-plan"
    sep = abs(np.mean(np.asarray(z)[:n2]) - np.mean(np.asarray(z)[n2:]))
    print(f"[vortex] final cluster separation {sep:.3f} (pair translates, "
          f"separation ~const)")
    imp = complex(np.sum(gamma * np.asarray(z)))
    drift = abs(imp - imp0) / max(abs(imp0), 1e-12)
    assert drift < 1e-2, f"impulse drift {drift} too large"
    print("[vortex] OK — invariants preserved")


if __name__ == "__main__":
    main()
