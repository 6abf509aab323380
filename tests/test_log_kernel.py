"""The logarithmic kernel end to end in float32, as the chip runs it
(Pallas in interpret mode here): a clustered problem, N = 4096 normal
(sigma 0.1), p = 17, through ``FmmSolver.apply``, against the reference
backend on complex phi and against the float64 direct sum on Re phi."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import FmmConfig
from repro.core.direct import direct_potential_numpy
from repro.data.synthetic import particles
from repro.solver import FmmSolver

CFG = FmmConfig(n=4096, nlevels=3, p=17, theta=0.5, kernel="log",
                dtype="f32", strong_cap=64, weak_cap=96, tile_boxes=8,
                stage_width=2)


@pytest.fixture(scope="module")
def problem():
    z, q = particles("normal", CFG.n, 15)
    z, q = z.astype(np.complex64), q.astype(np.complex64)
    solver = FmmSolver.build(CFG, "pallas")
    assert solver.dispatched["apply"] == "pallas"
    assert int(solver.refresh(jnp.asarray(z), jnp.asarray(q)).conn
               .overflow) == 0
    phi = np.asarray(solver.apply(jnp.asarray(z), jnp.asarray(q)),
                     np.complex128)
    return z, q, phi


def test_log_pallas_matches_reference_backend_on_complex_phi(problem):
    """Both backends run the same expansions in float32 and take the
    log's branch at the same points, so they differ by rounding alone:
    the M2L contraction's order and the kernels' polynomial ``atan2``
    (within 2.6e-7 rad of XLA's). That reads 3.2e-7 of max |phi|, and
    1.7e-4 with the M2L contraction on bfloat16 operands; the bound is
    1e-5 of it. A branch taken the other way moves Im phi by 2 pi |q| of
    one charge: above the bound for |q| > 5e-4."""
    z, q, phi = problem
    ref = np.asarray(FmmSolver.build(CFG, "reference").apply(
        jnp.asarray(z), jnp.asarray(q)), np.complex128)
    scale = np.abs(ref).max()
    assert np.abs(phi - ref).max() < 1e-5 * scale
    assert 2 * np.pi * np.median(np.abs(q)) > 1e-5 * scale


def test_log_pallas_matches_direct_sum_on_real_part(problem):
    """Re phi = sum q log|y - x| is single-valued with real charges; Im
    phi differs from the direct sum by multiples of 2 pi q, the FMM's
    expansions taking the branch at other points. Truncation at p = 17
    and theta 0.5 plus float32 rounding read 4.5e-7 of max |Re phi|, and
    2.6e-4 with the M2L contraction on bfloat16 operands; the bound is
    1e-5 of it."""
    z, q, phi = problem
    ref = direct_potential_numpy(z, z, q, kernel="log")
    err = np.abs(phi.real - ref.real).max() / np.abs(ref.real).max()
    assert err < 1e-5


def test_p2l_log_takes_the_reference_branch_at_a_tie():
    """A source on the horizontal line through the target box's centre,
    to its right: z0 - x is a negative real with a +0 imaginary part,
    whose log the reference P2L sweep takes as log|z0 - x| + i pi. The
    kernel forms z0 - x itself, not -(x - z0) (whose zero is -0, the
    other branch), so b_0 of a unit charge has Im +pi."""
    from repro.kernels import p2l_pallas

    nbox, n_pad, p = 8, 128, 4
    z0r = jnp.full((nbox,), 0.5, jnp.float32)
    z0i = jnp.full((nbox,), 0.25, jnp.float32)
    rho = jnp.full((nbox,), 0.1, jnp.float32)
    lists = jnp.full((nbox, 1), -1, jnp.int32).at[0, 0].set(1)
    plane = jnp.zeros((nbox + 1, n_pad), jnp.float32)
    xzr, xzi = plane.at[1, 0].set(0.75), plane.at[1, 0].set(0.25)
    xqr, xqi = plane.at[1, 0].set(1.0), plane
    outr, outi = p2l_pallas(lists, z0r, z0i, rho, xzr, xzi, xqr, xqi, p=p,
                            P=128, kernel="log", interpret=True)
    assert float(outr[0, 0]) == pytest.approx(np.log(0.25), rel=1e-6)
    assert float(outi[0, 0]) == pytest.approx(np.pi, rel=1e-6)
