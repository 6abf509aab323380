"""Leaf P2M as a dense reduction over the static leaf planes: parity with
a per-leaf oracle, finiteness on coincident leaves, batching, and the
jaxpr pins (no scatter-add in the upward phase; equal leaves take the
reshape, with no gather)."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _jaxpr import count_eqns

from repro.core import FmmConfig, expansions as E
from repro.core.config import leaf_sizes, level_bounds
from repro.core.fmm import effective_radii, p2m, upward
from repro.core.topology import build_tree
from repro.data.synthetic import particles
from repro.solver import FmmSolver

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"


def _tree(n, levels, kernel="harmonic", dtype="f64", seed=0, z=None):
    cfg = FmmConfig(n=n, nlevels=levels, p=17, kernel=kernel, dtype=dtype)
    zz, q = particles("normal", n, seed)
    z = zz if z is None else z
    return cfg, build_tree(jnp.asarray(z), jnp.asarray(q), cfg)


def _oracle(tree, cfg):
    """Per-leaf ``p2m_single`` around the leaf center, then normalized
    (a~_j = a_j rho^-j)."""
    lb = level_bounds(cfg)[-1]
    z, q = np.asarray(tree.z), np.asarray(tree.q)
    c = np.asarray(tree.centers[cfg.nlevels])
    rho = np.asarray(effective_radii(tree, cfg)[cfg.nlevels])
    j = np.arange(cfg.p + 1)
    return np.stack([
        np.asarray(E.p2m_single(z[lb[b]:lb[b + 1]], q[lb[b]:lb[b + 1]],
                                c[b], cfg.p, cfg.kernel)) * rho[b] ** -j
        for b in range(cfg.nboxes)])


@pytest.mark.parametrize("kernel", ["harmonic", "log"])
@pytest.mark.parametrize("n", [4096, 3000], ids=["equal", "unequal"])
def test_p2m_matches_per_leaf_oracle(n, kernel):
    cfg, tree = _tree(n, 3, kernel, seed=n)
    sizes = leaf_sizes(cfg)
    assert (sizes.min() == sizes.max()) == (n == 4096)
    got = np.asarray(p2m(tree, cfg))
    want = _oracle(tree, cfg)
    assert got.shape == (cfg.nboxes, cfg.p + 1)
    err = np.abs(got - want).max(axis=0)
    scale = np.abs(want).max(axis=0)
    assert (err <= 1e-12 * scale).all(), (err / np.where(scale, scale, 1))


@pytest.mark.parametrize("dtype", ["f32", "f64"])
@pytest.mark.parametrize("n", [256, 250], ids=["equal", "unequal"])
def test_p2m_coincident_leaf_stays_finite(n, dtype):
    """A leaf whose particles all coincide has radius 0: w = 0 there,
    and the padded slots of the gather branch add nothing."""
    z, _ = particles("uniform", n, 1)
    z = np.array(z)
    z[: n // 2] = 0.3 + 0.2j
    cfg, tree = _tree(n, 2, dtype=dtype, z=z)
    assert (np.asarray(tree.radii[cfg.nlevels]) == 0).any()
    got = np.asarray(p2m(tree, cfg))
    assert np.isfinite(got).all()
    if dtype == "f64":
        want = _oracle(tree, cfg)
        np.testing.assert_allclose(got, want, rtol=1e-12,
                                   atol=1e-12 * np.abs(want).max())


@pytest.mark.parametrize("n", [4096, 3000], ids=["equal", "unequal"])
def test_p2m_vmap_equals_stacked_single_calls(n):
    cfg = FmmConfig(n=n, nlevels=3, p=17, dtype="f64")
    trees = [build_tree(*map(jnp.asarray, particles("uniform", n, s)), cfg)
             for s in range(3)]
    batched = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *trees)
    got = np.asarray(jax.vmap(lambda t: p2m(t, cfg))(batched))
    want = np.stack([np.asarray(p2m(t, cfg)) for t in trees])
    np.testing.assert_allclose(got, want, rtol=1e-13,
                               atol=1e-13 * np.abs(want).max())


def _abstract_tree(cfg, batch=()):
    """Shapes of the ``Tree`` ``build_tree`` gives for ``cfg``."""
    z, q = (jax.ShapeDtypeStruct(batch + (cfg.n,), cfg.complex_dtype)
            for _ in range(2))
    build = lambda z, q: build_tree(z, q, cfg)
    return jax.eval_shape(jax.vmap(build) if batch else build, z, q)


@pytest.mark.parametrize("kernel", ["harmonic", "log"])
@pytest.mark.parametrize("n", [4096, 3000], ids=["equal", "unequal"])
def test_p2m_and_upward_hold_no_scatter_add(n, kernel):
    cfg = FmmConfig(n=n, nlevels=3, p=17, kernel=kernel, dtype="f32")
    tree = _abstract_tree(cfg)
    for fn in (p2m, upward):
        jaxpr = jax.make_jaxpr(lambda t: fn(t, cfg))(tree).jaxpr
        assert count_eqns(jaxpr, "scatter-add") == 0, fn.__name__
    # the unequal branch is one gather each of z and q
    gathers = count_eqns(jax.make_jaxpr(lambda t: p2m(t, cfg))(tree).jaxpr,
                         "gather")
    assert gathers == (0 if n == 4096 else 2)


def _scoped_eqns(jaxpr, name, scope, stack=""):
    """(all eqns, eqns of primitive ``name``) whose name stack holds
    ``scope``, recursing into sub-jaxprs (a pjit's inner stack is
    relative to its own eqn's)."""
    n_all = n_name = 0
    for eqn in jaxpr.eqns:
        path = f"{stack}/{eqn.source_info.name_stack}"
        if scope in path.split("/") or f"vmap({scope})" in path:
            n_all += 1
            n_name += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    a, b = _scoped_eqns(sub, name, scope, path)
                    n_all, n_name = n_all + a, n_name + b
    return n_all, n_name


@pytest.mark.parametrize("batched", [False, True], ids=["apply",
                                                        "apply_batched"])
def test_core_program_upward_scope_holds_no_scatter_add(batched):
    """The ``upward`` part of the solver's ``core`` program (the one the
    benchmark's apply cells run) holds no scatter-add."""
    cfg = FmmConfig(n=512, nlevels=2, p=6, dtype="f32", strong_cap=48,
                    weak_cap=128)
    solver = FmmSolver(cfg, "reference")
    fn = solver._apply_batched if batched else solver._apply
    shape = ((2,) if batched else ()) + (cfg.n,)
    z = jax.ShapeDtypeStruct(shape, jnp.complex64)
    jaxpr = jax.make_jaxpr(fn)(z, z).jaxpr
    n_upward, n_scatter = _scoped_eqns(jaxpr, "scatter-add", "upward")
    assert n_upward > 0
    assert n_scatter == 0


def _cell_config(name):
    return FmmConfig(**json.loads((CONFIGS / f"{name}.json").read_text())
                     ["fmm"])


@pytest.mark.parametrize("name,batch", [("fmm2d-1m", ()),
                                        ("fmm2d-3584", (64,)),
                                        ("fmm2d-64k-vortex", ())])
def test_benchmark_configs_take_the_reshape_branch(name, batch):
    """The benchmark cells' leaves are all of one size (64, 56, 64), so
    their P2M lays the leaves out by reshape: no gather, no scatter."""
    cfg = _cell_config(name)
    sizes = leaf_sizes(cfg)
    assert sizes.min() == sizes.max() == cfg.n // cfg.nboxes
    tree = _abstract_tree(cfg, batch)
    fn = lambda t: p2m(t, cfg)
    jaxpr = jax.make_jaxpr(jax.vmap(fn) if batch else fn)(tree).jaxpr
    assert count_eqns(jaxpr, "gather") == 0
    assert count_eqns(jaxpr, "scatter-add") == 0
