"""Device-resident topology subsystem: single-sort tree parity +
sort-count pin, batched/Pallas connectivity parity against a brute-force
theta oracle, overflow semantics, static-layout vectorization."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_fallback import given, settings, st
from _jaxpr import count_indexed, count_sorts

from repro.core import FmmConfig, build_connectivity
from repro.core.config import level_bounds, split_bounds
from repro.core.topology import (build_tree, build_tree_lexsort,
                                 connectivity_stats, leaf_particle_index,
                                 leaf_particle_index_loop)
from repro.data.synthetic import particles
from repro.kernels.topology import leaf_classify_pallas


def _tree_pair(n, levels, dist="uniform", seed=0, **kw):
    z, q = particles(dist, n, seed)
    cfg = FmmConfig(n=n, nlevels=levels, p=5, dtype="f64", **kw)
    z, q = jnp.asarray(z), jnp.asarray(q)
    return cfg, build_tree(z, q, cfg), build_tree_lexsort(z, q, cfg)


# ---------------------------------------------------------------------------
# single-sort tree build
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,levels,dist",
                         [(64, 1, "uniform"), (257, 2, "normal"),
                          (1024, 3, "layer"), (50, 0, "normal"),
                          (4096, 3, "normal"), (1000, 2, "uniform"),
                          (4097, 3, "layer")])
def test_tree_parity_with_lexsort_oracle(n, levels, dist):
    """Rank layout bit-identical to the seed lexsort cascade."""
    cfg, new, old = _tree_pair(n, levels, dist, seed=n)
    assert (np.asarray(new.perm) == np.asarray(old.perm)).all()
    assert (np.asarray(new.z) == np.asarray(old.z)).all()
    assert (np.asarray(new.q) == np.asarray(old.q)).all()
    for l in range(levels + 1):
        assert (np.asarray(new.centers[l]) == np.asarray(old.centers[l])).all()
        assert (np.asarray(new.radii[l]) == np.asarray(old.radii[l])).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_tree_parity_randomized_sweep(seed):
    dist = ["uniform", "normal", "layer"][seed % 3]
    cfg, new, old = _tree_pair(512, 2, dist, seed=seed)
    assert (np.asarray(new.perm) == np.asarray(old.perm)).all()
    for l in range(cfg.nlevels + 1):
        assert (np.asarray(new.centers[l]) == np.asarray(old.centers[l])).all()
        assert (np.asarray(new.radii[l]) == np.asarray(old.radii[l])).all()


def test_build_tree_at_most_two_sorts():
    """The single-sort scheme: ≤ 2 full-array sorts regardless of depth
    (the seed cascade did one lexsort per split = 2*nlevels)."""
    for levels in (1, 2, 3):
        n = 64 * 4**levels
        cfg = FmmConfig(n=n, nlevels=levels, p=5, dtype="f64")
        z, q = particles("uniform", n, 0)
        jx = jax.make_jaxpr(functools.partial(build_tree, cfg=cfg))(
            jnp.asarray(z), jnp.asarray(q))
        assert count_sorts(jx.jaxpr) <= 2, levels
        jo = jax.make_jaxpr(functools.partial(build_tree_lexsort, cfg=cfg))(
            jnp.asarray(z), jnp.asarray(q))
        assert count_sorts(jo.jaxpr) == 2 * levels  # what we replaced


@pytest.mark.parametrize("n,levels", [(1 << 20, 7), (1 << 16, 5),
                                      (3584, 3), (1000, 2)])
def test_build_tree_n_long_gathers_and_scatters(n, levels):
    """Per split one flag scatter, one gather and one partition scatter
    of N: ≤ 4L N-long scatters, and under equal segments (4**L divides
    N) ≤ 2L + 4 N-long gathers, the final rank permutation's included
    (the table path of unequal segments reads per-rank bookkeeping
    through gathers). Traced, not run."""
    cfg = FmmConfig(n=n, nlevels=levels, p=17)
    z = jax.ShapeDtypeStruct((n,), jnp.complex64)
    jx = jax.make_jaxpr(functools.partial(build_tree, cfg=cfg))(z, z).jaxpr
    assert count_indexed(jx, "scatter", n) <= 4 * levels
    if n % 4**levels == 0:
        assert count_indexed(jx, "gather", n) <= 2 * levels + 4


def _assert_kd_invariants(tree, z, cfg):
    """``perm`` permutes the input; at every split the left child's
    largest coordinate on the split axis is ≤ the right child's
    smallest; centers and radii are the boxes' direct min/max."""
    perm = np.asarray(tree.perm)
    assert (np.sort(perm) == np.arange(cfg.n)).all()
    zs = np.asarray(z)[perm]
    assert (np.asarray(tree.z) == zs).all()
    x, y = zs.real, zs.imag
    sb = split_bounds(cfg.n, 2 * cfg.nlevels)
    for s in range(2 * cfg.nlevels):
        b = sb[s]
        for a, e, m in zip(b[:-1], b[1:], sb[s + 1][1::2]):
            ex = x[a:e].max() - x[a:e].min()
            ey = y[a:e].max() - y[a:e].min()
            c = x if ex >= ey else y
            assert c[a:m].max() <= c[m:e].min(), (s, a)
    for l, bounds in enumerate(level_bounds(cfg)):
        for k, (a, e) in enumerate(zip(bounds[:-1], bounds[1:])):
            xmn, xmx = x[a:e].min(), x[a:e].max()
            ymn, ymx = y[a:e].min(), y[a:e].max()
            c = np.asarray(tree.centers[l])[k]
            assert c == complex(0.5 * (xmn + xmx), 0.5 * (ymn + ymx))
            r = 0.5 * jnp.hypot(xmx - xmn, ymx - ymn)   # the build's hypot
            assert np.asarray(tree.radii[l])[k] == r


@pytest.mark.parametrize("n,levels,dist",
                         [(4096, 3, "uniform"), (4096, 3, "normal"),
                          (3584, 3, "layer"), (1000, 2, "normal")])
def test_tree_kd_invariants_with_ties(n, levels, dist):
    """Coordinates rounded to a grid of 1/8: many equal coordinates, so
    the medians fall inside runs of ties."""
    z, q = particles(dist, n, n + 1)
    z = np.round(z.real * 8) / 8 + 1j * (np.round(z.imag * 8) / 8)
    cfg = FmmConfig(n=n, nlevels=levels, p=5, dtype="f64")
    tree = build_tree(jnp.asarray(z), jnp.asarray(q), cfg)
    _assert_kd_invariants(tree, z, cfg)


def test_build_tree_vmap_matches_single_calls():
    """The batched path (``apply_batched`` vmaps the build): 4 problems
    of 3584 equal the stacked single builds, bit for bit."""
    cfg = FmmConfig(n=3584, nlevels=3, p=17)
    zq = [particles(d, 3584, 20 + i)
          for i, d in enumerate(["uniform", "normal"] * 2)]
    z = jnp.asarray(np.stack([a for a, _ in zq]), jnp.complex64)
    q = jnp.asarray(np.stack([b for _, b in zq]), jnp.complex64)
    build = functools.partial(build_tree, cfg=cfg)
    batched = jax.jit(jax.vmap(build))(z, q)
    single = [jax.jit(build)(z[i], q[i]) for i in range(4)]
    for got, *want in zip(jax.tree_util.tree_leaves(batched),
                          *map(jax.tree_util.tree_leaves, single)):
        assert (np.asarray(got) == np.stack([np.asarray(w)
                                             for w in want])).all()


def test_connectivity_compaction_is_batched():
    """One flattened compaction sort + the (L-1) in-loop strong compacts:
    ≤ L sorts total (the seed did 2L + 3 per-level compactions)."""
    cfg = FmmConfig(n=1024, nlevels=3, p=5, dtype="f64")
    z, q = particles("uniform", 1024, 0)
    tree = build_tree(jnp.asarray(z), jnp.asarray(q), cfg)
    jx = jax.make_jaxpr(functools.partial(build_connectivity, cfg=cfg))(tree)
    assert count_sorts(jx.jaxpr) <= cfg.nlevels


def test_leaf_particle_index_matches_loop_oracle():
    for n, levels in [(64, 1), (300, 2), (1024, 3), (50, 0), (257, 2)]:
        cfg = FmmConfig(n=n, nlevels=levels, p=5, dtype="f64")
        assert (leaf_particle_index(cfg)
                == leaf_particle_index_loop(cfg)).all(), (n, levels)


# ---------------------------------------------------------------------------
# connectivity vs a brute-force theta oracle
# ---------------------------------------------------------------------------

def _conn_oracle(tree, cfg):
    """Dense numpy recursion: candidates = children of the parent's
    strong set, classified by the raw theta predicates — no caps, no
    compaction, no padding tricks."""
    centers = [np.asarray(c) for c in tree.centers]
    radii = [np.asarray(r) for r in tree.radii]
    t = cfg.theta
    strong = {0: [0]}
    weak = {l: {} for l in range(cfg.nlevels + 1)}
    for l in range(1, cfg.nlevels + 1):
        nxt = {}
        for b in range(4**l):
            nxt[b], weak[l][b] = [], []
            for s in strong[b // 4]:
                for c in (4 * s, 4 * s + 1, 4 * s + 2, 4 * s + 3):
                    d = np.hypot(centers[l][b].real - centers[l][c].real,
                                 centers[l][b].imag - centers[l][c].imag)
                    big = max(radii[l][b], radii[l][c])
                    small = min(radii[l][b], radii[l][c])
                    if big + t * small <= t * d:
                        weak[l][b].append(c)
                    else:
                        nxt[b].append(c)
        strong = nxt
    p2p, p2l, m2p = {}, {}, {}
    L = cfg.nlevels
    for b in range(4**L):
        p2p[b], p2l[b], m2p[b] = [], [], []
        for c in strong[b]:
            d = np.hypot(centers[L][b].real - centers[L][c].real,
                         centers[L][b].imag - centers[L][c].imag)
            rb, rc = radii[L][b], radii[L][c]
            swapped = min(rb, rc) + t * max(rb, rc) <= t * d
            if cfg.use_p2l_m2p and swapped and rc > rb:
                p2l[b].append(c)
            elif cfg.use_p2l_m2p and swapped and rc < rb:
                m2p[b].append(c)
            else:
                p2p[b].append(c)
    return weak, p2p, p2l, m2p


def _assert_matches_oracle(tree, conn, cfg):
    weak_o, p2p_o, p2l_o, m2p_o = _conn_oracle(tree, cfg)
    for l in range(1, cfg.nlevels + 1):
        got = np.asarray(conn.weak[l])
        for b in range(4**l):
            assert sorted(got[b][got[b] >= 0].tolist()) == sorted(
                weak_o[l][b]), ("weak", l, b)
    for name, oracle in (("p2p", p2p_o), ("p2l", p2l_o), ("m2p", m2p_o)):
        got = np.asarray(getattr(conn, name))
        for b in range(4**cfg.nlevels):
            assert sorted(got[b][got[b] >= 0].tolist()) == sorted(
                oracle[b]), (name, b)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_connectivity_matches_theta_oracle_clustered(seed):
    """Property: strong/weak/P2L/M2P lists == the brute-force theta
    classification, on clustered (adaptivity-stressing) inputs."""
    dist = ["normal", "layer"][seed % 2]
    cfg = FmmConfig(n=512, nlevels=2, p=5, dtype="f64")
    z, q = particles(dist, 512, seed)
    tree = build_tree(jnp.asarray(z), jnp.asarray(q), cfg)
    conn = build_connectivity(tree, cfg)
    assert int(conn.overflow) == 0
    _assert_matches_oracle(tree, conn, cfg)


def test_connectivity_oracle_without_p2l_m2p():
    cfg = FmmConfig(n=512, nlevels=2, p=5, dtype="f64", use_p2l_m2p=False)
    z, q = particles("normal", 512, 3)
    tree = build_tree(jnp.asarray(z), jnp.asarray(q), cfg)
    conn = build_connectivity(tree, cfg)
    _assert_matches_oracle(tree, conn, cfg)
    assert int((np.asarray(conn.p2l) >= 0).sum()) == 0
    assert int((np.asarray(conn.m2p) >= 0).sum()) == 0


# ---------------------------------------------------------------------------
# overflow fires exactly when a cap is exceeded
# ---------------------------------------------------------------------------

def test_overflow_fires_exactly_at_cap():
    z, q = particles("normal", 1024, 5)
    z, q = jnp.asarray(z), jnp.asarray(q)
    roomy = FmmConfig(n=1024, nlevels=3, p=5, dtype="f64",
                      strong_cap=64, weak_cap=256)
    tree = build_tree(z, q, roomy)
    stats = connectivity_stats(build_connectivity(tree, roomy))
    assert stats["overflow"] == 0
    smax, wmax = stats["strong_max"], stats["weak_max"]
    assert smax > 1 and wmax > 1

    # caps exactly at the measured occupancy: nothing truncates anywhere,
    # so the overflow flag must stay clean...
    tight = FmmConfig(n=1024, nlevels=3, p=5, dtype="f64",
                      strong_cap=smax, weak_cap=wmax)
    conn = build_connectivity(build_tree(z, q, tight), tight)
    assert int(conn.overflow) == 0

    # ...and one below either cap must fire it (by exactly the excess:
    # the box at max occupancy drops one entry)
    s_under = FmmConfig(n=1024, nlevels=3, p=5, dtype="f64",
                        strong_cap=smax - 1, weak_cap=wmax)
    conn = build_connectivity(build_tree(z, q, s_under), s_under)
    assert int(conn.overflow) >= 1
    w_under = FmmConfig(n=1024, nlevels=3, p=5, dtype="f64",
                        strong_cap=smax, weak_cap=wmax - 1)
    conn = build_connectivity(build_tree(z, q, w_under), w_under)
    assert int(conn.overflow) == 1


# ---------------------------------------------------------------------------
# Pallas leaf-classification kernel (topology backend hook)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist,kw", [("uniform", {}), ("normal", {}),
                                     ("layer", {}),
                                     ("normal", {"use_p2l_m2p": False}),
                                     ("layer", {"tile_boxes": 3})])
def test_pallas_leaf_classify_bit_parity(dist, kw):
    """build_connectivity(pallas hook) == build_connectivity(reference)
    bit-for-bit on every list of every level."""
    cfg = FmmConfig(n=1024, nlevels=3, p=5, dtype="f64", **kw)
    z, q = particles(dist, 1024, 11)
    tree = build_tree(jnp.asarray(z), jnp.asarray(q), cfg)
    ref = build_connectivity(tree, cfg)
    pal = build_connectivity(tree, cfg,
                             leaf_classify_impl=leaf_classify_pallas)
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(pal)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_connectivity_stats_single_transfer_semantics():
    """stats accept device arrays AND already-fetched numpy pytrees."""
    cfg = FmmConfig(n=256, nlevels=2, p=5, dtype="f64")
    z, q = particles("uniform", 256, 0)
    conn = build_connectivity(build_tree(jnp.asarray(z), jnp.asarray(q),
                                         cfg), cfg)
    on_device = connectivity_stats(conn)
    on_host = connectivity_stats(jax.device_get(conn))
    assert on_device == on_host
    assert on_device["p2p_pairs"] > 0
