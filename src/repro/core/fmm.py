"""The adaptive FMM pipeline (paper §3.3) as a single jit-able function.

Phases (paper naming), each traced under the ``jax.named_scope`` of the
same name, with the sub-scopes in parentheses:
  topology:    build_tree (sort) + build_connectivity (connect)
  upward:      P2M (p2m) , M2M (m2m)
  downward:    M2L (m2l) , L2L (l2l) , P2L (p2l)
  evaluation:  L2P (l2p) + M2P (m2p) + P2P (p2p), or the fused kernel;
               the scatter back to input order (unsort)

The scopes land in every device op's ``tf_op`` in a profiler trace
(``jit(core)/topology/sort/gather``), so a trace times each phase (Table
5.1 / Figs 5.1, 5.3, 5.7). The per-phase functions are exposed
individually so the Pallas kernels in ``repro.kernels`` can replace the
hot ones (P2P, M2L) one at a time.

Every shape is static given ``FmmConfig``; there is no data-dependent
control flow — the adaptivity lives entirely in the *contents* of the
padded interaction lists, which is the paper's central design point and
exactly what pjit/TPU want.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import expansions as E
from .config import FmmConfig
from .mathf import clog
from .topology import (MARGIN_CLASSES, Connectivity, Tree,
                       build_connectivity, build_tree, leaf_ids,
                       leaf_particle_index, leaf_planes)


class FmmPlan(NamedTuple):
    """Static constants + built tree/connectivity for one evaluation."""

    tree: Tree
    conn: Connectivity


#: Order of the per-class entries in ``Health.margins`` (the
#: connectivity's ``MARGIN_CLASSES``, re-exported at the pipeline level).
HEALTH_CLASSES = MARGIN_CLASSES


class Health(NamedTuple):
    """In-graph health plane of one evaluation (DESIGN.md §9).

    A handful of scalars computed *inside* the compiled pipeline, so
    validated entry points (``FmmSolver.apply_checked``, the guarded
    ladder) read execution health with ONE ``device_get`` on the launch
    they already ran — no second eager topology build:

      margins           (5,) int32, ``HEALTH_CLASSES`` order — slots left
                        on the fullest interaction list per class;
                        negative = that many entries were silently
                        dropped (the answer is wrong)
      overflow          () int32 — max dropped-entry count (0 = healthy)
      nonfinite_input   () bool — any NaN/Inf in z or q
      nonfinite_output  () bool — any NaN/Inf in phi
    """

    margins: jax.Array
    overflow: jax.Array
    nonfinite_input: jax.Array
    nonfinite_output: jax.Array


def _any_nonfinite(*arrays: jax.Array) -> jax.Array:
    flag = jnp.asarray(False)
    for a in arrays:
        flag = flag | ~jnp.all(jnp.isfinite(a))
    return flag


def health_of(plan: FmmPlan, z: jax.Array, q: jax.Array,
              phi: jax.Array) -> Health:
    """Assemble the health plane for an evaluation of ``plan`` on
    (z, q) that produced ``phi``. Pure graph ops — jit/vmap-safe."""
    return Health(margins=plan.conn.margins,
                  overflow=plan.conn.overflow,
                  nonfinite_input=_any_nonfinite(z, q),
                  nonfinite_output=_any_nonfinite(phi))


def effective_radii(tree: Tree, cfg: FmmConfig) -> list[jax.Array]:
    """Per-level normalization radii: the box radius floored at 1e-6 of the
    level maximum (point-like boxes would otherwise produce 0/0 ratios).

    All expansions are stored radius-normalized (a~_j = a_j rho^-j,
    b~_l = b_l rho^l): translations then multiply only bounded ratios,
    which is what makes deep trees work in f32 (the TPU dtype) — see
    expansions.py."""
    out = []
    for l in range(cfg.nlevels + 1):
        r = tree.radii[l]
        out.append(jnp.maximum(r, 1e-6 * jnp.max(r) + 1e-300))
    return out


# ---------------------------------------------------------------------------
# upward phase
# ---------------------------------------------------------------------------

def p2m(tree: Tree, cfg: FmmConfig, rho=None) -> jax.Array:
    """Leaf multipole expansions, radius-normalized; (4**L, p+1) complex.

    A dense reduction over the static leaf planes (``leaf_planes``): each
    power is summed along a leaf's row, so no per-particle scatter runs."""
    if rho is None:
        rho = effective_radii(tree, cfg)[cfg.nlevels]
    z, q = leaf_planes(tree, cfg)                         # (4**L, n_max)
    rho = rho[:, None]
    w = (z - tree.centers[cfg.nlevels][:, None]) / rho

    if cfg.kernel == "harmonic":
        coeffs = [jnp.zeros(cfg.nboxes, q.dtype)]
        pw = q / rho
        for _ in range(cfg.p):
            coeffs.append(-pw.sum(axis=-1))
            pw = pw * w
    else:  # log: a~_0 = sum q; a~_j = -sum q w^j / j  (w already /rho)
        coeffs = [q.sum(axis=-1)]
        pw = q
        for j in range(1, cfg.p + 1):
            pw = pw * w
            coeffs.append(-pw.sum(axis=-1) / j)
    return jnp.stack(coeffs, axis=-1)


def m2m_level(child_coeffs: jax.Array, tree: Tree, l: int,
              cfg: FmmConfig, rho_child, rho_parent) -> jax.Array:
    """Shift level-(l+1) multipoles into level-l parents; sum 4 children."""
    nb_child = 4 ** (l + 1)
    parent = jnp.arange(nb_child, dtype=jnp.int32) // 4
    t = tree.centers[l + 1] - tree.centers[l][parent]
    u = t / rho_parent[parent]
    ratio = (rho_child / rho_parent[parent]).astype(child_coeffs.dtype)
    shifted = E.m2m_norm(child_coeffs, u, ratio)
    return shifted.reshape(4**l, 4, cfg.p + 1).sum(axis=1)


def upward(tree: Tree, cfg: FmmConfig, rho=None) -> list[jax.Array]:
    """Normalized multipole coefficients per level (l -> (4**l, p+1))."""
    if rho is None:
        rho = effective_radii(tree, cfg)
    m = [None] * (cfg.nlevels + 1)
    with jax.named_scope("p2m"):
        m[cfg.nlevels] = p2m(tree, cfg, rho[cfg.nlevels])
    with jax.named_scope("m2m"):
        for l in range(cfg.nlevels - 1, -1, -1):
            m[l] = m2m_level(m[l + 1], tree, l, cfg, rho[l + 1], rho[l])
    return m


# ---------------------------------------------------------------------------
# downward phase
# ---------------------------------------------------------------------------

def m2l_level(mult: jax.Array, weak: jax.Array, centers: jax.Array,
              cfg: FmmConfig, mat, rho) -> jax.Array:
    """Sum of M2L translations into each box of one level (normalized).

    Chunked over the padded weak list to bound the (B, chunk, p+1) working
    set — the jnp analogue of the paper's shared-memory staging; the Pallas
    kernel (kernels/m2l.py) performs the same computation with explicit
    VMEM tiles.
    """
    nb, W = weak.shape
    c = cfg.m2l_chunk
    pad = (-W) % c
    wk_all = jnp.pad(weak, ((0, 0), (0, pad)), constant_values=-1)
    chunks = wk_all.reshape(nb, -1, c).transpose(1, 0, 2)  # (n_chunks, nb, c)

    def body(acc, wk):
        mask = wk >= 0
        src = jnp.where(mask, wk, 0)
        a = jnp.where(mask[..., None], mult[src], 0.0)
        r = jnp.where(mask, centers[:, None] - centers[src], 1.0)
        rho_s = jnp.where(mask, rho[src], 0.0)
        rho_t = rho[:, None]
        if cfg.translations == "mxu":
            contrib = E.m2l_norm(a, r, rho_s, rho_t, mat)
        else:
            contrib = E.m2l_norm_horner(a, r, rho_s, rho_t)
        return acc + contrib.sum(axis=1), None

    out, _ = jax.lax.scan(body, jnp.zeros((nb, cfg.p + 1), mult.dtype),
                          chunks)
    return out


def l2l_level(parent_local: jax.Array, tree: Tree, l: int,
              cfg: FmmConfig, rho_child, rho_parent) -> jax.Array:
    """Shift level-(l-1) locals down to level-l children (normalized)."""
    nb = 4**l
    parent = jnp.arange(nb, dtype=jnp.int32) // 4
    s = tree.centers[l] - tree.centers[l - 1][parent]
    v = s / rho_parent[parent]
    ratio = (rho_child / rho_parent[parent]).astype(parent_local.dtype)
    return E.l2l_norm(parent_local[parent], v, ratio)


def p2l_sweep(local: jax.Array, tree: Tree, conn: Connectivity,
              cfg: FmmConfig, idx: jax.Array, rho) -> jax.Array:
    """Direct particle->local shifts for swapped-theta leaf pairs
    (radius-normalized: b~_l = sum q/(x-z0) * (rho_t/(x-z0))^l).

    Scanned over list slots (one compiled body regardless of the cap)."""
    z0 = tree.centers[cfg.nlevels]

    def body(acc, src):
        bmask = src >= 0
        srcc = jnp.where(bmask, src, 0)
        pidx = idx[srcc]                                  # (nb, n_max)
        pmask = (pidx >= 0) & bmask[:, None]
        safe = jnp.where(pidx >= 0, pidx, 0)
        pz = tree.z[safe]
        pq = jnp.where(pmask, tree.q[safe], 0.0)
        inv = jnp.where(pmask, 1.0 / (pz - z0[:, None]), 0.0)
        w = rho[:, None] * inv
        if cfg.kernel == "harmonic":
            pw = pq * inv
            updates = []
            for _ in range(cfg.p + 1):
                updates.append(pw.sum(axis=-1))
                pw = pw * w
        else:
            logs = jnp.where(pmask, clog(z0[:, None] - pz), 0.0)
            updates = [(pq * logs).sum(axis=-1)]
            pw = pq * w
            for l in range(1, cfg.p + 1):
                updates.append(-(pw.sum(axis=-1)) / l)
                pw = pw * w
        return acc + jnp.stack(updates, axis=-1), None

    out, _ = jax.lax.scan(body, local, conn.p2l.T)
    return out


def _apply_p2l(local, tree, conn, cfg: FmmConfig, rho, p2l_impl):
    """Fold the leaf P2L contribution into ``local`` — via the reference
    jnp scan, or a ``p2l_impl(tree, conn, cfg, idx, rho_leaf)`` hook that
    returns the (nbox, p+1) contribution (the Pallas kernel)."""
    if not (cfg.use_p2l_m2p and cfg.nlevels > 0):
        return local
    idx = leaf_particle_index(cfg)
    with jax.named_scope("p2l"):
        if p2l_impl is None:
            return p2l_sweep(local, tree, conn, cfg, jnp.asarray(idx),
                             rho[cfg.nlevels])
        return local + p2l_impl(tree, conn, cfg, idx, rho[cfg.nlevels])


def downward(mult: list[jax.Array], tree: Tree, conn: Connectivity,
             cfg: FmmConfig, rho=None, p2l_impl=None) -> jax.Array:
    """Local coefficients at the leaf level (incl. M2L, L2L, P2L)."""
    p = cfg.p
    cdt = mult[-1].dtype
    m2l_mat = jnp.asarray(E.m2l_matrix(p), dtype=cfg.real_dtype)
    if rho is None:
        rho = effective_radii(tree, cfg)

    local = jnp.zeros((1, p + 1), dtype=cdt)
    for l in range(1, cfg.nlevels + 1):
        with jax.named_scope("l2l"):
            local = l2l_level(local, tree, l, cfg, rho[l], rho[l - 1])
        with jax.named_scope("m2l"):
            local = local + m2l_level(mult[l], conn.weak[l],
                                      tree.centers[l], cfg, m2l_mat, rho[l])
    if cfg.nlevels == 0:
        with jax.named_scope("m2l"):
            local = local + m2l_level(mult[0], conn.weak[0],
                                      tree.centers[0], cfg, m2l_mat, rho[0])
    return _apply_p2l(local, tree, conn, cfg, rho, p2l_impl)


# ---------------------------------------------------------------------------
# evaluation phase
# ---------------------------------------------------------------------------

def l2p(local: jax.Array, tree: Tree, cfg: FmmConfig, rho=None) -> jax.Array:
    """Evaluate leaf local expansions at the (sorted) particle positions."""
    lid = jnp.asarray(leaf_ids(cfg))
    if rho is None:
        rho = effective_radii(tree, cfg)[cfg.nlevels]
    t = (tree.z - tree.centers[cfg.nlevels][lid]) / rho[lid]
    b = local[lid]                                        # (N, p+1)
    acc = b[:, cfg.p]
    for j in range(cfg.p - 1, -1, -1):
        acc = acc * t + b[:, j]
    return acc


def m2p_sweep(phi: jax.Array, mult_leaf: jax.Array, tree: Tree,
              conn: Connectivity, cfg: FmmConfig, rho=None) -> jax.Array:
    """Evaluate source-box multipoles directly at target particles
    (normalized: Horner in w = rho_src/(z - z0_src))."""
    lid = jnp.asarray(leaf_ids(cfg))
    z0 = tree.centers[cfg.nlevels]
    if rho is None:
        rho = effective_radii(tree, cfg)[cfg.nlevels]

    def body(acc_phi, col):
        src = col[lid]                                    # (N,)
        mask = src >= 0
        srcc = jnp.where(mask, src, 0)
        a = mult_leaf[srcc]                               # (N, p+1)
        dz = tree.z - z0[srcc]
        w = jnp.where(mask, rho[srcc] / dz, 0.0)
        acc = a[:, cfg.p]
        for j in range(cfg.p - 1, 0, -1):
            acc = acc * w + a[:, j]
        acc = acc * w
        if cfg.kernel == "log":
            acc = acc + a[:, 0] * jnp.where(
                mask, clog(jnp.where(mask, dz, 1.0)), 0.0)
        return acc_phi + jnp.where(mask, acc, 0.0), None

    out, _ = jax.lax.scan(body, phi, conn.m2p.T)
    return out


def p2p_sweep(phi: jax.Array, tree: Tree, conn: Connectivity,
              cfg: FmmConfig, idx: jax.Array) -> jax.Array:
    """Near-field direct evaluation over the leaf P2P lists (Alg. 3.7).

    Pure-jnp reference path; the Pallas kernel (kernels/p2p.py) implements
    the same contraction with VMEM source tiles.
    """
    nb, n_max = idx.shape
    tmask = idx >= 0
    tidx = jnp.where(tmask, idx, 0)
    tz = tree.z[tidx]                                     # (nb, n_max)

    def body(acc, src):
        bmask = src >= 0
        srcc = jnp.where(bmask, src, 0)
        sidx = idx[srcc]
        smask = (sidx >= 0) & bmask[:, None]
        siu = jnp.where(sidx >= 0, sidx, 0)
        sz = tree.z[siu]
        sq = jnp.where(smask, tree.q[siu], 0.0)
        diff = sz[:, None, :] - tz[:, :, None]            # (nb, n_t, n_s)
        # self-interaction excluded by particle identity (global rank),
        # not position: distinct coincident particles contribute their
        # (singular) mutual term — the sum_{j != i} semantics of eq. (1.1).
        ok = smask[:, None, :] & (sidx[:, None, :] != idx[:, :, None])
        if cfg.kernel == "harmonic":
            contrib = (jnp.where(ok, sq[:, None, :], 0.0)
                       / jnp.where(ok, diff, 1.0))
        else:
            contrib = jnp.where(ok, sq[:, None, :]
                                * clog(jnp.where(ok, -diff, 1.0)), 0.0)
        return acc + contrib.sum(axis=-1), None

    acc, _ = jax.lax.scan(body, jnp.zeros_like(tz), conn.p2p.T)
    # scatter back to rank order (padded entries write a masked zero to rank 0)
    flat = jnp.where(tmask.reshape(-1), acc.reshape(-1), 0.0)
    return phi.at[tidx.reshape(-1)].add(flat)


# ---------------------------------------------------------------------------
# full pipeline
# ---------------------------------------------------------------------------

def fmm_build(z: jax.Array, q: jax.Array, cfg: FmmConfig,
              leaf_classify_impl=None) -> FmmPlan:
    """Topological phase: sort (single-sort tree build) + connect.

    ``leaf_classify_impl`` optionally replaces the leaf-level
    strong/weak/swapped-theta classification (the ``Backend.leaf_classify``
    topology hook — the Pallas kernel on the pallas backend)."""
    with jax.named_scope("topology"):
        with jax.named_scope("sort"):
            tree = build_tree(z, q, cfg)
        with jax.named_scope("connect"):
            conn = build_connectivity(tree, cfg,
                                      leaf_classify_impl=leaf_classify_impl)
    return FmmPlan(tree=tree, conn=conn)


def fmm_evaluate(plan: FmmPlan, cfg: FmmConfig,
                 p2p_impl=None, m2l_impl=None, l2p_impl=None,
                 m2l_fused_impl=None, p2l_impl=None,
                 eval_fused_impl=None) -> jax.Array:
    """Run upward/downward/evaluation on a built plan; returns sorted phi.

    ``p2p_impl`` / ``m2l_impl`` / ``l2p_impl`` optionally override the
    near-field, M2L and L2P sweeps (used to swap in Pallas kernels; see
    ``repro.solver.backends`` for the registry that bundles them).
    ``m2l_fused_impl`` takes precedence over ``m2l_impl``: it receives the
    per-level sequences and computes the whole downward M2L in one launch
    (see ``downward_fused``). ``p2l_impl`` overrides the downward P2L
    scan (returns the (nbox, p+1) contribution). ``eval_fused_impl``
    takes precedence over the three evaluation hooks: it computes the
    whole evaluation phase (L2P + M2P + P2P) in one launch —
    ``eval_fused_impl(local, mult_leaf, tree, conn, cfg, idx) -> (n,)``.
    """
    tree, conn = plan.tree, plan.conn
    with jax.named_scope("upward"):
        mult = upward(tree, cfg)

    with jax.named_scope("downward"):
        if m2l_fused_impl is not None:
            local = downward_fused(mult, tree, conn, cfg, m2l_fused_impl,
                                   p2l_impl)
        elif m2l_impl is None:
            local = downward(mult, tree, conn, cfg, p2l_impl=p2l_impl)
        else:
            local = downward_with(mult, tree, conn, cfg, m2l_impl, p2l_impl)

    # numpy constant (static layout): kernel wrappers derive shapes from it
    idx = leaf_particle_index(cfg)
    with jax.named_scope("evaluation"):
        if eval_fused_impl is not None:
            return eval_fused_impl(local, mult[cfg.nlevels], tree, conn, cfg,
                                   idx)
        with jax.named_scope("l2p"):
            if l2p_impl is None:
                phi = l2p(local, tree, cfg)
            else:
                phi = l2p_impl(local, tree, cfg, idx)
        if cfg.use_p2l_m2p:
            with jax.named_scope("m2p"):
                phi = m2p_sweep(phi, mult[cfg.nlevels], tree, conn, cfg)
        with jax.named_scope("p2p"):
            if p2p_impl is None:
                return p2p_sweep(phi, tree, conn, cfg, jnp.asarray(idx))
            return phi + p2p_impl(tree, conn, cfg, idx)


def downward_with(mult, tree, conn, cfg, m2l_impl, p2l_impl=None) -> jax.Array:
    p = cfg.p
    rho = effective_radii(tree, cfg)
    local = jnp.zeros((1, p + 1), dtype=mult[-1].dtype)
    for l in range(1, cfg.nlevels + 1):
        with jax.named_scope("l2l"):
            local = l2l_level(local, tree, l, cfg, rho[l], rho[l - 1])
        with jax.named_scope("m2l"):
            local = local + m2l_impl(mult[l], conn.weak[l], tree.centers[l],
                                     cfg, rho[l])
    if cfg.nlevels == 0:
        with jax.named_scope("m2l"):
            local = local + m2l_impl(mult[0], conn.weak[0], tree.centers[0],
                                     cfg, rho[0])
    return _apply_p2l(local, tree, conn, cfg, rho, p2l_impl)


def downward_fused(mult, tree, conn, cfg, m2l_fused_impl,
                   p2l_impl=None) -> jax.Array:
    """Downward pass with the level-fused M2L hook (one launch, all levels).

    ``m2l_fused_impl(mult, weak, centers, cfg, rho)`` receives the
    per-level sequences and returns the per-level M2L contributions; the
    (cheap, inherently sequential) L2L recursion then folds them in
    level by level, replacing the per-level launch loop. ``p2l_impl``
    optionally replaces the leaf P2L scan (one more launch, no jnp
    fallback on the pallas path).
    """
    p = cfg.p
    rho = effective_radii(tree, cfg)
    with jax.named_scope("m2l"):
        contribs = m2l_fused_impl(mult, conn.weak, tree.centers, cfg, rho)
    local = jnp.zeros((1, p + 1), dtype=mult[-1].dtype)
    if cfg.nlevels == 0:
        local = local + contribs[0]
    else:
        with jax.named_scope("l2l"):
            for l in range(1, cfg.nlevels + 1):
                local = l2l_level(local, tree, l, cfg, rho[l], rho[l - 1])
                local = local + contribs[l - 1]
    return _apply_p2l(local, tree, conn, cfg, rho, p2l_impl)


@functools.partial(jax.jit, static_argnums=2)
def fmm_potential(z: jax.Array, q: jax.Array, cfg: FmmConfig) -> jax.Array:
    """Phi(z_i) = sum_{j != i} G(z_i, x_j) for all input points (eq. 1.1)."""
    plan = fmm_build(z, q, cfg)
    return unsort(fmm_evaluate(plan, cfg), plan.tree.perm)


def unsort(phi_sorted: jax.Array, perm: jax.Array) -> jax.Array:
    """Sorted (leaf-order) potentials back to the input order; the last
    step of the evaluation phase."""
    with jax.named_scope("evaluation"), jax.named_scope("unsort"):
        return jnp.zeros_like(phi_sorted).at[perm].set(phi_sorted)


def fmm_potential_checked(z, q, cfg: FmmConfig, max_grow: int = 3):
    """fmm_potential with interaction-list overflow validation.

    The padded-list caps are static shapes; if the input distribution
    overflows them the jit path would silently drop interactions. This
    wrapper checks the overflow scalar (one cheap eager build) and regrows
    the caps (x2, up to ``max_grow`` times) before evaluating. Production
    deployments pin the grown config and stay on the jit path.
    """
    import dataclasses

    for _ in range(max_grow + 1):
        plan = fmm_build(z, q, cfg)
        if int(jax.device_get(plan.conn.overflow)) == 0:
            return unsort(fmm_evaluate(plan, cfg), plan.tree.perm), cfg
        cfg = dataclasses.replace(cfg, strong_cap=2 * cfg.strong_cap,
                                  weak_cap=0)
    from ..errors import CapOverflowError
    raise CapOverflowError(
        f"interaction lists overflow even at strong_cap={cfg.strong_cap}")
