"""Asymmetric adaptive FMM tree (paper §2, [7]) — single-sort build.

Boxes are split at the particle *median*, twice per level, along the most
eccentric axis -> a perfectly balanced 4-ary pyramid. Because splits happen
at exact ranks, box b at level l owns the contiguous rank-slice
``[bounds[l][b], bounds[l][b+1])`` where the bounds depend only on (N, l):
a *static memory layout*, which is the property the whole GPU (here: TPU)
implementation is organized around.

Single-sort scheme (DESIGN.md §8): the seed implementation re-sorted the
full particle array once per split — ``2*nlevels`` O(N log N) lexsorts.
This build sorts exactly **once** — one stable sort of the stacked (2, N)
coordinate rows, an argsort per coordinate in a single op — and then
maintains, through every split, two id arrays ``A_x``/``A_y`` that
are segment-contiguous at the static rank bounds and internally sorted by
x resp. y. Each median split is then O(N) sort-free work:

  * segment extents are *gathers of boundary elements* of A_x/A_y (the
    min/max of a sorted run are its endpoints), giving the eccentric-axis
    choice without a segmented reduction;
  * "goes left" is a static positional predicate in the chosen axis's
    array (the first ceil(n/2) entries of the segment), scattered to
    particle ids;
  * both arrays are *stable-partitioned* at the static median ranks with
    one cumulative sum — the classic presorted kd-tree construction,
    mapped to scatters so every step is an O(N) data-parallel primitive.

The final rank order equals the lexsort cascade's for inputs with
distinct coordinates (ties break by initial argsort order instead of the
evolving order — a measure-zero difference on continuous inputs); the
parity sweep in tests/test_topology.py checks bit-identical rank layout
against ``build_tree_lexsort``, the seed implementation kept as oracle.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from ..config import (FmmConfig, leaf_sizes, level_bounds, segment_ids,
                      split_bounds)


class Tree(NamedTuple):
    """Sorted particles + per-level box geometry. All shapes static."""

    perm: jax.Array          # (N,) int32; sorted_field[i] corresponds to input index perm[i]
    z: jax.Array             # (N,) complex, rank-sorted positions
    q: jax.Array             # (N,) complex, rank-sorted strengths
    centers: tuple[jax.Array, ...]   # level l: (4**l,) complex
    radii: tuple[jax.Array, ...]     # level l: (4**l,) real


def _seg_minmax(v: jax.Array, sid: jax.Array, nseg: int):
    mn = jax.ops.segment_min(v, sid, num_segments=nseg, indices_are_sorted=True)
    mx = jax.ops.segment_max(v, sid, num_segments=nseg, indices_are_sorted=True)
    return mn, mx


def _partition(order, left_of, starts_pos, mids_pos, offs_pos):
    """Stable-partition ``order`` within static segments by a per-id flag.

    ``order``: (N,) int32 particle ids, segment-contiguous at the static
    bounds and internally sorted by one coordinate. ``left_of``: (N,)
    int32 0/1 flag per particle *id* (not bool: the TPU compiler builds
    an int32 scatter of 2**20 entries in under a second, a bool one in
    tens of seconds). ``starts_pos``/``mids_pos``/``offs_pos``: (N,)
    per-position segment start / median rank / offset within the
    segment. Left entries keep their relative order in ``[start, mid)``,
    right entries in ``[mid, end)`` — so both coordinate orders survive
    every split without re-sorting.
    """
    f = left_of[order]
    # exclusive count of lefts before p; int32 even under x64
    lefts = jnp.cumsum(f, dtype=jnp.int32) - f
    seg_l = lefts - lefts[starts_pos]              # lefts before p in segment
    seg_r = offs_pos - seg_l                       # rights before p in segment
    dest = jnp.where(f > 0, starts_pos + seg_l, mids_pos + seg_r)
    return jnp.zeros_like(order).at[dest].set(order)


def build_tree(z: jax.Array, q: jax.Array, cfg: FmmConfig) -> Tree:
    """Sort particles into the static pyramid layout and compute geometry.

    One sort op regardless of depth — both coordinates' argsorts as the
    rows of one (2, N) stable sort, which the TPU compiler also builds in
    about a third of the time two 1-D sorts of 2**20 take; everything
    else is cumsum/gather/scatter. The jaxpr sort-count test in
    tests/test_topology.py pins this property.
    """
    rdt = cfg.real_dtype
    cdt = cfg.complex_dtype
    z = z.astype(cdt)
    q = q.astype(cdt)
    x = jnp.real(z).astype(rdt)
    y = jnp.imag(z).astype(rdt)
    N, L = cfg.n, cfg.nlevels

    if L == 0:
        perm = jnp.arange(N, dtype=jnp.int32)
    else:
        keys = jnp.stack([x, y])                   # (2, N)
        _, order = jax.lax.sort(
            (keys, jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)),
            dimension=1, num_keys=1, is_stable=True)
        ax, ay = order[0], order[1]                # argsort of x, of y
        sb = split_bounds(N, 2 * L)
        split_x = None
        # Per-position segment bookkeeping is derived in-graph from the
        # (2**s,)-entry bound tables: (N,)-sized numpy constants per
        # split would embed hundreds of MB into the program at N ~ 1e6.
        pos = jnp.arange(N, dtype=jnp.int32)
        sid_pos = jnp.zeros(N, jnp.int32)          # segment of each rank
        for s in range(2 * L):
            b = sb[s]
            mids = sb[s + 1][1::2]
            starts_pos = jnp.asarray(b[:-1], jnp.int32)[sid_pos]
            mids_pos = jnp.asarray(mids, jnp.int32)[sid_pos]
            offs_pos = pos - starts_pos
            # sorted-run endpoints ARE the segment extents: 2 gathers/axis
            jst = jnp.asarray(b[:-1], jnp.int32)
            jla = jnp.asarray(b[1:] - 1, jnp.int32)
            xmn, xmx = x[ax[jst]], x[ax[jla]]
            ymn, ymx = y[ay[jst]], y[ay[jla]]
            split_x = (xmx - xmn) >= (ymx - ymn)           # (2**s,)
            # positional "first half of my segment" flag, static per rank
            pos_left = (pos < mids_pos).astype(jnp.int32)
            xleft = jnp.zeros(N, jnp.int32).at[ax].set(pos_left)
            yleft = jnp.zeros(N, jnp.int32).at[ay].set(pos_left)
            sid_of_id = jnp.zeros(N, jnp.int32).at[ax].set(sid_pos)
            goes_left = jnp.where(split_x[sid_of_id], xleft, yleft)
            ax = _partition(ax, goes_left, starts_pos, mids_pos, offs_pos)
            ay = _partition(ay, goes_left, starts_pos, mids_pos, offs_pos)
            # children of segment k are 2k (left half) and 2k+1
            sid_pos = 2 * sid_pos + 1 - pos_left
        # Final rank order within each leaf = ascending in the axis its
        # parent split on (what the lexsort cascade leaves behind): both
        # id arrays are leaf-contiguous at the same static bounds, so the
        # choice is a positionwise select.
        choose_x = split_x[sid_pos // 2]
        perm = jnp.where(choose_x, ax, ay)

    xs, ys = x[perm], y[perm]
    z_sorted = (xs + 1j * ys).astype(cdt)
    q_sorted = q[perm]
    centers, radii = _level_geometry(xs, ys, cfg)
    return Tree(perm=perm, z=z_sorted, q=q_sorted,
                centers=centers, radii=radii)


def _level_geometry(xs, ys, cfg: FmmConfig):
    """Shrink-to-fit centers/radii for every level from ONE segmented pass.

    The four segmented min/max reductions run once, over the leaf boxes;
    every coarser level's extents are 4-child min/max reductions of the
    (4**l,) level arrays (exact: min over a box == min of its children's
    mins), so the O(N) geometry work is not repeated per level.
    """
    rdt, cdt = cfg.real_dtype, cfg.complex_dtype
    lid = jnp.asarray(leaf_ids(cfg))
    nb = 4 ** cfg.nlevels
    xmn, xmx = _seg_minmax(xs, lid, nb)
    ymn, ymx = _seg_minmax(ys, lid, nb)
    centers: list = [None] * (cfg.nlevels + 1)
    radii: list = [None] * (cfg.nlevels + 1)
    for l in range(cfg.nlevels, -1, -1):
        cx = 0.5 * (xmn + xmx)
        cy = 0.5 * (ymn + ymx)
        centers[l] = (cx + 1j * cy).astype(cdt)
        radii[l] = (0.5 * jnp.hypot(xmx - xmn, ymx - ymn)).astype(rdt)
        if l > 0:
            xmn = xmn.reshape(-1, 4).min(axis=1)
            xmx = xmx.reshape(-1, 4).max(axis=1)
            ymn = ymn.reshape(-1, 4).min(axis=1)
            ymx = ymx.reshape(-1, 4).max(axis=1)
    return tuple(centers), tuple(radii)


def build_tree_lexsort(z: jax.Array, q: jax.Array, cfg: FmmConfig) -> Tree:
    """Seed implementation (one full lexsort per split), kept as the
    parity oracle for ``build_tree`` — see tests/test_topology.py."""
    rdt = cfg.real_dtype
    cdt = cfg.complex_dtype
    z = z.astype(cdt)
    q = q.astype(cdt)
    x = jnp.real(z).astype(rdt)
    y = jnp.imag(z).astype(rdt)
    perm = jnp.arange(cfg.n, dtype=jnp.int32)

    sb = split_bounds(cfg.n, 2 * cfg.nlevels)
    for s in range(2 * cfg.nlevels):
        nseg = 2**s
        sid = jnp.asarray(segment_ids(sb[s]))
        xmn, xmx = _seg_minmax(x, sid, nseg)
        ymn, ymx = _seg_minmax(y, sid, nseg)
        split_x = (xmx - xmn) >= (ymx - ymn)
        coord = jnp.where(split_x[sid], x, y)
        order = jnp.lexsort((coord, sid))
        x, y, perm = x[order], y[order], perm[order]

    z_sorted = (x + 1j * y).astype(cdt)
    q_sorted = q[perm]

    centers = []
    radii = []
    lb = level_bounds(cfg)
    for l in range(cfg.nlevels + 1):
        nseg = 4**l
        sid = jnp.asarray(segment_ids(lb[l]))
        xmn, xmx = _seg_minmax(x, sid, nseg)
        ymn, ymx = _seg_minmax(y, sid, nseg)
        cx = 0.5 * (xmn + xmx)
        cy = 0.5 * (ymn + ymx)
        centers.append((cx + 1j * cy).astype(cdt))
        radii.append((0.5 * jnp.hypot(xmx - xmn, ymx - ymn)).astype(rdt))

    return Tree(perm=perm, z=z_sorted, q=q_sorted,
                centers=tuple(centers), radii=tuple(radii))


def leaf_particle_index(cfg: FmmConfig) -> np.ndarray:
    """(4**L, n_max) int32 gather map leaf-box -> particle ranks, -1 padded.

    Purely static (depends only on N and nlevels) — this is the paper's
    "static layout of memory" made literal: the map is a numpy constant
    baked into the compiled program. Built by broadcasting the leaf rank
    bounds against a column index (no per-box Python loop).
    """
    lb = level_bounds(cfg)[-1]
    sizes = np.diff(lb)
    n_max = int(sizes.max())
    col = np.arange(n_max, dtype=np.int64)
    idx = lb[:-1, None] + col[None, :]
    return np.where(col[None, :] < sizes[:, None], idx, -1).astype(np.int32)


def leaf_planes(tree: Tree, cfg: FmmConfig) -> tuple[jax.Array, jax.Array]:
    """Rank-sorted (z, q) laid out as (4**L, n_max) leaf planes.

    Leaf b's row holds its rank slice ``[lb[b], lb[b+1])``. When every
    leaf has the same size the slices tile the ranks, and the planes are
    a reshape (no gather, no scatter); otherwise one gather through
    ``leaf_particle_index``, whose padded slots get q = 0 and the leaf's
    center as position, so powers of (z - center) stay finite.
    """
    sizes = leaf_sizes(cfg)
    if sizes.min() == sizes.max():
        return tree.z.reshape(len(sizes), -1), tree.q.reshape(len(sizes), -1)
    idx = leaf_particle_index(cfg)
    valid = jnp.asarray(idx >= 0)
    safe = jnp.asarray(np.maximum(idx, 0))
    z = jnp.where(valid, tree.z[safe], tree.centers[cfg.nlevels][:, None])
    q = jnp.where(valid, tree.q[safe], 0)
    return z, q


def leaf_particle_index_loop(cfg: FmmConfig) -> np.ndarray:
    """Seed O(4**L) Python-loop construction, kept as parity oracle."""
    lb = level_bounds(cfg)[-1]
    sizes = np.diff(lb)
    n_max = int(sizes.max())
    nbox = len(sizes)
    idx = np.full((nbox, n_max), -1, dtype=np.int32)
    for b in range(nbox):
        idx[b, : sizes[b]] = np.arange(lb[b], lb[b + 1], dtype=np.int32)
    return idx


def leaf_ids(cfg: FmmConfig) -> np.ndarray:
    """(N,) int32: leaf box owning each rank."""
    return segment_ids(level_bounds(cfg)[-1])
