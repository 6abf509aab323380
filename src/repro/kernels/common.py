"""Shared helpers for the Pallas TPU kernels.

TPU has no native complex arithmetic in Pallas, so every kernel operates on
separate real/imag f32 (or f64 in interpret mode) planes. Particle data is
staged into *dense per-leaf-box* arrays of shape (nbox+1, n_pad): row `nbox`
is an all-zero dummy row that -1 (masked) interaction-list entries are
redirected to, so the kernels never branch on list validity — a zero-strength
source contributes exactly zero. ``n_pad`` is the max leaf population rounded
up to the 128-lane width.

Every kernel grid is *batch-major* (DESIGN.md §2): operands carry a
leading problem axis B, the grid is ``(B, ntile, steps)`` with
``program_id(0)`` selecting the problem, and the interaction lists ride
in SMEM as one (B, nbox, S) scalar-prefetch operand whose BlockSpec
index maps take the batch coordinate first. B problems therefore
lengthen the grid without touching the per-step VMEM working set —
single-problem callers run the same kernels at B = 1, and
``jax.vmap`` of the per-problem wrappers lowers onto the batched grid
through their custom batching rules (see the ``*_op`` factories in each
kernel module).

"One launch" means one ``pallas_call`` in the program: it sits inside
``run_chunked`` and runs once per chunk of target tiles, so that each
run's scalar-prefetched slice of the lists fits SMEM — a single run for
small problems, several at N = 2**20.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.mathf import atan2, log, negate


#: Block index 0 for BlockSpec index maps. A bare Python ``0`` traces
#: as int64 under ``jax_enable_x64``, and Mosaic refuses 64-bit index
#: maps; every map returns this int32 zero instead.
ZERO = np.int32(0)


def default_interpret() -> bool:
    """Pallas interpret mode: True off-TPU (this container is CPU-only)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Auto-select interpret mode from the JAX platform when unset.

    Every kernel entry point takes ``interpret=None`` by default and
    resolves it here: compiled on a real TPU, interpreted elsewhere — so
    no caller has to thread the flag explicitly.
    """
    return default_interpret() if interpret is None else bool(interpret)


def pad_rows(a: jax.Array, nrows: int, value=0):
    """Pad a (rows, ...) array with ``value`` rows up to ``nrows``."""
    extra = nrows - a.shape[0]
    if extra == 0:
        return a
    widths = ((0, extra),) + ((0, 0),) * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=value)


def pad_boxes(a: jax.Array, nrows: int, value=0):
    """Pad the box axis (axis -2) of a batch-major array up to ``nrows``."""
    extra = nrows - a.shape[-2]
    if extra == 0:
        return a
    widths = ((0, 0),) * (a.ndim - 2) + ((0, extra), (0, 0))
    return jnp.pad(a, widths, constant_values=value)


def broadcast_unbatched(args, in_batched, axis_size: int):
    """Broadcast the unbatched operands of a custom-vmap rule to the full
    (B, ...) batch-major shape the kernels expect. Operands already
    carrying the mapped axis (moved to front by ``jax.custom_batching``)
    pass through untouched."""
    return [a if b else jnp.broadcast_to(a[None], (axis_size,) + a.shape)
            for a, b in zip(args, in_batched)]


def make_batched_op(batched_call):
    """Per-problem view of a batch-major kernel entry, with the custom
    batching rule that makes it batch-native.

    ``batched_call(*args)`` must take operands with a leading problem
    axis B and return a tuple of (B, ...) outputs. The returned op takes
    the same operands *without* the batch axis; calling it runs the
    kernel at B = 1, and ``jax.vmap`` of it lowers onto the batch-major
    grid directly — one launch for the whole batch — broadcasting any
    unbatched operands first. Kernels whose operand list varies by
    static config (m2l's log planes, the fused evaluation's m2p region)
    wrap their own rule instead.
    """
    @jax.custom_batching.custom_vmap
    def op(*args):
        outs = batched_call(*(a[None] for a in args))
        return tuple(o[0] for o in outs)

    @op.def_vmap
    def _rule(axis_size, in_batched, *args):
        outs = batched_call(*broadcast_unbatched(args, in_batched,
                                                 axis_size))
        return tuple(outs), tuple(True for _ in outs)

    return op


def row_view(a: jax.Array) -> jax.Array:
    """(B, rows, width) source plane -> (B, rows, 1, width).

    Mosaic takes a block only if its last two dims are (8, 128)-aligned
    or equal the array's own; a one-row (1, width) block of a
    (rows, width) plane is neither. With a singleton sublane axis the
    staged block's last two dims equal the array's, so one source row
    per list entry stays a legal DMA (see ``prefetch_row_specs``)."""
    return a[..., None, :]


def prefetch_row_specs(TB: int, SW: int, width: int):
    """One ``(None, None, 1, width)`` scalar-prefetch-indexed BlockSpec
    per staged source row on the batch-major grid, over a ``row_view``
    plane: spec (w, tb) DMAs the row of problem ``b`` named by list
    entry ``[b, i*TB + tb, s*SW + w]`` at grid step (b, i, s). The list
    itself is the first scalar-prefetch operand (``lref``, shape
    (B, ntile*TB, S_pad)); the squeezed batch and row dims leave the
    kernel body the same (1, width) rows as a single-problem launch."""

    def make_src_map(w, tb):
        def src_map(b, i, s, lref):
            return (b, lref[b, i * TB + tb, s * SW + w], ZERO, ZERO)
        return src_map

    return [pl.BlockSpec((None, None, 1, width), make_src_map(w, tb))
            for w in range(SW) for tb in range(TB)]


def slot_view(a: jax.Array, SW: int) -> jax.Array:
    """(B, rows, steps*SW) per-slot plane -> (B, steps, rows, SW).

    Step-major, so a grid step's (TB, SW) slot block spans the array's
    full last dim and stays legal for Mosaic at any ``stage_width``
    (see ``slot_spec``)."""
    B, rows, cols = a.shape
    return a.reshape(B, rows, cols // SW, SW).transpose(0, 2, 1, 3)


def slot_spec(TB: int, SW: int):
    """BlockSpec of the (TB, SW) slot block of grid step (b, i, s) over
    a ``slot_view`` plane."""
    def slot_map(b, i, s, lref):
        return (b, s, i, ZERO)
    return pl.BlockSpec((None, None, TB, SW), slot_map)


#: Bytes of interaction list one launch may hold in SMEM. Scalar
#: prefetch places the whole list operand there (1 MiB per TPU v5e
#: core, laid out in (8, 128) int32 tiles); a paper-scale problem's
#: lists run to several MiB, so the staged kernels launch once per chunk
#: of target tiles whose slice of the list fits this budget.
SMEM_LIST_BYTES = 256 * 1024


def staged_lists(lists_seq, dummy: int, TB: int, SW: int):
    """Stage one or more interaction lists for a batch-major grid.

    Each (B, nbox, S_k) region is dummy-redirected (masked -1 entries
    point at the all-zero ``dummy`` row) and padded to a multiple of
    ``SW`` so it owns a whole number of grid steps; the regions are
    concatenated along the slot axis (one fused grid) and the box axis
    is padded to ``nchunk`` equal chunks of whole ``TB``-box tiles, each
    chunk's list small enough for SMEM (``SMEM_LIST_BYTES``).

    Returns ``(combined, nchunk, region_steps)``: ``combined`` is
    (B, rows, cols) with rows a multiple of ``nchunk * TB`` — callers pad
    their target operands to the same rows — and ``region_steps[k]`` is
    the number of SW-wide grid steps of region k (a fused kernel
    branches on ``pl.program_id(2)`` against the running offsets).
    """
    B, nbox = lists_seq[0].shape[:2]
    regions, steps = [], []
    for lists in lists_seq:
        S = lists.shape[-1]
        S_pad = round_up(S, SW)
        l = jnp.where(lists >= 0, lists, dummy)
        l = jnp.pad(l, ((0, 0), (0, 0), (0, S_pad - S)),
                    constant_values=dummy)
        regions.append(l)
        steps.append(S_pad // SW)
    cols = sum(steps) * SW
    ntile = -(-nbox // TB)
    tile_bytes = 4 * B * TB * round_up(cols, 128)
    per = max(1, min(ntile, SMEM_LIST_BYTES // tile_bytes))
    nchunk = -(-ntile // per)
    combined = pad_boxes(jnp.concatenate(regions, axis=-1),
                         nchunk * per * TB, dummy)
    return combined, nchunk, steps


def staged_grid_steps(lists_seq, dummy: int, TB: int, SW: int):
    """The grid steps of each region that ``staged_lists`` stages from
    the same arguments, and how many of them are empty: every one of
    their TB x SW slots names the ``dummy`` row, so the step only stages
    zeros (cap padding of the lists, or the box padding of the tiles).

    Returns ``[(steps, empty), ...]``, one pair per region: ``steps`` a
    Python int (B x tiles x the region's steps), ``empty`` an int32
    scalar."""
    combined, _, region_steps = staged_lists(lists_seq, dummy, TB, SW)
    B, rows, cols = combined.shape
    live = (combined != dummy).reshape(B, rows // TB, TB, cols // SW,
                                       SW).any(axis=(2, 4))
    counts, start = [], 0
    for k in region_steps:
        empty = jnp.sum(~live[..., start:start + k], dtype=jnp.int32)
        counts.append((B * (rows // TB) * k, empty))
        start += k
    return counts


def run_chunked(launch, nchunk: int, operands):
    """``launch(*chunk)`` over ``nchunk`` equal slices of the box axis
    (axis 1) of every operand, in sequence; the outputs' chunks are
    stitched back along axis 1. ``operands`` lead with the staged list,
    so each launch scalar-prefetches only its own chunk of it."""
    def split(a):
        B, rows = a.shape[:2]
        return jnp.moveaxis(
            a.reshape(B, nchunk, rows // nchunk, *a.shape[2:]), 1, 0)

    def merge(a):
        n, B, rows = a.shape[:3]
        return jnp.moveaxis(a, 0, 1).reshape(B, n * rows, *a.shape[3:])

    outs = jax.lax.map(lambda xs: launch(*xs), [split(a) for a in operands])
    return [merge(o) for o in outs]


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def planes(z: jax.Array):
    return jnp.real(z), jnp.imag(z)


def dense_leaf_arrays(z: jax.Array, q: jax.Array, idx: np.ndarray,
                      n_pad: int):
    """Gather rank-sorted particles into (nbox+1, n_pad) dense planes.

    Returns (zr, zi, qr, qi, tmask) where the trailing dummy row is zero and
    padded slots carry q = 0 (and are additionally masked out of *target*
    positions by ``tmask``).
    """
    nbox, n_max = idx.shape
    pad_cols = n_pad - n_max
    idxj = jnp.asarray(idx)
    valid = idxj >= 0
    safe = jnp.where(valid, idxj, 0)
    zr = jnp.where(valid, jnp.real(z)[safe], 0.0)
    zi = jnp.where(valid, jnp.imag(z)[safe], 0.0)
    qr = jnp.where(valid, jnp.real(q)[safe], 0.0)
    qi = jnp.where(valid, jnp.imag(q)[safe], 0.0)

    def pack(a):
        a = jnp.pad(a, ((0, 1), (0, pad_cols)))
        return a

    return pack(zr), pack(zi), pack(qr), pack(qi), jnp.pad(valid, ((0, 1), (0, pad_cols)))


def launch_name(base: str, kernel: str) -> str:
    """A launch's name under G-kernel ``kernel``: ``base`` for the harmonic
    kernel, ``<base>_<kernel>`` otherwise, so a trace tells the variants
    apart. The launch's ``jax.named_scope`` and its ``pallas_call(name=)``
    both take it."""
    return base if kernel == "harmonic" else f"{base}_{kernel}"


def pairwise_tile(kernel: str, tzr, tzi, trk, szr, szi, qr, qi, srk):
    """One staged P2P source tile against the resident targets.

    All inputs (TB, n_pad); returns the (TB, n_pad) (real, imag)
    contribution to accumulate. Shared by the standalone P2P kernel and
    the fused evaluation megakernel so the kernel math (including the
    rank-based self-exclusion) has exactly one definition.
    """
    dx = szr[:, None, :] - tzr[:, :, None]   # (TB, n_t, n_s): z_src - z_tgt
    dy = szi[:, None, :] - tzi[:, :, None]
    qr, qi = qr[:, None, :], qi[:, None, :]
    d2 = dx * dx + dy * dy
    # self-interaction excluded by particle identity (global rank), never
    # by position: distinct coincident particles interact (singular
    # contribution — the correct sum_{j != i} semantics).
    ok = (srk[:, None, :] >= 0) & (srk[:, None, :] != trk[:, :, None])
    if kernel == "harmonic":
        # q / (dx + i dy) = q * (dx - i dy) / |d|^2
        inv = jnp.where(ok, 1.0 / d2, 0.0)
        return (((qr * dx + qi * dy) * inv).sum(axis=-1),
                ((qi * dx - qr * dy) * inv).sum(axis=-1))
    # q * log(z_t - z_s) = q * (log|d| + i*arg(-dx, -dy))
    lr = jnp.where(ok, 0.5 * log(d2), 0.0)
    li = jnp.where(ok, atan2(negate(dy), negate(dx)), 0.0)
    return ((qr * lr - qi * li).sum(axis=-1),
            (qr * li + qi * lr).sum(axis=-1))


def l2p_horner(p: int, br_ref, bi_ref, tr, ti):
    """Local-expansion Horner at pre-centered particles.

    br_ref/bi_ref: (TB, P) coefficient block (ref or array; read as
    per-row (TB, 1) columns at static lane indices); tr/ti: (TB, n_pad).
    Returns the (TB, n_pad) (real, imag) potential. Shared by the L2P
    kernel and the fused evaluation megakernel's output seed.
    """
    accr = jnp.zeros_like(tr) + br_ref[:, p:p + 1]
    acci = jnp.zeros_like(ti) + bi_ref[:, p:p + 1]
    for j in range(p - 1, -1, -1):
        nr = accr * tr - acci * ti + br_ref[:, j:j + 1]
        ni = accr * ti + acci * tr + bi_ref[:, j:j + 1]
        accr, acci = nr, ni
    return accr, acci


def dense_rank_planes(idx: np.ndarray, n_pad: int) -> jax.Array:
    """(nbox+1, n_pad) int32 global particle ranks per dense leaf slot.

    Padded slots and the trailing dummy row carry -1, so rank equality
    against a valid target rank is never spuriously true — this is the
    plane the kernels compare to exclude self-interaction *by particle
    identity* (rank i == rank j), not by position coincidence, so
    distinct particles at duplicated positions still interact (their
    mutual contribution is the kernel singularity, by definition of
    phi_i = sum_{j != i} G(z_i, x_j)).
    """
    nbox, n_max = idx.shape
    return jnp.pad(jnp.asarray(idx, jnp.int32),
                   ((0, 1), (0, n_pad - n_max)), constant_values=-1)


def scatter_from_leaves(values: jax.Array, idx: np.ndarray, n: int):
    """Scatter (nbox, n_pad)->(n,) rank order; padded slots masked to rank 0."""
    nbox, n_max = idx.shape
    vals = values[:, :n_max].reshape(-1)
    flat_idx = jnp.asarray(idx).reshape(-1)
    ok = flat_idx >= 0
    out = jnp.zeros((n,), values.dtype)
    return out.at[jnp.where(ok, flat_idx, 0)].add(jnp.where(ok, vals, 0.0))
