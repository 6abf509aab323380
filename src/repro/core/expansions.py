"""Multipole/local expansions and translation operators (2D, complex plane).

Conventions (paper §2, eqs (2.2)-(2.3)):

  multipole around z0:  M(z) = a_0 log(z - z0) + sum_{j=1..p} a_j (z - z0)^{-j}
  local     around z0:  L(z) = sum_{j=0..p} b_j (z - z0)^j

Kernels:
  "harmonic": G(z, x) = q / (x - z)          (paper eq. (5.1); a_0 = 0)
  "log":      G(z, x) = q * log(z - x)       (potential is Re-valued;
                                              branch cuts only affect Im)

Two implementations of each translation:

  *_horner : the paper's Algorithms 3.4(b) / 3.5 / 3.6 — scaled
             Pascal-triangle accumulation, no binomial tables. Kept as the
             paper-faithful baseline and as the oracle for the Pallas
             kernels.
  *_apply  : TPU-native factorization  diag-scale -> constant (p+1)^2
             matrix multiply -> diag-scale.  The constant matrices are
             binomial (Pascal / Hankel) tables; the per-shift work becomes
             a batched GEMM on the MXU.  Mathematically identical.

All ops are batched over arbitrary leading axes; coefficient arrays have
shape (..., p+1) and shift offsets shape (...).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .mathf import clog

#: Precision of every matrix product here: XLA's default f32 matmul on a
#: TPU rounds operands to bf16, which costs the f32 FMM two orders of
#: magnitude of accuracy at p = 17 (the binomial tables reach ~1e9).
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# constant binomial matrices (numpy, float64; cast at use site)
# --------------------------------------------------------------------------

def _binom_table(n: int) -> np.ndarray:
    c = np.zeros((n + 1, n + 1))
    c[:, 0] = 1.0
    for i in range(1, n + 1):
        for j in range(1, i + 1):
            c[i, j] = c[i - 1, j - 1] + c[i - 1, j]
    return c


def m2m_matrix(p: int) -> np.ndarray:
    """A with b_hat = A @ a_hat;  a_hat_j = a_j t^-j, b_hat_l = b_l t^-l,
    t = z_child - z_parent.  A[l,j] = C(l-1, j-1) for 1<=j<=l; the a_0
    (log-source) column is A[l,0] = -1/l; A[0,0] = 1."""
    c = _binom_table(p)
    a = np.zeros((p + 1, p + 1))
    a[0, 0] = 1.0
    for l in range(1, p + 1):
        a[l, 0] = -1.0 / l
        for j in range(1, l + 1):
            a[l, j] = c[l - 1, j - 1]
    return a


def m2l_matrix(p: int) -> np.ndarray:
    """H with b_hat = H @ a_hat; a_hat_k = a_k r^-k, b_l = b_hat_l (-1)^l r^-l
    (l>=1), b_0 = b_hat_0 + a_0 log r;  r = z_target - z_source.
    H[l,k] = C(l+k-1, k-1) for l>=1,k>=1; H[0,k]=1 (k>=1); H[l,0] = -1/l."""
    c = _binom_table(2 * p)
    h = np.zeros((p + 1, p + 1))
    for k in range(1, p + 1):
        h[0, k] = 1.0
    for l in range(1, p + 1):
        h[l, 0] = -1.0 / l
        for k in range(1, p + 1):
            h[l, k] = c[l + k - 1, k - 1]
    return h


def l2l_matrix(p: int) -> np.ndarray:
    """B with c_hat = B @ b_hat; b_hat_j = b_j s^j, c_hat_l = c_l s^l,
    s = z_child - z_parent.  B[l,j] = C(j, l) for j>=l."""
    c = _binom_table(p)
    b = np.zeros((p + 1, p + 1))
    for l in range(p + 1):
        for j in range(l, p + 1):
            b[l, j] = c[j, l]
    return b


# --------------------------------------------------------------------------
# power helpers
# --------------------------------------------------------------------------

def pows(r: jax.Array, p: int) -> jax.Array:
    """[r^0, r^1, ..., r^p] stacked on a new trailing axis."""
    out = [jnp.ones_like(r)]
    for _ in range(p):
        out.append(out[-1] * r)
    return jnp.stack(out, axis=-1)


def inv_pows(r: jax.Array, p: int) -> jax.Array:
    return pows(1.0 / r, p)


# --------------------------------------------------------------------------
# matrix ("mxu") forms
# --------------------------------------------------------------------------

def m2m_apply(a: jax.Array, t: jax.Array, mat: jax.Array) -> jax.Array:
    """Shift multipole coefficients by t = z_child - z_parent."""
    p = a.shape[-1] - 1
    ti = inv_pows(t, p)
    a_hat = a * ti
    b_hat = jnp.einsum("...j,lj->...l", a_hat, mat, precision=HIGHEST)
    return b_hat * pows(t, p)


def m2l_apply(a: jax.Array, r: jax.Array, mat: jax.Array) -> jax.Array:
    """Multipole around z_source -> local around z_target; r = z_t - z_s."""
    p = a.shape[-1] - 1
    a_hat = a * inv_pows(r, p)
    b_hat = jnp.einsum("...k,lk->...l", a_hat, mat, precision=HIGHEST)
    b = b_hat * inv_pows(-r, p)
    # log-source correction on the constant term
    return b.at[..., 0].add(a[..., 0] * clog(r))


def l2l_apply(b: jax.Array, s: jax.Array, mat: jax.Array) -> jax.Array:
    """Shift local coefficients by s = z_child - z_parent."""
    p = b.shape[-1] - 1
    b_hat = b * pows(s, p)
    c_hat = jnp.einsum("...j,lj->...l", b_hat, mat, precision=HIGHEST)
    return c_hat * inv_pows(s, p)


# --------------------------------------------------------------------------
# paper-faithful scaled-Horner forms (Algorithms 3.4(b), 3.5, 3.6)
# --------------------------------------------------------------------------

def m2m_horner(a: jax.Array, t: jax.Array) -> jax.Array:
    """Algorithm 3.4(b). t = z_child - z_parent (paper's r)."""
    p = a.shape[-1] - 1
    rinv = 1.0 / t
    c = [a[..., j] for j in range(p + 1)]
    w = jnp.ones_like(t)
    for j in range(1, p + 1):            # pre-scale: a_j /= r^j
        w = w * rinv
        c[j] = c[j] * w
    for k in range(p, 1, -1):            # Pascal accumulation (sequential j)
        for j in range(k, p + 1):
            c[j] = c[j] + c[j - 1]
    w = jnp.ones_like(t)
    out = [c[0]]
    for j in range(1, p + 1):            # post-scale + log-source correction
        w = w * t
        out.append((c[j] - c[0] / j) * w)
    return jnp.stack(out, axis=-1)


def l2l_horner(b: jax.Array, s: jax.Array) -> jax.Array:
    """Algorithm 3.5. Paper's r = z_parent - z_child = -s."""
    p = b.shape[-1] - 1
    r = -s
    c = [b[..., j] for j in range(p + 1)]
    w = jnp.ones_like(r)
    for j in range(1, p + 1):            # pre-scale: b_j *= r^j
        w = w * r
        c[j] = c[j] * w
    for k in range(p + 1):               # inner loop is order-independent
        for j in range(p - k, p):
            c[j] = c[j] - c[j + 1]
    w = jnp.ones_like(r)
    out = [c[0]]
    for j in range(1, p + 1):            # post-scale: b_j /= r^j
        w = w * r
        out.append(c[j] / w)
    return jnp.stack(out, axis=-1)


def m2l_horner(a: jax.Array, r: jax.Array) -> jax.Array:
    """Algorithm 3.6. r = z_target - z_source (paper's z_o - z_i).

    Note on signs: the published pseudocode's (-1)^j factors assume the
    opposite shift direction (r = z_i - z_o). With our r the map reduces to
    the all-positive Pascal chain below, with the alternating sign folded
    into the (-r)^-j post-scale. Verified identical to the binomial-matrix
    oracle ``m2l_apply`` (see tests/test_expansions.py): the two reductions
    compute the L·Lᵀ factorization of the Hankel matrix C(l+k-1, k-1)
    (Vandermonde identity), which is the combination the paper notes it had
    "not seen described elsewhere".
    """
    p = a.shape[-1] - 1
    rinv = 1.0 / r
    b = [jnp.zeros_like(a[..., 0]) for _ in range(p + 1)]
    w = jnp.ones_like(r)
    for j in range(1, p + 1):            # b_{j-1} := a_j / r^j
        w = w * rinv
        b[j - 1] = a[..., j] * w
    # first reduction (L2L-style; inner loop order-independent): L^T
    for k in range(2, p + 1):
        for j in range(p - k, p):
            b[j] = b[j] + b[j + 1]
    # second reduction (M2M-style; inner loop sequential): L
    for k in range(p, 0, -1):
        for j in range(k, p + 1):
            b[j] = b[j] + b[j - 1]
    a0 = a[..., 0]
    w = jnp.ones_like(r)
    out = [b[0] + a0 * clog(r)]
    for j in range(1, p + 1):
        w = w * (-rinv)
        out.append((b[j] - a0 / j) * w)
    return jnp.stack(out, axis=-1)


# --------------------------------------------------------------------------
# direct expansion constructors / evaluators (single box; used by tests,
# refs and the pointwise P2M/P2L/L2P/M2P sweeps in fmm.py)
# --------------------------------------------------------------------------

def p2m_single(x: jax.Array, q: jax.Array, z0: jax.Array, p: int,
               kernel: str) -> jax.Array:
    """Multipole coefficients of sources x (strengths q) around z0."""
    t = x - z0
    if kernel == "harmonic":
        # q/(x - z) = -q * sum_k (x-z0)^k (z-z0)^-(k+1)  =>  a_j = -sum q t^(j-1)
        coeffs = [jnp.sum(q) * 0]  # a_0 = 0
        w = q
        for _ in range(p):
            coeffs.append(-jnp.sum(w))
            w = w * t
        return jnp.stack(coeffs, axis=-1)
    elif kernel == "log":
        # q log(z - x): a_0 = sum q; a_j = -sum q t^j / j
        coeffs = [jnp.sum(q)]
        w = q
        for j in range(1, p + 1):
            w = w * t
            coeffs.append(-jnp.sum(w) / j)
        return jnp.stack(coeffs, axis=-1)
    raise ValueError(kernel)


def p2l_single(x: jax.Array, q: jax.Array, z0: jax.Array, p: int,
               kernel: str) -> jax.Array:
    """Local coefficients around z0 from *far* sources x (strengths q)."""
    w = 1.0 / (x - z0)
    if kernel == "harmonic":
        # q/(x - z) = q sum_l (z-z0)^l (x-z0)^-(l+1)  =>  b_l = sum q w^(l+1)
        pw = q * w
        coeffs = []
        for _ in range(p + 1):
            coeffs.append(jnp.sum(pw))
            pw = pw * w
        return jnp.stack(coeffs, axis=-1)
    elif kernel == "log":
        # q log(z - x) = q log(z0 - x) - q sum_l ((z-z0) w)^l / l
        coeffs = [jnp.sum(q * clog(z0 - x))]
        pw = q * w
        for l in range(1, p + 1):
            coeffs.append(-jnp.sum(pw) / l)
            pw = pw * w
        return jnp.stack(coeffs, axis=-1)
    raise ValueError(kernel)


def eval_multipole(a: jax.Array, z0: jax.Array, z: jax.Array) -> jax.Array:
    """M(z) for coefficients a around z0 (Horner in 1/(z-z0))."""
    p = a.shape[-1] - 1
    w = 1.0 / (z - z0)
    acc = jnp.zeros_like(z) + a[..., p]
    for j in range(p - 1, 0, -1):
        acc = acc * w + a[..., j]
    acc = acc * w
    return acc + a[..., 0] * clog(z - z0)


def eval_local(b: jax.Array, z0: jax.Array, z: jax.Array) -> jax.Array:
    """L(z) for coefficients b around z0 (Horner)."""
    p = b.shape[-1] - 1
    t = z - z0
    acc = jnp.zeros_like(z) + b[..., p]
    for j in range(p - 1, -1, -1):
        acc = acc * t + b[..., j]
    return acc


# --------------------------------------------------------------------------
# radius-normalized forms (beyond-paper numerical upgrade, DESIGN.md §2/§7)
#
# Coefficients are stored scaled by the owning box's effective radius:
#   multipole:  a~_j = a_j * rho^-j      local:  b~_l = b_l * rho^l
# Every translation then only multiplies by bounded ratios (|t|/rho_parent,
# rho_child/rho_parent, rho/r with r the pair separation), so no power of a
# small length is ever inverted — the plain scaled forms overflow f32 for
# any tree deeper than ~5 levels (|t|^-p with |t| ~ 2^-depth) and f64 in
# degenerate shrink-to-fit geometries. M2L keeps the constant Hankel matrix
# (MXU path); M2M/L2L become multiplier-Horner passes (they are <1% of the
# work, paper Table 5.1).
# --------------------------------------------------------------------------

def p2m_norm(w: jax.Array, q: jax.Array, inv_rho, p: int, kernel: str,
             seg_sum) -> jax.Array:
    """Normalized P2M. w = (x - z0)/rho per particle; seg_sum reduces a
    per-particle vector to per-box. Returns (nbox, p+1) scaled coeffs."""
    coeffs = []
    if kernel == "harmonic":
        coeffs.append(seg_sum(q) * 0)
        pw = q
        for _ in range(p):
            coeffs.append(-seg_sum(pw) * inv_rho)
            pw = pw * w
    else:
        coeffs.append(seg_sum(q))
        pw = q
        for j in range(1, p + 1):
            pw = pw * w
            coeffs.append(-seg_sum(pw) / j)
    return jnp.stack(coeffs, axis=-1)


def _shift_operator(mat: np.ndarray, x: jax.Array) -> jax.Array:
    """Per-box (..., p+1, p+1) shift operator ``mat[l, j] * x**|l - j|``.

    ``mat`` is a triangular binomial table (lower: M2M, upper: L2L), so
    only non-negative powers of the bounded ratio ``x`` occur and the
    operator stays finite for coincident centers (x -> 0) — the property
    the normalized scaled-Horner passes have — while the whole shift is
    one gather of the power table and one multiply-reduce: a few HLO ops
    per level instead of O(p**2) unrolled passes (which take the TPU
    compiler minutes to schedule at p = 17)."""
    p = mat.shape[0] - 1
    l, j = np.indices(mat.shape)
    power = pows(x, p)[..., np.abs(l - j)]
    return power * mat.astype(power.real.dtype)


def _apply_operator(op: jax.Array, c: jax.Array) -> jax.Array:
    """out[..., l] = sum_j op[..., l, j] c[..., j] — elementwise on the
    vector unit, exact in the working dtype (no MXU matmul precision)."""
    return (op * c[..., None, :]).sum(axis=-1)


def m2m_norm(a: jax.Array, u: jax.Array, ratio: jax.Array) -> jax.Array:
    """Normalized M2M: u = t/rho_parent, ratio = rho_child/rho_parent.

    out_l = sum_j A[l, j] u**(l-j) a_j ratio**j with A = ``m2m_matrix``
    (Pascal columns plus the a_0 log-source column)."""
    p = a.shape[-1] - 1
    c = a * pows(ratio, p).astype(a.dtype)
    return _apply_operator(_shift_operator(m2m_matrix(p), u), c)


def l2l_norm(b: jax.Array, v: jax.Array, ratio: jax.Array) -> jax.Array:
    """Normalized L2L: v = s/rho_parent, ratio = rho_child/rho_parent.

    out_l = ratio**l sum_j C(j, l) v**(j-l) b_j (``l2l_matrix``)."""
    p = b.shape[-1] - 1
    c = _apply_operator(_shift_operator(l2l_matrix(p), v), b)
    return c * pows(ratio, p).astype(b.dtype)


def m2l_norm(a: jax.Array, r: jax.Array, rho_s: jax.Array,
             rho_t: jax.Array, mat: jax.Array) -> jax.Array:
    """Normalized M2L (constant Hankel matrix preserved — the MXU path).

    r = z_target - z_source; all scale vectors are powers of rho/r ratios
    bounded by the theta-criterion."""
    p = a.shape[-1] - 1
    pre = pows(rho_s / r, p)
    pre = pre.at[..., 0].set(1.0)        # a~_0 = a_0 (log strength)
    a_hat = a * pre
    b_hat = jnp.einsum("...k,lk->...l", a_hat, mat, precision=HIGHEST)
    b = b_hat * pows(-rho_t / r, p)
    return b.at[..., 0].add(a[..., 0] * clog(r))


def m2l_norm_horner(a: jax.Array, r: jax.Array, rho_s: jax.Array,
                    rho_t: jax.Array) -> jax.Array:
    """Normalized Algorithm 3.6 (positive-Pascal chain, cf. m2l_horner)."""
    p = a.shape[-1] - 1
    ws = rho_s / r
    b = [jnp.zeros_like(a[..., 0]) for _ in range(p + 1)]
    w = jnp.ones_like(r)
    for j in range(1, p + 1):
        w = w * ws
        b[j - 1] = a[..., j] * w
    for k in range(2, p + 1):
        for j in range(p - k, p):
            b[j] = b[j] + b[j + 1]
    for k in range(p, 0, -1):
        for j in range(k, p + 1):
            b[j] = b[j] + b[j - 1]
    a0 = a[..., 0]
    wt = -rho_t / r
    w = jnp.ones_like(r)
    out = [b[0] + a0 * clog(r)]
    for j in range(1, p + 1):
        w = w * wt
        out.append((b[j] - a0 / j) * w)
    return jnp.stack(out, axis=-1)
