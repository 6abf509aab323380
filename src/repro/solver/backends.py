"""Per-phase backend registry for the FMM hot paths.

The pipeline in ``repro.core.fmm`` exposes seven override hooks — the
near-field P2P sweep, the level M2L translation (per-level or fused
across all levels in one launch), the leaf L2P evaluation, the downward
P2L shift, the fused whole-evaluation-phase hook (L2P + M2P + P2P in
one launch; the evaluation phase is ~56% of the paper's GPU runtime,
Table 5.1), and the topology phase's leaf-level classification
(``fmm_build``'s ``leaf_classify_impl``). A ``Backend`` bundles one
implementation per hook; the
registry maps names to backends so callers (``FmmSolver``, benchmarks,
tests) pick by string:

  "reference"  pure-jnp oracles from ``repro.core.fmm`` (every hook None
               -> the core path runs its own sweep)
  "pallas"     the Pallas TPU kernels from ``repro.kernels`` (interpret
               mode off-TPU); both G-kernels (harmonic and log), the
               downward M2L fused into a single launch, P2L as a kernel,
               and the whole evaluation phase as ONE fused launch — no
               phase of the default config falls back to a jnp sweep
  "auto"       "pallas" on a TPU backend, "reference" otherwise —
               interpret-mode Pallas on CPU is a correctness tool, not a
               fast path

Each backend also declares its **batched-dispatch contract**
(``batched_dispatch``) — how ``FmmSolver.apply_batched`` may serve B
problems per call through its hooks:

  "native"     the hooks contain batch-native kernels with custom
               batching rules: ``jax.vmap`` lowers onto batch-major
               (B, ...) kernel grids, one launch per phase for the whole
               batch (the pallas backend)
  "vmap"       plain jnp hooks that batch under ``jax.vmap`` as-is (the
               reference backend; the default for new backends)
  "fallback"   hooks that cannot batch at all — the solver downgrades
               the batched entry point to the reference sweeps and warns

Third parties register additional backends with ``register_backend`` —
e.g. a shard_map multi-chip variant — without touching the dispatch
sites; a backend whose kernels lack batching rules declares
``batched_dispatch="fallback"``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax

from ..core.config import FmmConfig
from ..errors import DTypeError

# Hook signatures (matching repro.core.fmm.fmm_evaluate):
#   p2p(tree, conn, cfg, idx)            -> (n,) complex contribution
#   m2l(mult, weak, centers, cfg, rho)   -> (nbox, p+1) complex
#   m2l_fused(mult, weak, centers, cfg, rho) -> per-level list; the
#       arguments are the *per-level* sequences (one launch, all levels)
#   l2p(local, tree, cfg, idx)           -> (n,) complex
#   p2l(tree, conn, cfg, idx, rho_leaf)  -> (nbox, p+1) complex
#       contribution folded into the downward local coefficients
#   eval_fused(local, mult_leaf, tree, conn, cfg, idx) -> (n,) complex:
#       the WHOLE evaluation phase (L2P + M2P + P2P) in one launch;
#       takes precedence over p2p/l2p
#
# Topology hooks (matching repro.core.fmm.fmm_build):
#   leaf_classify(cand, valid, centers, radii, cfg) -> five keyed
#       (4**L, 4S) int32 arrays (strong, weak, p2p, p2l, m2p) for the
#       leaf-level strong/weak/swapped-theta classification
PhaseImpl = Optional[Callable]


def _platform() -> str:
    """The JAX platform driving "auto" dispatch (monkeypatchable in tests)."""
    return jax.default_backend()


#: Valid ``Backend.batched_dispatch`` values (see module docstring):
#: "native" = batch-major kernel grids behind custom batching rules,
#: "vmap" = plain-jnp hooks safe under jax.vmap, "fallback" = the
#: solver downgrades apply_batched to the reference sweeps.
BATCHED_DISPATCH = ("native", "vmap", "fallback")


@dataclasses.dataclass(frozen=True)
class Backend:
    """Named bundle of per-phase implementations (None -> core jnp path).

    ``batched_dispatch`` is the three-way batched-dispatch contract for
    ``FmmSolver.apply_batched`` (module docstring): "native" and "vmap"
    hooks serve batches directly under ``jax.vmap`` — batch-major kernel
    grids vs plain jnp batching — while "fallback" downgrades the
    batched entry point to the reference sweeps.
    ``supports(cfg)`` gates dispatch (config/kernel compatibility).
    """

    name: str
    p2p: PhaseImpl = None
    m2l: PhaseImpl = None
    l2p: PhaseImpl = None
    m2l_fused: PhaseImpl = None
    p2l: PhaseImpl = None
    eval_fused: PhaseImpl = None
    leaf_classify: PhaseImpl = None
    batched_dispatch: str = "vmap"

    def __post_init__(self):
        if self.batched_dispatch not in BATCHED_DISPATCH:
            raise ValueError(
                f"batched_dispatch={self.batched_dispatch!r} not in "
                f"{BATCHED_DISPATCH}")

    def supports(self, cfg: FmmConfig) -> bool:
        return True

    def phase_impls(self, cfg: FmmConfig) -> dict:
        """kwargs for ``fmm_evaluate`` selecting this backend's hooks."""
        return {"p2p_impl": self.p2p, "m2l_impl": self.m2l,
                "l2p_impl": self.l2p, "m2l_fused_impl": self.m2l_fused,
                "p2l_impl": self.p2l, "eval_fused_impl": self.eval_fused}

    def topology_impls(self, cfg: FmmConfig) -> dict:
        """kwargs for ``fmm_build`` selecting this backend's topology
        hooks (the sort/connect phase — paper §4.1/§4.3)."""
        return {"leaf_classify_impl": self.leaf_classify}


_REGISTRY: dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    """Register (or replace) a backend under ``backend.name``."""
    _REGISTRY[backend.name] = backend
    return backend


def available_backends() -> list[str]:
    return sorted(_REGISTRY) + ["auto"]


def get_backend(name: str, cfg: FmmConfig | None = None) -> Backend:
    """Resolve a backend name ("auto" needs ``cfg`` to pick per-config).

    On a TPU, an f64 ``cfg`` for "pallas" or "auto" raises ``DTypeError``:
    the kernels compute in f32 on the chip, which has no f64 vector unit
    (XLA would have to emulate f64, and complex128 may not lower)."""
    if (name in ("auto", "pallas") and cfg is not None
            and cfg.dtype == "f64" and _platform() == "tpu"):
        raise DTypeError(
            f"dtype='f64' config with backend={name!r} on a TPU: the "
            "Pallas kernels run f32 on the chip; build the config with "
            "dtype='f32'")
    if name == "auto":
        return _resolve_auto(cfg)
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def _resolve_auto(cfg: FmmConfig | None) -> Backend:
    pallas = _REGISTRY["pallas"]
    if (_platform() == "tpu"
            and (cfg is None or pallas.supports(cfg))):
        return pallas
    return _REGISTRY["reference"]


def _make_reference() -> Backend:
    return Backend(name="reference")


def _make_pallas() -> Backend:
    from ..kernels import (eval_fused_apply, l2p_apply, leaf_classify_pallas,
                           m2l_fused_apply, m2l_level_apply, p2l_apply,
                           p2p_apply)

    def p2p(tree, conn, cfg, idx):
        return p2p_apply(tree, conn, cfg, idx)

    def m2l(mult, weak, centers, cfg, rho):
        return m2l_level_apply(mult, weak, centers, cfg, rho)

    def m2l_fused(mult, weak, centers, cfg, rho):
        return m2l_fused_apply(mult, weak, centers, cfg, rho)

    def l2p(local, tree, cfg, idx):
        return l2p_apply(local, tree, cfg, idx)

    def p2l(tree, conn, cfg, idx, rho):
        return p2l_apply(tree, conn, cfg, idx, rho)

    def eval_fused(local, mult_leaf, tree, conn, cfg, idx):
        return eval_fused_apply(local, mult_leaf, tree, conn, cfg, idx)

    def leaf_classify(cand, valid, centers, radii, cfg):
        return leaf_classify_pallas(cand, valid, centers, radii, cfg)

    # batch-native: every kernel wrapper op carries a custom batching
    # rule that lowers jax.vmap onto its batch-major (B, ...) grid, so
    # apply_batched serves through these hooks at kernel speed.
    return Backend(name="pallas", p2p=p2p, m2l=m2l, l2p=l2p,
                   m2l_fused=m2l_fused, p2l=p2l, eval_fused=eval_fused,
                   leaf_classify=leaf_classify, batched_dispatch="native")


register_backend(_make_reference())
register_backend(_make_pallas())
