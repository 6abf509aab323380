"""Adaptive fast multipole method (Goude & Engblom 2012) — TPU-native JAX.

Public API:
  FmmConfig, num_levels_for        — problem description / calibration
  build_tree, build_connectivity   — topological phase
  fmm_potential                    — end-to-end evaluation (jit)
  direct_potential                 — O(N^2) oracle / baseline
"""
from .config import FmmConfig, num_levels_for, max_leaf_size
from .topology import (Tree, build_tree, leaf_particle_index, leaf_ids,
                       Connectivity, MARGIN_CLASSES, build_connectivity,
                       connectivity_stats)
from .fmm import (FmmPlan, Health, HEALTH_CLASSES, fmm_build, fmm_evaluate,
                  fmm_potential, fmm_potential_checked, health_of, p2m,
                  upward, downward, l2p)
from .direct import direct_potential, direct_potential_numpy, rel_error_inf

__all__ = [
    "FmmConfig", "num_levels_for", "max_leaf_size",
    "Tree", "build_tree", "leaf_particle_index", "leaf_ids",
    "Connectivity", "MARGIN_CLASSES", "build_connectivity",
    "connectivity_stats",
    "FmmPlan", "Health", "HEALTH_CLASSES", "fmm_build", "fmm_evaluate",
    "fmm_potential", "fmm_potential_checked",
    "health_of", "p2m", "upward", "downward", "l2p",
    "direct_potential", "direct_potential_numpy", "rel_error_inf",
]
