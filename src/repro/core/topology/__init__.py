"""Device-resident topology subsystem (paper §4.1–§4.3).

The paper's headline claim is that *every* phase runs on the GPU,
"including the initial phase which assembles the topological information
of the input data". This package is that phase for the TPU port:

  tree.py          single-sort adaptive tree build (2 full sorts total,
                   then O(N) segmented rank-partitions per split) plus
                   the fused level-geometry pass
  connectivity.py  theta-criterion interaction lists with the per-level
                   compaction batched into one flattened sort and the
                   leaf-level classification exposed as a backend hook
                   (jnp reference | Pallas kernel)

``repro.core`` re-exports the public names, so callers keep importing
``from repro.core import build_tree, build_connectivity``.
"""
from .tree import (Tree, build_tree, build_tree_lexsort, leaf_ids,
                   leaf_particle_index, leaf_particle_index_loop, leaf_planes)
from .connectivity import (MARGIN_CLASSES, Connectivity, build_connectivity,
                           connectivity_stats, leaf_classify_reference)

__all__ = [
    "Tree", "build_tree", "build_tree_lexsort", "leaf_ids",
    "leaf_particle_index", "leaf_particle_index_loop", "leaf_planes",
    "Connectivity", "MARGIN_CLASSES", "build_connectivity",
    "connectivity_stats", "leaf_classify_reference",
]
