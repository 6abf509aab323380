"""Mesh axes, logical->physical sharding rules, and constraint helpers.

Physical mesh axes:
  "pod"    cross-pod data parallelism (multi-pod runs only)
  "data"   in-pod data parallelism / FSDP
  "model"  tensor / expert / sequence parallelism

Logical param axes (see models/common.py) map through ``Rules``; activations
use ``batch_spec``/``act_spec`` helpers. ``maybe_shard`` is a no-op outside a
mesh context so single-device tests and smoke runs need no mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import PartitionSpec as PS


def dp_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


@dataclasses.dataclass(frozen=True)
class Rules:
    """Logical-axis -> mesh-axes table.

    fsdp: additionally shard the "embed" axis of params over the data axes
    (ZeRO-3 style; required for the >100B archs to fit HBM).
    """
    multi_pod: bool = False
    fsdp: bool = True

    def table(self) -> dict[str | None, Any]:
        dp = dp_axes(self.multi_pod)
        t: dict[str | None, Any] = {
            "vocab": "model",
            "heads": "model",
            "kv": "model",
            "ff": "model",
            "experts": "model",
            "layers": None,
            None: None,
        }
        t["embed"] = dp if self.fsdp else None
        return t

    def batch(self) -> PS:
        return PS(dp_axes(self.multi_pod))

    def act(self, *rest) -> PS:
        return PS(dp_axes(self.multi_pod), *rest)


ACT_DP = ("pod", "data")   # data axes for activation batch dims


def active_mesh():
    """The (abstract) context mesh whose axes sharding constraints may
    reference."""
    return jax.sharding.get_abstract_mesh()


def maybe_shard(x, spec: PS):
    """with_sharding_constraint that degrades gracefully:

    - identity when no mesh is active (single-device tests);
    - axis names absent from the mesh are dropped (e.g. "pod" on the
      single-pod mesh);
    - axis entries whose product does not divide the corresponding array
      dim are dropped (e.g. batch 1 on a 16-wide data axis) — GSPMD's
      padding for uneven shardings is exactly what we want to avoid.

    NOTE: a PartitionSpec entry of None *forces replication* of that dim —
    always spell out the data axes on batch dims (this was a measured
    16x activation-memory bug, see EXPERIMENTS.md §Perf).
    """
    mesh = active_mesh()
    if mesh is None or mesh.empty:
        return x
    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

    def keep(entry, dim):
        if entry is None:
            return None
        if not isinstance(entry, (tuple, list)):
            entry = (entry,)
        kept = tuple(e for e in entry if e in names)
        total = 1
        for e in kept:
            total *= sizes[e]
        if not kept or total == 0 or dim % total:
            return None
        return kept

    spec = PS(*[keep(e, d) for e, d in zip(spec, x.shape)])
    return jax.lax.with_sharding_constraint(x, spec)
