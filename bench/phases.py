"""Device time by the program's phase scopes, read from the op names of a
reduced trace (``bench.trace_reduce.Summary.op_s``, keyed by ``tf_op``).

The program traces its phases under ``jax.named_scope`` (``core/fmm.py``:
``topology``, ``upward``, ``downward``, ``evaluation``), so an op's
``tf_op`` reads ``jit(<program>)/<phase>/<sub-scope>/.../<op>``, and under
``jax.vmap`` ``jit(<program>)/vmap(<phase>)/...``. An op of a program
without scopes names an op or a jitted function there instead
(``jit(core)/gather``, ``jit(core)/jit(_m2l_pallas)/...``), and an op with
no ``tf_op`` is keyed by its HLO category or name: both count as
``UNSCOPED``, so a program without the scopes has no phase to read.
"""
from __future__ import annotations

import collections

UNSCOPED = "unscoped"


def program_and_phase(name: str):
    """``(program, phase)`` of an op name: the program is None where the
    name does not start with ``jit(<program>)``, the phase ``UNSCOPED``
    where no scope follows the program. ``vmap(...)`` wrappers are
    stripped; of a fused op's ``;``-joined names the first counts."""
    parts = name.split(";")[0].split("/")
    head = parts[0]
    if not (head.startswith("jit(") and head.endswith(")")):
        return None, UNSCOPED
    program = head[len("jit("):-1]
    if len(parts) < 3:
        return program, UNSCOPED
    scope = parts[1]
    while scope.startswith("vmap(") and scope.endswith(")"):
        scope = scope[len("vmap("):-1]
    if not scope or "(" in scope:
        return program, UNSCOPED
    return program, scope


def phase_seconds(op_s: dict) -> dict:
    """Self time in seconds by ``(program, phase)``."""
    out = collections.Counter()
    for name, seconds in op_s.items():
        out[program_and_phase(name)] += seconds
    return dict(out)


def phase_ms(run, unit: str, phase: str):
    """Device time per unit (``"call"`` or ``"step"``) of the ops under the
    top-level scope ``phase``, summed over the programs, in ms; None
    without a trace, in a run of another unit, or where no op carries
    the scope."""
    if run.trace is None or run.unit != unit:
        return None
    times = [s for (_, p), s in phase_seconds(run.trace.op_s).items()
             if p == phase]
    if not times:
        return None
    return 1e3 * sum(times) / len(run.unit_s)
