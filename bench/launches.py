"""Device time of one named launch or scope, read from the op names of a
reduced trace (``bench.trace_reduce.Summary.op_s``, keyed by ``tf_op``).

The program names each Pallas launch twice with the same word: its
``jax.named_scope`` and its ``pallas_call(name=...)``, so the kernel's
own op reads ``.../<name>/jit(_<kernel>_pallas)/.../<name>/pallas_call``
and the wrapper's staging ops ``.../<name>/<op>``. An XLA computation the
program wraps in a scope of its own (``log_planes``) reads
``.../<scope>/<op>``. Of a fused op's ``;``-joined names the first
counts, as in ``bench.phases``, so no op's time is read under two names.
"""
from __future__ import annotations


def _per_unit_ms(run, unit: str, match):
    """ms per unit of the ops whose first name's path ``match``es; None
    without a trace, in a run of another unit, or where none does."""
    if run.trace is None or run.unit != unit:
        return None
    times = [s for op, s in run.trace.op_s.items()
             if match(op.split(";")[0].split("/"))]
    if not times:
        return None
    return 1e3 * sum(times) / len(run.unit_s)


def launch_ms(run, unit: str, name: str):
    """Device time per unit (``"call"`` or ``"step"``) of the Pallas
    launch ``name``: ops whose ``tf_op`` holds ``<name>/pallas_call``, in
    ms; None without a trace, in a run of another unit, or where no op
    carries the name."""
    def match(path):
        return any(a == name and b == "pallas_call"
                   for a, b in zip(path, path[1:]))

    return _per_unit_ms(run, unit, match)


def scope_ms(run, unit: str, scope: str):
    """Device time per unit of the ops under the scope ``scope`` at any
    depth, in ms; None as ``launch_ms``."""
    return _per_unit_ms(run, unit, lambda path: scope in path[:-1])
