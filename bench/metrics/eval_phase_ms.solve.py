"""eval_phase_ms.solve: device time per call of the ops under the program's
scope ``evaluation`` (the fused evaluation kernel and its staging, and
the scatter back to input order), in ms, from the ``tf_op`` of each op
in the profiler trace (``bench.phases``)."""
from bench.phases import phase_ms


def read(run):
    return phase_ms(run, "call", "evaluation")
