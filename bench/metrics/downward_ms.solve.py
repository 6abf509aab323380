"""downward_ms.solve: device time per call of the ops under the program's
scope ``downward`` (M2L, L2L and P2L), in ms, from the ``tf_op`` of each
op in the profiler trace (``bench.phases``)."""
from bench.phases import phase_ms


def read(run):
    return phase_ms(run, "call", "downward")
