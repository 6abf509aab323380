"""upward_ms.step: device time per RK2 step of the ops under the program's
scope ``upward`` (P2M and M2M), in ms, from the ``tf_op`` of each op in
the profiler trace (``bench.phases``)."""
from bench.phases import phase_ms


def read(run):
    return phase_ms(run, "step", "upward")
