"""topology_ms.solve: device time per call of the ops under the program's
scope ``topology`` (``fmm_build``: the tree's sort and the connectivity),
in ms, from the ``tf_op`` of each op in the profiler trace
(``bench.phases``)."""
from bench.phases import phase_ms


def read(run):
    return phase_ms(run, "call", "topology")
