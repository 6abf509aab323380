"""log_planes_ms.solve: device time per call of the XLA complex log of
the M2L translation vectors, log(r) for every weak-list slot of every
level (ops under the program's scope ``log_planes``), in ms, from the
``tf_op`` of each op in the profiler trace (``bench.launches``). None
where the program has no such scope."""
from bench.launches import scope_ms


def read(run):
    return scope_ms(run, "call", "log_planes")
