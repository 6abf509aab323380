"""p2l_log_ms.solve: device time per call of the P2L kernel with the
logarithmic b_0 column (the launch ``p2l_log``), in ms, from the
``tf_op`` of each op in the profiler trace (``bench.launches``). None
where the program runs no such launch."""
from bench.launches import launch_ms


def read(run):
    return launch_ms(run, "call", "p2l_log")
