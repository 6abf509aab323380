"""eval_fused_log_ms.solve: device time per call of the fused evaluation
kernel with the logarithmic near-field tile (the launch
``eval_fused_log``: L2P, M2P and P2P with a log and an ``atan2`` per
pair), in ms, from the ``tf_op`` of each op in the profiler trace
(``bench.launches``). None where the program runs no such launch."""
from bench.launches import launch_ms


def read(run):
    return launch_ms(run, "call", "eval_fused_log")
