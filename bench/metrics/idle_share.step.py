"""idle_share.step: share of the traced window in which no operation ran on
the device, in %: 1 - (union of the XLA op intervals) / (window), from the
profiler trace (bench.trace_reduce), averaged over the chips used."""


def read(run):
    if run.trace is None or run.unit != "step" or run.trace.chips == 0:
        return None
    return 100.0 * run.trace.idle_share
