"""sort_ms.solve: device time per call of the XLA sort ops (HLO category
``sort``: the tree's sort of the coordinates and the connectivity's
compaction sorts), in ms, from the profiler trace."""


def read(run):
    if run.trace is None or run.unit != "call":
        return None
    sort_s = run.trace.kind_s.get("sort")
    if not sort_s:
        return None
    return 1e3 * sort_s / len(run.unit_s)
