"""topology_ms.step: device time per RK2 step of the program ``build``
(``FmmSolver.refresh``: the tree's sort and the connectivity), in ms,
from that program's runs in the profiler trace."""

PROGRAM = "build"


def read(run):
    if run.trace is None or run.unit != "step":
        return None
    seconds = run.trace.program_s.get(PROGRAM)
    if not seconds:
        return None
    return 1e3 * seconds / len(run.unit_s)
