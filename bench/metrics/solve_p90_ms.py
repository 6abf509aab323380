"""solve_p90_ms: 90th percentile of the host wall time of every call in
the window, in ms (the ``inclusive`` quantile of ``statistics``; a window
of one call gives that call)."""
import statistics


def read(run):
    if run.unit != "call":
        return None
    if len(run.unit_s) == 1:
        return 1e3 * run.unit_s[0]
    return 1e3 * statistics.quantiles(run.unit_s, n=10, method="inclusive")[-1]
