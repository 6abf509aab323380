"""step_ms: the window's length over the RK2 time steps completed in it,
in ms."""


def read(run):
    if run.unit != "step":
        return None
    return 1e3 * run.window_s / len(run.unit_s)
