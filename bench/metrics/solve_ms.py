"""solve_ms: the window's length over the calls completed in it, in ms.
A call is one ``apply`` of the problem or one ``apply_batched`` of the
batch, ended by ``block_until_ready``."""


def read(run):
    if run.unit != "call":
        return None
    return 1e3 * run.window_s / len(run.unit_s)
