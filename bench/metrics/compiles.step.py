"""compiles.step: programs compiled, or loaded from the persistent cache,
inside the window, counted by the benchmark's own ``jax.monitoring``
listener. Should be 0: every shape is warmed in set-up."""

UNIT = "step"


def read(run):
    if run.unit != UNIT:
        return None
    return run.compiles
