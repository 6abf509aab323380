"""setup_s: host seconds from the start of the process to the end of
set-up (imports, inputs, solver, compiles or cache loads, warm-up)."""


def read(run):
    return run.setup_s
