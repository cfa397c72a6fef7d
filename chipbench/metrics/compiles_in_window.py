"""Programs built inside the window: JAX backend compiles (persistent-cache
loads included) plus fresh traces of the counted solver entries
(``solvers.TRACE_COUNTS``).  The names are in the result's ``info``."""


def read(run):
    return run.compiles
