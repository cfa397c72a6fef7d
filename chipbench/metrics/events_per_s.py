"""Events committed by the waves handed to the engine in the window, over
the window's length (to the last commit, where that ends past the
window)."""


def read(run):
    return run.committed / run.elapsed_s if run.elapsed_s > 0 else None
