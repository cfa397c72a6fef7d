"""Share of the traced window in which no operation ran on the device."""

WINDOW = "chipbench.window"


def read(run):
    red = run.trace
    if red is None or not red.spans.get(WINDOW) or not red.n_devices:
        return None
    a, b = red.window(WINDOW)
    return 100.0 * (1.0 - red.busy_in(a, b) / (b - a))
