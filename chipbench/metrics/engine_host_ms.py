"""Host time per wave in the online engine: the mean, over the window's
``apply_wave`` annotations, of each one's length minus the device-busy time
inside it (the trace's clock)."""

WAVE = "chipbench.apply_wave"


def read(run):
    red = run.trace
    if red is None or not red.spans.get(WAVE):
        return None
    per = [(b - a) - red.busy_in(a, b) for a, b in red.spans[WAVE]]
    return 1e-6 * sum(per) / len(per)
