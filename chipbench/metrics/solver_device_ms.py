"""Device time per wave: the mean, over the window's ``apply_wave``
annotations, of the device-busy time inside each (the trace's clock)."""

WAVE = "chipbench.apply_wave"


def read(run):
    red = run.trace
    if red is None or not red.spans.get(WAVE) or not red.n_devices:
        return None
    per = [red.busy_in(a, b) for a, b in red.spans[WAVE]]
    return 1e-6 * sum(per) / len(per)
