"""Host time per wave in the wave's re-solve: over the traced waves, the
sum of each program span ``repro.resolve_wave``'s length minus the
device-busy time inside it, over the number of the harness's
``apply_wave`` annotations (the trace's clock).  Nothing is read where the
trace holds no such span."""

WAVE = "chipbench.apply_wave"
SPAN = "repro.resolve_wave"


def read(run):
    red = run.trace
    if red is None or not red.spans.get(WAVE) or not red.spans.get(SPAN):
        return None
    host = sum((b - a) - red.busy_in(a, b) for a, b in red.spans[SPAN])
    return 1e-6 * host / len(red.spans[WAVE])
