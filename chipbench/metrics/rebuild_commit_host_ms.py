"""Host time per wave in the engine's rebuild and commit: over the traced
waves, the sum of each program span ``repro.wave.detach``, ``rebuild``,
``warm_state`` and ``commit``'s length minus the device-busy time inside
it, over the number of the harness's ``apply_wave`` annotations (the
trace's clock).  Nothing is read where the trace holds none of them."""

WAVE = "chipbench.apply_wave"
SPANS = ("repro.wave.detach", "repro.wave.rebuild", "repro.wave.warm_state",
         "repro.wave.commit")


def read(run):
    red = run.trace
    if red is None or not red.spans.get(WAVE):
        return None
    spans = [sp for name in SPANS for sp in red.spans.get(name, [])]
    if not spans:
        return None
    host = sum((b - a) - red.busy_in(a, b) for a, b in spans)
    return 1e-6 * host / len(red.spans[WAVE])
