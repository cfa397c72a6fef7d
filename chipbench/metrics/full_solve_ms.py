"""Wall time of a full solve on the event path: the mean length of the
program spans ``repro.wave.full_solve`` in the trace.  Nothing is read
where the trace holds none."""

SPAN = "repro.wave.full_solve"


def read(run):
    red = run.trace
    spans = red.spans.get(SPAN) if red is not None else None
    if not spans:
        return None
    return 1e-6 * sum(b - a for a, b in spans) / len(spans)
