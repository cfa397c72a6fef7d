"""Float64 watts of the committed placement over float64 watts of the
all-CDC placement of the same live services, averaged over the window by
the time each committed placement stood (from its commit to the next)."""


def read(run):
    r = run.watts_ratios
    total = sum(d for d, _ in r)
    return sum(d * x for d, x in r) / total if total > 0 else None
