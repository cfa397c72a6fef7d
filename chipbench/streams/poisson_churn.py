"""Independent Poisson arrivals with exponential lifetimes: an M/M/inf
churn whose live count drifts around ``arrivals_per_s * mean_lifetime_s``
instead of holding still, as in online virtual-network embedding
(Chowdhury, Rahman and Boutaba, INFOCOM 2009; Yu et al., CCR 2008).

The services live when the window opens leave after memoryless residual
lifetimes drawn from the same law.  A departure due after the window is
not in the trace.  Every seed gets the same multiset of arrival gaps and
of lifetimes (exponential quantiles), in an order drawn from the seed, so
the seed changes when the live count moves, not how much work the window
holds.  The live count along the trace depends on the seed and on how
many services are live, not on which.

Traffic keys: ``arrivals_per_s``, ``mean_lifetime_s``."""
from typing import List, Sequence

import numpy as np

import gen


def _exp_quantiles(n: int, mean: float) -> np.ndarray:
    """The quantiles (i + 1/2) / n of an exponential law, scaled to the
    mean."""
    x = -np.log1p(-(np.arange(n) + 0.5) / n)
    return x * (mean * n / x.sum())


def events(rng: np.random.Generator, seconds: float, live: Sequence[int],
           first_sid: int, arrivals_per_s: float, mean_lifetime_s: float,
           **_) -> List[gen.Event]:
    """Arrivals and departures due in (0, seconds), in due order.  Arrival
    sids count up from ``first_sid``."""
    n = max(1, int(round(float(arrivals_per_s) * seconds)))
    mean = float(mean_lifetime_s)
    arrive = gen.due_times(rng, n, seconds)
    life = rng.permutation(_exp_quantiles(n, mean))
    residual = rng.permutation(_exp_quantiles(len(live), mean))
    out = [gen.Event(float(t), sid=first_sid + k)
           for k, t in enumerate(arrive)]
    out += [gen.Event(float(t + d), victim=first_sid + k)
            for k, (t, d) in enumerate(zip(arrive, life)) if t + d < seconds]
    out += [gen.Event(float(d), victim=int(s))
            for s, d in zip(live, residual) if d < seconds]
    out.sort(key=lambda e: e.due)
    return out


def _bucket(n: int) -> int:
    """The program's row bucket of a live count: the next power of two
    from 2 (``PlacementSpec``'s default row bucketing)."""
    b = 2
    while b < n:
        b *= 2
    return b


def warm_plan(rng: np.random.Generator, live: Sequence[int], first_sid: int,
              cap_events: int, counts: Sequence[int], **_) -> List[tuple]:
    """Waves that reach every row bucket the trace visits and, in each,
    every wave bucket: at one live count of the bucket (the one nearest
    the start), replace waves of 1, 2, 4, ... pairs up to the cap, waves
    of departures alone and of arrivals alone, and lone departures and
    arrivals (the per-event path), each kind once ending at that count;
    then back to the count the trace starts from.  Link counts are not warmed: the other counts of a
    bucket have other link counts.  Each wave is (arriving sids, departing
    sids)."""
    cur, n0 = set(live), len(live)
    waves, nxt = [], [first_sid]

    def wave(n_arrive: int, n_depart: int) -> None:
        depart = [int(x) for x in
                  rng.choice(sorted(cur), size=n_depart, replace=False)]
        arrive = list(range(nxt[0], nxt[0] + n_arrive))
        nxt[0] += n_arrive
        cur.difference_update(depart)
        cur.update(arrive)
        waves.append((arrive, depart))

    def move_to(c: int) -> None:
        while len(cur) < c:
            wave(min(c - len(cur), cap_events), 0)
        while len(cur) > c:
            wave(0, min(len(cur) - c, cap_events))

    for b in sorted({_bucket(c) for c in counts if c > 0}):
        c = min((c for c in counts if _bucket(c) == b),
                key=lambda c: (abs(c - n0), c))
        move_to(c)
        top = min(cap_events // 2, c)
        k = 1
        while k < top:
            wave(k, k)
            k *= 2
        wave(top, top)
        # each kind once ending at the bucket's count, whichever side of
        # it the other lands
        if c > 2:
            for n_arrive, n_depart in ((2, 0), (0, 2), (0, 2), (2, 0)):
                wave(n_arrive, n_depart)
        if c > 1:
            for n_arrive, n_depart in ((1, 0), (0, 1), (0, 1), (1, 0)):
                wave(n_arrive, n_depart)
    move_to(n0)
    return waves
