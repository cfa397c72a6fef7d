"""Poisson replace pairs at a fixed rate: each pair is one departure of a
uniformly random live service and one arrival of a fresh service, due at
the same instant, so the live count never moves.  Every seed gets the same
number of pairs and the same multiset of gaps (exponential quantiles at
the rate) in its own order: the seed changes which gaps come when and
which services leave, not how much work the window holds.

Traffic keys: ``rate_events_per_s`` (two events a pair)."""
from typing import List, Sequence

import numpy as np

import gen


def events(rng: np.random.Generator, seconds: float, live: Sequence[int],
           first_sid: int, rate_events_per_s: float, **_) -> List[gen.Event]:
    """The window's pairs, due in (0, seconds); each victim is drawn
    uniformly from the live set as the pairs before it leave it.  Arrival
    sids count up from ``first_sid``."""
    n = max(1, int(round(float(rate_events_per_s) * seconds / 2)))
    live = list(live)
    out = []
    for k, t in enumerate(gen.due_times(rng, n, seconds)):
        victim = live.pop(int(rng.integers(len(live))))
        sid = first_sid + k
        live.append(sid)
        out.append(gen.Event(float(t), victim=victim, sid=sid))
    return out


def warm_plan(rng: np.random.Generator, live: Sequence[int], first_sid: int,
              cap_events: int, counts: Sequence[int], **_) -> List[tuple]:
    """Replace waves of 1, 2, 4, ... pairs and the largest wave possible:
    every wave bucket the window can use, at the one live count it keeps.
    Each wave is (arriving sids, departing sids)."""
    live = set(live)
    top = min(cap_events // 2, len(live))
    ks, k = [], 1
    while k < top:
        ks.append(k)
        k *= 2
    waves, sid = [], first_sid
    for k in ks + [top]:
        depart = [int(x) for x in
                  rng.choice(sorted(live), size=k, replace=False)]
        arrive = list(range(sid, sid + k))
        sid += k
        live.difference_update(depart)
        live.update(arrive)
        waves.append((arrive, depart))
    return waves
