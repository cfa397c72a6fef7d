"""Seeded inputs shared by every deployment: the services' record, the
event record, due times, the wave cut and the greedy bootstrap.

Every generator here, and under ``fleets/`` and ``streams/``, reads only
its arguments and a numpy ``Generator``, so the same seed gives the same
services, the same due times and the same departures.  Nothing here
imports the program.

* ``Services``: the benchmark's own record of a pool of services, the
  rectangular arrays that a fleet generator (``fleets/<name>.py``) returns.
* ``due_times``: the exponential quantiles of a Poisson process in an
  order drawn from the seed: every seed gets the same gaps.
* ``Event``: one due event of a stream (``streams/<name>.py``): an
  arrival, a departure, or both as a replace pair.
* ``cut_wave``: the serving loop's wave cut: every due event up to the
  cap, cut before a departure of a service that arrives in the same wave.
* ``live_counts``: the live counts that a trace passes through.
* ``greedy_bootstrap``: the load-balanced greedy start over the serving
  tiers that the churn benchmarks of the program use: each VM on the least
  loaded fog or cloud node by accumulated GFLOPS.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np


@dataclass
class Services:
    F: np.ndarray      # [R, V] float32 GFLOPS
    H: np.ndarray      # [R, V, V] float32 Mbps
    src: np.ndarray    # [R] int32 source node of VM 0

    def __len__(self) -> int:
        return self.F.shape[0]


def due_times(rng: np.random.Generator, n: int, seconds: float) -> np.ndarray:
    """n due times in (0, seconds): the gaps are the exponential quantiles
    (i + 1/2) / n of a Poisson process with n events in ``seconds``, in an
    order drawn from ``rng``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds * n / (n + 0.5) / gaps.sum()
    return np.cumsum(rng.permutation(gaps))


@dataclass
class Event:
    due: float                      # seconds after the window opens
    victim: Optional[int] = None    # sid of the departing service
    sid: Optional[int] = None       # sid of the arriving service (pool row)

    @property
    def size(self) -> int:
        """Events it counts for: a replace pair is two."""
        return (self.victim is not None) + (self.sid is not None)


def cut_wave(events: Sequence[Event], start: int, now: float,
             cap_events: int) -> int:
    """End index of the wave that starts at ``events[start]``: every event
    due by ``now``, at most ``cap_events`` events (a replace pair counts
    two), cut before an event whose departure is a service that arrives
    earlier in the same wave."""
    arriving = set()
    j, n = start, 0
    while j < len(events):
        e = events[j]
        if (e.due > now or n + e.size > cap_events
                or e.victim in arriving):
            break
        if e.sid is not None:
            arriving.add(e.sid)
        n += e.size
        j += 1
    return j


def live_counts(events: Sequence[Event], n_live: int) -> List[int]:
    """The live count at the start and after each event of a trace, every
    arrival admitted."""
    out, n = [n_live], n_live
    for e in events:
        n += (e.sid is not None) - (e.victim is not None)
        out.append(n)
    return out


def greedy_bootstrap(F: np.ndarray, hosts: Sequence[int]) -> np.ndarray:
    """X0 [R, V]: each VM, service by service, onto the least loaded host by
    accumulated GFLOPS (ties to the earliest host)."""
    load = {p: 0.0 for p in hosts}
    X0 = np.zeros(F.shape, np.int32)
    for r in range(F.shape[0]):
        for v in range(F.shape[1]):
            p = min(hosts, key=load.get)
            X0[r, v] = p
            load[p] += float(F[r, v])
    return X0
