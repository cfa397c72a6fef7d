"""Seeded inputs: services, the open-loop event trace and the bootstrap.

Every generator here reads only its arguments and a numpy ``Generator``,
so the same seed gives the same services, the same due times and the same
victims.  Nothing here imports the program.

* ``services``: the paper's VSR generator (arXiv:2105.03221 section 3):
  VM 0 is the input VM at the service's source with U(0.1, 1) GFLOPS, the
  other VMs U(3, 10) GFLOPS, chained by U(5, 50) Mbps virtual links.
* ``replace_pairs``: Poisson "replace pairs" at a fixed rate.  Each pair is
  one departure of a uniformly random live service and one arrival of a fresh service, due at the same
  instant, so the live count never moves.
  Every seed gets the same number of pairs and the same multiset of gaps
  (exponential quantiles at the rate) in its own order: the seed changes
  which gaps come when and which services leave, not how much work the
  window holds.
* ``cut_wave``: the serving loop's wave cut: every due pair up to the cap,
  cut before a departure of a service that arrives in the same wave.
* ``greedy_bootstrap``: the load-balanced greedy start over the serving
  tiers that the churn benchmarks of the program use: each VM on the least
  loaded fog or cloud node by accumulated GFLOPS.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np


@dataclass
class Services:
    F: np.ndarray      # [R, V] float32 GFLOPS
    H: np.ndarray      # [R, V, V] float32 Mbps
    src: np.ndarray    # [R] int32 source node of VM 0

    def __len__(self) -> int:
        return self.F.shape[0]


def services(rng: np.random.Generator, n: int, n_vms: int,
             sources: Sequence[int], vm_gflops=(3.0, 10.0),
             input_gflops=(0.1, 1.0), link_mbps=(5.0, 50.0)) -> Services:
    F = rng.uniform(*vm_gflops, size=(n, n_vms)).astype(np.float32)
    F[:, 0] = rng.uniform(*input_gflops, size=n)
    H = np.zeros((n, n_vms, n_vms), np.float32)
    for v in range(n_vms - 1):
        H[:, v, v + 1] = rng.uniform(*link_mbps, size=n)
    src = np.asarray(rng.choice(np.asarray(sources), size=n), np.int32)
    return Services(F=F, H=H, src=src)


def due_times(rng: np.random.Generator, n: int, seconds: float) -> np.ndarray:
    """n due times in (0, seconds): the gaps are the exponential quantiles
    (i + 1/2) / n of a Poisson process with n events in ``seconds``, in an
    order drawn from ``rng``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds * n / (n + 0.5) / gaps.sum()
    return np.cumsum(rng.permutation(gaps))


@dataclass
class Pair:
    due: float         # seconds after the window opens
    victim: int        # sid of the departing service
    sid: int           # sid of the arriving service (its row in the pool)


def replace_pairs(rng: np.random.Generator, n: int, seconds: float,
                  live: Sequence[int], first_sid: int) -> List[Pair]:
    """n replace pairs due in (0, seconds); each victim is drawn uniformly
    from the live set as the pairs before it leave it.  Arrival sids count
    up from ``first_sid``."""
    live = list(live)
    out = []
    for k, t in enumerate(due_times(rng, n, seconds)):
        victim = live.pop(int(rng.integers(len(live))))
        sid = first_sid + k
        live.append(sid)
        out.append(Pair(float(t), victim, sid))
    return out


def cut_wave(pairs: Sequence[Pair], start: int, now: float,
             cap_pairs: int) -> int:
    """End index of the wave that starts at ``pairs[start]``: every pair
    due by ``now``, at most ``cap_pairs``, cut before a pair whose
    departure is a service that arrives earlier in the same wave."""
    arriving = set()
    j = start
    while (j < len(pairs) and j - start < cap_pairs
           and pairs[j].due <= now and pairs[j].victim not in arriving):
        arriving.add(pairs[j].sid)
        j += 1
    return j


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
