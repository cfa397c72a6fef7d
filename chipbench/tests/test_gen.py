"""The traffic and the fleet: the same seed gives the same trace, a replace
pair is one departure and one arrival, every seed gets the same gaps and
lifetimes, no wave departs a service that arrives in it, and the default
fleet and stream draw what the harness drew before they were files of
their own."""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

import gen
import run

ROOT = Path(__file__).resolve().parents[2]
PAIRS = run.load("streams", "replace_pairs")
CHURN = run.load("streams", "poisson_churn")
CHAINS = run.load("fleets", "paper_chains")


def _trace(seed, n=400, live=20, seconds=10.0):
    return PAIRS.events(np.random.default_rng(seed), seconds,
                        list(range(live)), live,
                        rate_events_per_s=2 * n / seconds)


def test_same_seed_same_trace():
    a, b = _trace(2 ** 33 + 1), _trace(2 ** 33 + 1)
    assert [(p.due, p.victim, p.sid) for p in a] == \
        [(p.due, p.victim, p.sid) for p in b]
    c = _trace(2 ** 33 + 2)
    assert [p.victim for p in a] != [p.victim for p in c]


def test_every_seed_gets_the_same_gaps():
    gaps = [np.sort(np.diff(np.r_[0.0, [p.due for p in _trace(s)]]))
            for s in (1, 2, 3)]
    for g in gaps[1:]:
        np.testing.assert_allclose(g, gaps[0], rtol=1e-9)
    due = [p.due for p in _trace(1)]
    assert 0.0 < due[0] and due[-1] < 10.0 and np.all(np.diff(due) > 0)


def test_pairs_replace_one_live_service_each():
    live = set(range(20))
    for k, p in enumerate(_trace(9)):
        assert p.victim in live and p.sid not in live and p.size == 2
        assert p.sid == 20 + k
        live.remove(p.victim)
        live.add(p.sid)
        assert len(live) == 20


def test_waves_never_depart_a_service_arriving_in_them():
    pairs = _trace(4, n=2000, live=20, seconds=1.0)
    i = 0
    while i < len(pairs):
        j = gen.cut_wave(pairs, i, now=2.0, cap_events=64)
        assert 1 <= j - i <= 32
        wave = pairs[i:j]
        arriving = {p.sid for p in wave}
        assert not arriving & {p.victim for p in wave}
        if j < len(pairs) and j - i < 32:
            assert pairs[j].victim in arriving      # cut for that reason
        i = j
    assert gen.cut_wave(pairs, 0, now=pairs[0].due - 1e-9,
                        cap_events=64) == 0


def test_services_are_paper_chains():
    s = CHAINS.services(np.random.default_rng(0), 50, [4, 5], n_vms=3)
    assert s.F.dtype == np.float32 and s.F.shape == (50, 3)
    assert np.all((s.F[:, 0] >= 0.1) & (s.F[:, 0] <= 1.0))
    assert np.all((s.F[:, 1:] >= 3.0) & (s.F[:, 1:] <= 10.0))
    assert np.count_nonzero(s.H) == 100 and np.all(s.H[:, 0, 1] >= 5.0)
    assert set(s.src.tolist()) <= {4, 5}


# sha256 prefixes of what the harness drew for the ``paper`` cells before
# fleets and streams were files of their own (the pool from
# ``gen.services``, the replace warm-up, the trace from
# ``gen.replace_pairs``, and the waves that ``gen.cut_wave`` cut from it,
# by pairs, at two service speeds), keyed by mix / seconds / rate / wave
# cap / seed
RECORDED = {
    "paper_steady/51/83/64/1": "fa431a66646e4168e8ae",
    "paper_steady/51/83/64/2147483647": "508a6c7243e098a8d79c",
    "paper_steady/51/83/64/4294967302": "1837a677296962fa09d7",
    "paper_steady/51/83/64/3141592653": "dda4ff28b8bd48b33827",
    "paper_overload/51/156/64/1": "c48e4b827aa3c62e2f2f",
    "paper_overload/51/156/64/2147483647": "870765da39d45108c9dc",
    "paper_overload/51/156/64/4294967302": "7a975fb80e10be72507f",
    "paper_overload/51/156/64/3141592653": "8e179e19cc8658341141",
    "paper_steady/3/20/8/1": "611c87d7b9f3c6242d2a",
    "paper_steady/3/20/8/2147483647": "54314eff18490217f398",
    "paper_steady/3/20/8/4294967302": "1fbe074d3f2979af5c0f",
    "paper_steady/3/20/8/3141592653": "cf1a7c8345f47fe35f6c",
}


def _waves(events, cap, base_s, per_event_s):
    """The waves a loop cuts that takes ``base_s + per_event_s`` a wave
    event and waits for the next due event when idle."""
    now, i, out = 0.0, 0, []
    while i < len(events):
        now = max(now, events[i].due)
        j = gen.cut_wave(events, i, now, cap)
        out.append((i, j))
        now += base_s + per_event_s * (j - i)
        i = j
    return out


@pytest.mark.parametrize("case", sorted(RECORDED))
def test_replace_pairs_inputs_are_unchanged(case):
    mix, seconds, rate, cap, seed = case.split("/")
    cfg = json.loads((ROOT / "chipbench/configs/paper.json").read_text())
    traffic = json.loads(
        (ROOT / f"chipbench/traffic/{mix}.json").read_text())
    traffic.update(rate_events_per_s=float(rate), wave_cap_events=int(cap))
    p = run.plan(cfg, traffic, int(seed), float(seconds))
    h = hashlib.sha256()
    for a in (p.pool.F, p.pool.H, p.pool.src):
        h.update(np.ascontiguousarray(a).tobytes())
    h.update(repr(p.warm).encode())
    h.update(repr([(e.due, e.victim, e.sid) for e in p.events]).encode())
    h.update(repr(_waves(p.events, int(cap), 0.01, 0.001)
                  + _waves(p.events, int(cap), 0.3, 0.005)).encode())
    assert h.hexdigest()[:20] == RECORDED[case]


def _churn(seed, seconds=51.0, live=20, first=100, mean_lifetime_s=0.482):
    return CHURN.events(np.random.default_rng(seed), seconds,
                        list(range(live)), first, arrivals_per_s=41.5,
                        mean_lifetime_s=mean_lifetime_s)


def test_churn_every_seed_gets_the_same_gaps_and_lifetimes():
    """With lifetimes short enough that every departure falls inside the
    window, each seed holds the same arrival gaps, lifetimes and residual
    lifetimes, in its own order."""
    seen = []
    for seed in (1, 2 ** 31 + 5, 2 ** 33 + 9):
        ev = _churn(seed, mean_lifetime_s=1e-4)
        arrive = {e.sid: e.due for e in ev if e.sid is not None}
        life = [e.due - arrive[e.victim] for e in ev
                if e.victim is not None and e.victim >= 100]
        residual = [e.due for e in ev
                    if e.victim is not None and e.victim < 100]
        assert len(arrive) == 2116 and len(life) == 2116
        assert len(residual) == 20
        assert [e.due for e in ev] == sorted(e.due for e in ev)
        gaps = np.diff(np.r_[0.0, sorted(arrive.values())])
        seen.append((np.sort(gaps), np.sort(life), np.sort(residual)))
    for got in seen[1:]:
        for a, b in zip(got, seen[0]):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-11)
    assert [e.due for e in _churn(1)] != [e.due for e in _churn(2)]
    np.testing.assert_allclose(np.mean(seen[0][1]), 1e-4, rtol=1e-6)


def test_churn_mean_live_count_is_twenty():
    seconds = 500.0
    ev = _churn(7, seconds=seconds)
    counts = np.array(gen.live_counts(ev, 20))
    due = np.r_[0.0, [e.due for e in ev], seconds]
    mean = np.sum(counts * np.diff(due)) / seconds
    assert 18.0 <= mean <= 22.0
    assert counts.min() > 0


def test_churn_waves_are_cut_before_a_departure_of_an_arrival():
    ev = _churn(3)
    live, i, cuts = set(range(20)), 0, 0
    while i < len(ev):
        j = gen.cut_wave(ev, i, now=1e9, cap_events=16)
        wave = ev[i:j]
        assert 1 <= sum(e.size for e in wave) <= 16
        arriving = {e.sid for e in wave if e.sid is not None}
        departing = {e.victim for e in wave if e.victim is not None}
        assert not arriving & departing and departing <= live
        if j < len(ev) and sum(e.size for e in wave) < 16:
            assert ev[j].victim in arriving     # cut for that reason
            cuts += 1
        live = (live - departing) | arriving
        i = j
    assert cuts > 0


def test_churn_warm_plan_reaches_every_row_bucket():
    """Each row bucket that the trace visits gets, at the count of it
    nearest the start, replace waves of 1, 2, 4, ... pairs up to the cap,
    waves of departures alone and of arrivals alone, and a lone departure
    and arrival, each ending at that count; the plan ends where it
    began."""
    ev = _churn(11)
    counts = gen.live_counts(ev, 20)
    waves = CHURN.warm_plan(np.random.default_rng(5), list(range(20)), 100,
                            64, counts)
    live, ending_at = set(range(20)), {}
    for arrive, depart in waves:
        assert set(depart) <= live and not set(arrive) & live
        assert len(arrive) + len(depart) <= 64
        live = (live - set(depart)) | set(arrive)
        ending_at.setdefault(len(live), set()).add((len(arrive),
                                                    len(depart)))
    assert len(live) == 20
    buckets = {CHURN._bucket(c) for c in counts}
    assert len(buckets) >= 3
    for b in buckets:
        c = min((c for c in counts if CHURN._bucket(c) == b),
                key=lambda c: (abs(c - 20), c))
        top = min(32, c)
        k, want = 1, {(top, top), (0, 2), (2, 0), (0, 1), (1, 0)}
        while k < top:
            want.add((k, k))
            k *= 2
        assert want <= ending_at[c]
