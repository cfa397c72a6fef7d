"""The traffic generator: the same seed gives the same trace, each pair is
one departure and one arrival, every seed gets the same gaps, and no wave
departs a service that arrives in it."""
import numpy as np

import gen


def _trace(seed, n=400, live=20, seconds=10.0):
    return gen.replace_pairs(np.random.default_rng(seed), n, seconds,
                             list(range(live)), live)


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
        assert p.victim in live and p.sid not in live
        assert p.sid == 20 + k
        live.remove(p.victim)
        live.add(p.sid)
        assert len(live) == 20


def test_waves_never_depart_a_service_arriving_in_them():
    pairs = _trace(4, n=2000, live=20, seconds=1.0)
    i = 0
    while i < len(pairs):
        j = gen.cut_wave(pairs, i, now=2.0, cap_pairs=32)
        assert 1 <= j - i <= 32
        wave = pairs[i:j]
        arriving = {p.sid for p in wave}
        assert not arriving & {p.victim for p in wave}
        if j < len(pairs) and j - i < 32:
            assert pairs[j].victim in arriving      # cut for that reason
        i = j
    assert gen.cut_wave(pairs, 0, now=pairs[0].due - 1e-9, cap_pairs=32) == 0


def test_services_are_paper_chains():
    s = gen.services(np.random.default_rng(0), 50, 3, [4, 5])
    assert s.F.dtype == np.float32 and s.F.shape == (50, 3)
    assert np.all((s.F[:, 0] >= 0.1) & (s.F[:, 0] <= 1.0))
    assert np.all((s.F[:, 1:] >= 3.0) & (s.F[:, 1:] <= 10.0))
    assert np.count_nonzero(s.H) == 100 and np.all(s.H[:, 0, 1] >= 5.0)
    assert set(s.src.tolist()) <= {4, 5}
