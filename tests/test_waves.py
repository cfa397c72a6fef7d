"""Wave-batched churn tests (the batched churn waves PR).

Covers: waves of one (``add``/``remove`` run the wave path: verdicts,
counters and f64-oracle-checked objectives), same-tick
replace semantics (departures detach before arrivals inside one wave, so
capacity is never double-counted), ``merge_timelines`` tie-ordering as a
property, the flash-crowd preset round-trip, priority-heap admission and
drain order, queue-drain fairness after capacity-increasing events,
preemption under power-budget pressure, the amortized background defrag
tick (never-regressing, cursor carried across ticks, periodic defrag
disabled), wave compile stability (zero fresh traces after the warmup
wave per shape bucket), the scheduler's batch facade, and the federated
per-region wave path.
"""
import jax
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.api import CFNSession, FederatedSession, PlacementSpec
from repro.core import dynamic, federation, power, solvers, topology, vsr
from repro.fault.monitor import PlacementMonitor
from repro.kernels import ref as kref


@pytest.fixture(scope="module")
def topo():
    return topology.paper_topology()


def _quick_spec(**kw):
    return PlacementSpec(effort="quick", anneal_steps=0, defrag_every=0,
                         **kw)


def _services(topo, n, seed0=0, n_vms=3):
    iot = topo.layer_indices("iot")
    return [vsr.random_vsrs(1, rng=np.random.default_rng(seed0 + i),
                            n_vms=n_vms, source_nodes=iot[:4])
            for i in range(n)]


def _pair(topo, n=4, seed0=0, **spec_kw):
    """Two sessions with identical keys/specs, seeded with n live services
    via the per-event path (so only the follow-up churn differs)."""
    a = CFNSession(topo, _quick_spec(**spec_kw), key=jax.random.PRNGKey(7))
    b = CFNSession(topo, _quick_spec(**spec_kw), key=jax.random.PRNGKey(7))
    svcs = _services(topo, n, seed0=seed0)
    for i, sv in enumerate(svcs):
        assert a.add(sv, sid=i) is not None
        assert b.add(sv, sid=i) is not None
    return a, b, svcs


# ---------------------------------------------------------------------------
# waves of one: add / remove run the wave path, f64-oracle-checked
# ---------------------------------------------------------------------------

def _oracle_agrees(s, res):
    """The committed objective agrees with the f64 oracle's rescoring of
    the committed placement."""
    obj = kref.placement_objective_f64(s.problem, np.asarray(s.engine._X))
    assert abs(obj - float(res.objective)) <= 5e-2 + 1e-3 * abs(obj)


def test_wave_of_one_arrival_is_bit_identical_to_add(topo):
    """A one-arrival wave is ``add``: admitted with the right verdict lists
    and counters, pins kept, the objective the f64 oracle's, and the same
    placement as ``add`` from the same state.  Under a budget it cannot
    meet, the arrival is refused and the engine is left as it was."""
    a, b, _ = _pair(topo)
    fresh = _services(topo, 2, seed0=50)
    ra = a.add(fresh[0], sid=99)
    adm0 = dict(b.engine.admission)
    wr = b.apply_wave([(fresh[0], 99)])
    assert wr.sids == [99] and wr.admitted == [99]
    assert wr.rejected == [] and wr.queued == [] and wr.departed == []
    assert b.engine.admission == dict(adm0, admitted=adm0["admitted"] + 1)
    assert b.stats[-1].event == "add" and wr.result.method == "wave"
    _oracle_agrees(b, wr.result)
    p = b.problem
    fixed = np.asarray(p.fixed_mask)
    np.testing.assert_array_equal(np.asarray(b.X)[fixed],
                                  np.asarray(p.fixed_node)[fixed])
    np.testing.assert_array_equal(np.asarray(a.X), np.asarray(b.X))
    assert a.engine.admission == b.engine.admission
    assert float(ra.power) == float(wr.result.power)

    b.engine.brownout(1e-6)            # no arrival fits this budget
    X0, adm0 = np.asarray(b.X).copy(), dict(b.engine.admission)
    wr = b.apply_wave([(fresh[1], 100)])
    assert wr.admitted == [] and wr.rejected == [100] and wr.queued == []
    assert b.engine.admission == dict(adm0, rejected=adm0["rejected"] + 1)
    assert 100 not in b.sids and b.stats[-1].event == "reject"
    np.testing.assert_array_equal(np.asarray(b.X), X0)
    assert b.add(fresh[1], sid=100) is None


def test_wave_of_one_departure_is_bit_identical_to_remove(topo):
    """A one-departure wave is ``remove``: the service leaves, no admission
    counter moves, the objective is the f64 oracle's, and the placement is
    the one ``remove`` commits from the same state."""
    a, b, _ = _pair(topo)
    ra = a.remove(2)
    adm0 = dict(b.engine.admission)
    wr = b.apply_wave(departures=[2])
    assert wr.departed == [2] and wr.sids == []
    assert wr.admitted == wr.rejected == wr.queued == []
    assert b.sids == [0, 1, 3] and b.engine.admission == adm0
    assert b.stats[-1].event == "remove" and wr.result.method == "wave"
    _oracle_agrees(b, wr.result)
    np.testing.assert_array_equal(np.asarray(a.X), np.asarray(b.X))
    assert a.sids == b.sids
    assert float(ra.power) == float(wr.result.power)


def test_empty_wave_is_a_noop(topo):
    a, _, _ = _pair(topo, n=2)
    before = np.asarray(a.X).copy()
    wr = a.apply_wave()
    assert wr.admitted == [] and wr.departed == []
    np.testing.assert_array_equal(np.asarray(a.X), before)


# ---------------------------------------------------------------------------
# wave semantics: same-tick replace, accounting, validation
# ---------------------------------------------------------------------------

def test_wave_replace_keeps_live_count_and_bucket(topo):
    s = CFNSession(topo, _quick_spec(), key=jax.random.PRNGKey(3))
    for i, sv in enumerate(_services(topo, 4)):
        s.add(sv, sid=i)
    R_pad = s.problem.R
    fresh = _services(topo, 2, seed0=70)
    wr = s.apply_wave([(fresh[0], 10), (fresh[1], 11)], departures=[0, 1])
    assert s.n_live == 4
    assert s.problem.R == R_pad        # same shape bucket: no re-compile
    assert set(s.sids) == {2, 3, 10, 11}
    assert set(wr.admitted) == {10, 11} and wr.departed == [0, 1]
    # every arrival sid lands in exactly one verdict bucket
    verdicts = wr.admitted + wr.rejected + wr.queued
    assert sorted(verdicts) == sorted(wr.sids)
    # the committed placement is coherent under the f64 oracle
    obj = kref.placement_objective_f64(s.problem, np.asarray(s.engine._X))
    assert abs(obj - float(wr.result.objective)) <= \
        5e-2 + 1e-3 * abs(obj)


def test_wave_validates_inputs(topo):
    s = CFNSession(topo, _quick_spec(), key=jax.random.PRNGKey(0))
    sv = _services(topo, 1)[0]
    s.add(sv, sid=0)
    with pytest.raises(KeyError):
        s.apply_wave(departures=[5])
    with pytest.raises(ValueError):
        s.apply_wave(departures=[0, 0])
    with pytest.raises(ValueError):
        s.apply_wave([(sv, 0)])       # sid already live
    with pytest.raises(ValueError):
        s.apply_wave([(sv, 7), (sv, 7)])


# ---------------------------------------------------------------------------
# merge_timelines tie ordering + flash-crowd preset
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=30)
@given(seed=st.integers(0, 10_000))
def test_departures_sort_before_arrivals_within_every_wave(seed):
    """Property: however the same-tick events are interleaved on input,
    every wave out of merge_timelines + iter_waves applies departures
    first -- the ordering a same-tick replace relies on to never
    double-count capacity."""
    rng = np.random.default_rng(seed)
    events = []
    for t in range(int(rng.integers(1, 4))):
        for i in range(int(rng.integers(1, 6))):
            kind = "arrive" if rng.random() < 0.5 else "depart"
            events.append(dynamic.ServiceEvent(float(t), kind,
                                               int(rng.integers(0, 50))))
    rng.shuffle(events)
    merged = dynamic.merge_timelines(events)
    waves = list(dynamic.iter_waves(merged))
    assert sum(len(w) for w in waves) == len(events)
    for wave in waves:
        assert len({e.t for e in wave}) == 1
        kinds = [e.kind for e in wave]
        first_arrive = kinds.index("arrive") if "arrive" in kinds else None
        if first_arrive is not None:
            assert all(k == "arrive" for k in kinds[first_arrive:])


def test_fault_events_are_single_event_barrier_waves():
    events = dynamic.merge_timelines(
        [dynamic.ServiceEvent(1.0, "arrive", 0),
         dynamic.ServiceEvent(1.0, "depart", 9),
         dynamic.ServiceEvent(2.0, "arrive", 1)],
        [dynamic.FaultEvent(1.0, "fail_node", 3)])
    waves = list(dynamic.iter_waves(events))
    # tie order: depart < fail < arrive, and the fault is its own wave
    assert [[e.kind for e in w] for w in waves] == \
        [["depart"], ["fail_node"], ["arrive"], ["arrive"]]


def test_flash_crowd_replace_preset_roundtrip(topo):
    events = dynamic.flash_crowd_trace(4, 3, 4, rng=0, replace=True)
    waves = list(dynamic.iter_waves(events))
    assert len(waves) == 4                      # bootstrap + 3 churn waves
    assert [len(w) for w in waves] == [4, 4, 4, 4]
    for w in waves[1:]:                         # replace: 2 out, 2 in
        assert [e.kind for e in w] == ["depart"] * 2 + ["arrive"] * 2
    s = CFNSession(topo, _quick_spec(), key=jax.random.PRNGKey(1))
    make = lambda sid: _services(topo, 1, seed0=100 + sid)[0]
    s.replay(events, make, waves=True)
    assert s.n_live == 4                        # live count never moves


def test_flash_crowd_burst_preset_drains_to_steady(topo):
    events = dynamic.flash_crowd_trace(3, 2, 3, rng=0, replace=False)
    s = CFNSession(topo, _quick_spec(), key=jax.random.PRNGKey(1))
    make = lambda sid: _services(topo, 1, seed0=200 + sid)[0]
    s.replay(events, make, waves=True)
    assert set(s.sids) == {0, 1, 2}             # crowd fully drained


def test_replay_waves_and_per_event_agree_on_live_set(topo):
    events = dynamic.flash_crowd_trace(4, 2, 4, rng=3)
    make = lambda sid: _services(topo, 1, seed0=300 + sid)[0]
    a = CFNSession(topo, _quick_spec(), key=jax.random.PRNGKey(2))
    b = CFNSession(topo, _quick_spec(), key=jax.random.PRNGKey(2))
    a.replay(events, make)
    b.replay(events, make, waves=True)
    assert set(a.sids) == set(b.sids)


# ---------------------------------------------------------------------------
# priority admission, queue-drain fairness, preemption
# ---------------------------------------------------------------------------

def test_priority_classes_validated(topo):
    s = CFNSession(topo, _quick_spec(priority_classes=2),
                   key=jax.random.PRNGKey(0))
    sv = _services(topo, 1)[0]
    with pytest.raises(ValueError):
        s.add(sv, priority=2)
    with pytest.raises(ValueError):
        s.add(sv, priority=-1)
    with pytest.raises(ValueError):
        PlacementSpec(priority_classes=0)


def test_queue_drains_in_priority_order(topo):
    s = CFNSession(topo, _quick_spec(priority_classes=3,
                                     queue_rejected=True),
                   key=jax.random.PRNGKey(0))
    anchor = _services(topo, 1)[0]
    s.add(anchor, sid=0)
    s.engine.brownout(0.0)          # nothing fits a zero-watt budget
    svcs = _services(topo, 3, seed0=40)
    for sid, prio in [(1, 2), (2, 0), (3, 1)]:
        assert s.add(svcs[sid - 1], sid=sid, priority=prio) is None
    assert s.engine.queued_sids == [2, 3, 1]    # heap order: class first
    s.engine.brownout_end()
    # drain admits class 0 first, then 1, then 2 -- reflected in row order
    assert s.sids == [0, 2, 3, 1]
    assert not s.engine._queue


def test_departure_drains_queue_until_first_rerejection(topo):
    """Satellite regression: ANY capacity-increasing event retries the
    queue until the first re-rejection -- under a still-zero budget the
    first retry re-parks and the rest never run; once the brownout lifts,
    every queued service that fits is admitted."""
    s = CFNSession(topo, _quick_spec(queue_rejected=True),
                   key=jax.random.PRNGKey(0))
    svcs = _services(topo, 5)
    for i in range(2):
        assert s.add(svcs[i], sid=i) is not None
    s.engine.brownout(0.0)
    for i in range(2, 5):
        assert s.add(svcs[i], sid=i) is None
    assert len(s.engine._queue) == 3
    s.remove(0)                     # capacity up, but budget still zero
    assert set(s.sids) == {1}
    assert len(s.engine._queue) == 3   # first retry re-parked, drain stopped
    s.engine.brownout_end()         # budget restored: full drain
    assert set(s.sids) == {1, 2, 3, 4}
    assert not s.engine._queue


def test_queue_reattempt_keeps_its_place_and_counts_once(topo):
    """A drained arrival that is refused again goes back to its old heap
    position and is not counted again as rejected or queued (the counters
    and the monitor count distinct arrivals)."""
    mon = PlacementMonitor()
    s = CFNSession(topo, _quick_spec(queue_rejected=True),
                   key=jax.random.PRNGKey(0), monitor=mon)
    svcs = _services(topo, 4)
    for i in range(2):
        assert s.add(svcs[i], sid=i) is not None
    s.engine.brownout(0.0)
    for i in (2, 3):
        assert s.add(svcs[i], sid=i) is None
    adm = dict(s.engine.admission)
    assert adm["rejected"] == 2 and adm["queued"] == 2
    assert mon["admission_rejected"] == 2
    heap = sorted(e[:3] for e in s.engine._queue)    # (class, seq, sid)
    s.remove(0)                     # drain re-attempts sid 2: refused
    assert s.engine.admission == adm
    assert mon["admission_rejected"] == 2
    assert sorted(e[:3] for e in s.engine._queue) == heap
    assert s.engine.queued_sids == [2, 3]


def test_source_down_reattempt_is_not_queued_again(topo):
    """An arrival whose source is down is parked once; a drain's
    re-attempt while the source is still down re-parks it at the same
    position without counting it again."""
    s = CFNSession(topo, _quick_spec(), key=jax.random.PRNGKey(0))
    iot = topo.layer_indices("iot")
    for i, sv in enumerate(_services(topo, 2)):
        assert s.add(sv, sid=i) is not None
    s.engine.fail_node(iot[5])
    down = vsr.random_vsrs(1, rng=np.random.default_rng(77), n_vms=3,
                           source_nodes=[iot[5]])
    wr = s.apply_wave([(down, 9)])
    assert wr.queued == [9] and wr.admitted == [] and wr.rejected == []
    assert s.stats[-1].event == "strand"
    adm = dict(s.engine.admission)
    heap = [e[:3] for e in s.engine._queue]
    s.remove(0)                     # drain re-attempts sid 9: still down
    assert s.engine.admission == adm
    assert [e[:3] for e in s.engine._queue] == heap
    s.engine.recover_node(iot[5])
    assert 9 in s.sids and not s.engine._queue


def test_recovery_admits_all_queued_that_fit(topo):
    """A recovery must re-admit EVERY parked service that now fits, not
    one -- the queue-drain fairness fix."""
    s = CFNSession(topo, _quick_spec(queue_rejected=True),
                   key=jax.random.PRNGKey(0))
    iot = topo.layer_indices("iot")
    src = iot[0]
    svcs = [vsr.random_vsrs(1, rng=np.random.default_rng(10 + i),
                            n_vms=3, source_nodes=[src])
            for i in range(3)]
    for i, sv in enumerate(svcs):
        assert s.add(sv, sid=i) is not None
    s.engine.fail_node(src)         # all three strand on the dead source
    assert s.n_live == 0
    assert len(s.engine.queued_sids) == 3
    s.engine.recover_node(src)
    assert set(s.sids) == {0, 1, 2}     # one recovery, all three back
    assert not s.engine._queue


def test_preemption_parks_lower_class_for_higher(topo):
    s = CFNSession(topo, _quick_spec(priority_classes=2, preempt=True,
                                     queue_rejected=True),
                   key=jax.random.PRNGKey(0))
    svcs = _services(topo, 3)
    assert s.add(svcs[0], sid=0, priority=0) is not None
    assert s.add(svcs[1], sid=1, priority=1) is not None   # the victim
    s.engine.brownout(0.0)          # all marginal power now over budget
    s.add(svcs[2], sid=2, priority=0)
    # the power refusal preempted the newest lowest-class service ...
    assert s.engine.admission["preempted"] == 1
    assert 1 in s.engine.queued_sids
    assert 0 in s.sids
    # ... and never a same-or-higher-class one
    assert all(s.engine._prio[s.sids.index(sid)] == 0 for sid in s.sids)
    s.engine.brownout_end()
    assert set(s.sids) >= {0, 1}    # the victim returns once budget lifts


def test_wave_admission_is_priority_ordered_under_budget(topo):
    """Under a marginal power budget a wave refuses lowest class first."""
    s = CFNSession(topo, _quick_spec(priority_classes=2,
                                     queue_rejected=True),
                   key=jax.random.PRNGKey(0))
    base = _services(topo, 1)[0]
    assert s.add(base, sid=0) is not None
    s.engine.brownout(0.0)
    fresh = _services(topo, 2, seed0=60)
    wr = s.apply_wave([(fresh[0], 1, 0), (fresh[1], 2, 1)])
    # zero budget refuses both, but class 1 is chosen for refusal first
    assert set(wr.queued) == {1, 2}
    assert s.engine.queued_sids[0] == 1     # class 0 parked at heap top


# ---------------------------------------------------------------------------
# amortized background defrag
# ---------------------------------------------------------------------------

def test_defrag_tick_never_regresses_and_carries_cursor(topo):
    spec = _quick_spec(defrag_rows_per_tick=2)
    s = CFNSession(topo, spec, key=jax.random.PRNGKey(0))
    for i, sv in enumerate(_services(topo, 5)):
        s.add(sv, sid=i)
    objs = [float(s.result.objective)]
    cursors = [s.engine._defrag_cursor]
    for _ in range(6):
        res = s.defrag_tick()
        if res is not None:
            assert res.method == "defrag_tick"
        objs.append(float(s.result.objective))
        cursors.append(s.engine._defrag_cursor)
    for prev, cur in zip(objs, objs[1:]):
        assert cur <= prev + 1e-9          # never-regressing
    # round-robin cursor: advances by K mod n_live each tick
    for prev, cur in zip(cursors, cursors[1:]):
        assert cur == (prev + 2) % s.n_live


def test_defrag_rows_per_tick_disables_periodic_full_defrag(topo):
    spec = PlacementSpec(effort="quick", anneal_steps=0, defrag_every=2,
                         defrag_rows_per_tick=1)
    s = CFNSession(topo, spec, key=jax.random.PRNGKey(0))
    for i, sv in enumerate(_services(topo, 6)):
        s.add(sv, sid=i)
    # defrag_every=2 would have forced full re-packs; the amortized mode
    # keeps every event on the incremental path
    assert all(st.method != "defrag"
               for st in s.engine.stats if st.event == "add")
    assert not s.engine._defrag_due()


def test_defrag_tick_empty_engine_is_noop(topo):
    s = CFNSession(topo, _quick_spec(defrag_rows_per_tick=2),
                   key=jax.random.PRNGKey(0))
    assert s.defrag_tick() is None


# ---------------------------------------------------------------------------
# compile stability: one trace set per wave-shape bucket
# ---------------------------------------------------------------------------

def test_wave_zero_fresh_traces_after_warmup(topo):
    s = CFNSession(topo, _quick_spec(), key=jax.random.PRNGKey(0))
    for i, sv in enumerate(_services(topo, 6)):
        s.add(sv, sid=i)
    fresh = _services(topo, 8, seed0=80)
    # warmup wave: compiles the wave-bucket variants once
    s.apply_wave([(fresh[0], 10), (fresh[1], 11)], departures=[0, 1])
    before = dict(solvers.TRACE_COUNTS)
    # same bucket (2 dep + 2 arr at the same live count): zero fresh traces
    s.apply_wave([(fresh[2], 12), (fresh[3], 13)], departures=[2, 3])
    assert solvers.TRACE_COUNTS == before, \
        "a same-bucket wave must not retrace solver kernels"


def test_defrag_tick_zero_fresh_traces_after_warmup(topo):
    s = CFNSession(topo, _quick_spec(defrag_rows_per_tick=2),
                   key=jax.random.PRNGKey(0))
    for i, sv in enumerate(_services(topo, 5)):
        s.add(sv, sid=i)
    s.defrag_tick()
    before = dict(solvers.TRACE_COUNTS)
    for _ in range(4):
        s.defrag_tick()
    assert solvers.TRACE_COUNTS == before, \
        "same-bucket defrag ticks must not retrace solver kernels"


# ---------------------------------------------------------------------------
# federated per-region waves
# ---------------------------------------------------------------------------

def _fed_topo():
    return topology.federated_scale(n_regions=3, n_olt=1, onus_per_olt=2,
                                    iot_per_onu=2, n_core=6)


def test_federated_wave_batches_per_region():
    ftopo = _fed_topo()
    part = federation.RegionPartition.from_topology(ftopo)
    srcs = [int(r.proc_ids[0]) for r in part.regions]
    sess = FederatedSession(ftopo, PlacementSpec(effort="quick"),
                            key=jax.random.PRNGKey(0))
    mk = lambda sid: vsr.random_vsrs(1, rng=100 + sid,
                                     source_nodes=[srcs[sid % 3]])
    wr = sess.apply_wave([(mk(i), i) for i in range(6)])
    assert sorted(wr.admitted) == list(range(6))
    assert {sess.assignment(i) for i in range(6)} == {0, 1, 2}
    wr2 = sess.apply_wave([(mk(10), 10)], departures=[0, 3])
    assert wr2.departed == [0, 3] and wr2.admitted == [10]
    assert sess.n_live == 5
    # power accounting stays exact through the batched path
    bd = sess.breakdown()
    assert bd.total_w > 0 and np.all(np.asarray(bd.regional_w) >= 0)


def test_federated_wave_of_one_matches_per_event():
    ftopo = _fed_topo()
    part = federation.RegionPartition.from_topology(ftopo)
    srcs = [int(r.proc_ids[0]) for r in part.regions]
    mk = lambda sid: vsr.random_vsrs(1, rng=100 + sid,
                                     source_nodes=[srcs[sid % 3]])
    a = FederatedSession(ftopo, PlacementSpec(effort="quick"),
                         key=jax.random.PRNGKey(2))
    b = FederatedSession(ftopo, PlacementSpec(effort="quick"),
                         key=jax.random.PRNGKey(2))
    for i in range(3):
        a.add(mk(i), sid=i)
        b.apply_wave([(mk(i), i)])
    np.testing.assert_array_equal(np.asarray(a.X), np.asarray(b.X))
    assert a.sids == b.sids


def test_federated_replay_waves():
    ftopo = _fed_topo()
    part = federation.RegionPartition.from_topology(ftopo)
    srcs = [int(r.proc_ids[0]) for r in part.regions]
    mk = lambda sid: vsr.random_vsrs(1, rng=100 + sid,
                                     source_nodes=[srcs[sid % 3]])
    sess = FederatedSession(ftopo, PlacementSpec(effort="quick",
                                                 defrag_rows_per_tick=1),
                            key=jax.random.PRNGKey(1))
    events = dynamic.flash_crowd_trace(3, 2, 2, rng=0)
    stats = sess.replay(events, mk, waves=True)
    assert sess.n_live == 3
    assert len(stats) == len(events)


def test_federated_rejects_preempt():
    ftopo = _fed_topo()
    with pytest.raises(ValueError, match="preempt"):
        FederatedSession(ftopo, PlacementSpec(effort="quick", preempt=True),
                         key=jax.random.PRNGKey(0))
