"""Online engine tests: service-granular state ops (attach/detach/warm),
incremental re-embedding, per-service power attribution, churn timelines,
and the OnlineEmbedder / scheduler event loop.

The attach/detach yardstick is kernels.ref.placement_objective_f64 -- the
float64 objective whose own error is ~1e-10 -- so tolerances measure the
float32 state math, not reference noise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import dynamic, power, solvers, topology, vsr
from repro.kernels import ref


@pytest.fixture(scope="module")
def topo():
    return topology.paper_topology()


def _services(n, seed0=100, **kw):
    return [vsr.random_vsrs(1, rng=seed0 + i, source_nodes=[0], **kw)
            for i in range(n)]


def _concat(batches):
    out = batches[0]
    for b in batches[1:]:
        out = out.concat(b)
    return out


# ---------------------------------------------------------------------------
# attach / detach / warm_state vs the float64 oracle
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 10_000), r=st.integers(0, 5))
def test_attach_detach_roundtrip_matches_f64_oracle(seed, r):
    """detach(attach) is the identity AND the detached objective equals the
    float64 oracle of the problem without that service."""
    topo = topology.paper_topology()
    vs = vsr.random_vsrs(6, rng=seed, source_nodes=[0])
    prob = power.build_problem(topo, vs)
    rng = np.random.default_rng(seed)
    X = rng.integers(0, prob.P, size=(prob.R, prob.V)).astype(np.int32)
    st0 = power.init_state(prob, jnp.asarray(X))
    Xp = np.asarray(st0.X)

    det = power.detach_vsrs(prob, st0, [r])
    back = power.attach_vsrs(prob, det, [r])
    for name in ("omega", "tm", "theta", "lam"):
        np.testing.assert_allclose(np.asarray(getattr(back, name)),
                                   np.asarray(getattr(st0, name)),
                                   rtol=1e-5, atol=1e-2)
    assert abs(float(back.obj) - float(st0.obj)) <= \
        1e-3 + 1e-6 * abs(float(st0.obj))

    keep = [i for i in range(prob.R) if i != r]
    vs_red = vsr.VSRBatch(F=vs.F[keep], H=vs.H[keep], src=vs.src[keep],
                          input_vm=vs.input_vm[keep])
    prob_red = power.build_problem(topo, vs_red)
    want = ref.placement_objective_f64(prob_red, Xp[keep])
    assert abs(float(det.obj) - want) <= 5e-2 + 1e-5 * abs(want)


def test_attach_with_explicit_rows_equals_init_state(topo):
    """attach_vsrs(X_rows=...) writes the placement and its loads in one
    step: the result matches a from-scratch init_state."""
    vs = vsr.random_vsrs(4, rng=3, source_nodes=[0])
    prob = power.build_problem(topo, vs)
    rng = np.random.default_rng(3)
    X = rng.integers(0, prob.P, size=(prob.R, prob.V)).astype(np.int32)
    st0 = power.init_state(prob, jnp.asarray(X))
    det = power.detach_vsrs(prob, st0, [1])
    new_row = rng.integers(0, prob.P, size=(1, prob.V)).astype(np.int32)
    got = power.attach_vsrs(prob, det, [1], X_rows=new_row)
    X2 = np.asarray(st0.X).copy()
    X2[1] = new_row[0]
    want = power.init_state(prob, jnp.asarray(X2))
    np.testing.assert_array_equal(np.asarray(got.X), np.asarray(want.X))
    assert abs(float(got.obj) - float(want.obj)) <= \
        1e-3 + 1e-6 * abs(float(want.obj))


def test_warm_state_grow_and_shrink(topo):
    """Carrying loads through arrival (grow) and departure (shrink) matches
    a from-scratch state build, including a VM-width change."""
    wide = vsr.random_vsrs(3, rng=0, n_vms=4, source_nodes=[0])
    prob = power.build_problem(topo, wide)
    rng = np.random.default_rng(1)
    X = rng.integers(0, prob.P, size=(prob.R, prob.V)).astype(np.int32)
    st0 = power.init_state(prob, jnp.asarray(X))
    loads = (st0.omega, st0.tm, st0.theta, st0.lam)

    # grow by a NARROWER service (width stays 4, new row padded)
    narrow = vsr.random_vsrs(1, rng=7, n_vms=2, source_nodes=[0])
    grown = wide.concat(narrow)
    prob_g = power.build_problem(topo, grown)
    wg = power.warm_state(prob_g, np.asarray(st0.X), prev_loads=loads)
    fresh = power.init_state(prob_g, wg.X)
    assert abs(float(wg.obj) - float(fresh.obj)) <= \
        1e-3 + 1e-6 * abs(float(fresh.obj))
    # survivors kept their placement
    np.testing.assert_array_equal(np.asarray(wg.X)[:3], np.asarray(st0.X))

    # shrink: drop row 1, carried loads from detach
    det = power.detach_vsrs(prob, st0, [1])
    keep = [0, 2]
    vs_red = vsr.VSRBatch(F=wide.F[keep], H=wide.H[keep], src=wide.src[keep],
                          input_vm=wide.input_vm[keep])
    prob_s = power.build_problem(topo, vs_red)
    ws = power.warm_state(prob_s, np.asarray(st0.X),
                          prev_loads=(det.omega, det.tm, det.theta, det.lam),
                          row_map=keep)
    fresh_s = power.init_state(prob_s, ws.X)
    assert abs(float(ws.obj) - float(fresh_s.obj)) <= \
        1e-3 + 1e-6 * abs(float(fresh_s.obj))
    np.testing.assert_array_equal(np.asarray(ws.X),
                                  np.asarray(st0.X)[keep])


def test_warm_state_rejects_bad_row_map(topo):
    vs = vsr.random_vsrs(2, rng=0, source_nodes=[0])
    prob = power.build_problem(topo, vs)
    with pytest.raises(ValueError):
        power.warm_state(prob, np.zeros((2, 3), np.int32), row_map=[0])


# ---------------------------------------------------------------------------
# per-service power attribution
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 10_000))
def test_attribution_sums_to_total(seed):
    topo = topology.paper_topology()
    vs = vsr.random_vsrs(5, rng=seed, source_nodes=[0])
    prob = power.build_problem(topo, vs)
    rng = np.random.default_rng(seed)
    X = rng.integers(0, prob.P, size=(prob.R, prob.V)).astype(np.int32)
    bd = power.evaluate(prob, power.apply_pins(prob, jnp.asarray(X)))
    per = power.attribute_power(prob, X, bd)
    assert per.shape == (prob.R,)
    assert np.all(per >= -1e-9)
    np.testing.assert_allclose(per.sum(), float(bd.total),
                               rtol=1e-5, atol=1e-3)


def test_attribution_isolated_service_pays_its_own_way(topo):
    """Two identical services on disjoint nodes split the total evenly;
    a heavier service is attributed more."""
    vs = vsr.VSRBatch(
        F=np.array([[0.5, 4.0], [0.5, 8.0]], np.float32),
        H=np.zeros((2, 2, 2), np.float32),
        src=np.array([0, 1], np.int32), input_vm=np.zeros(2, np.int32))
    vs.H[:, 0, 1] = 20.0
    prob = power.build_problem(topo, vs)
    cdc = topo.proc_index("cdc0")
    X = np.array([[0, cdc], [1, cdc]], np.int32)
    per = power.attribute_power(prob, X)
    bd = power.evaluate(prob, jnp.asarray(X))
    np.testing.assert_allclose(per.sum(), float(bd.total), rtol=1e-6)
    assert per[1] > per[0]  # heavier stage at the shared CDC pays more


# ---------------------------------------------------------------------------
# incremental re-solve
# ---------------------------------------------------------------------------

def test_resolve_incremental_close_to_portfolio(topo):
    """One arrival on a warm 5-service placement lands within 1% of the
    from-scratch portfolio, keeps pins, and reports a sane history."""
    base = _concat(_services(5))
    prob_b = power.build_problem(topo, base)
    warm = solvers.solve_cfn(prob_b, topo, jax.random.PRNGKey(0))
    grown = base.concat(_services(1, seed0=500)[0])
    prob = power.build_problem(topo, grown)
    st = power.warm_state(prob, warm.X)
    res = solvers.resolve_incremental(prob, np.asarray(st.X),
                                      key=jax.random.PRNGKey(1),
                                      changed_rows=[5], state=st)
    scratch = solvers.solve_cfn(prob, topo, jax.random.PRNGKey(2))
    assert res.objective <= scratch.objective * 1.01
    fixed_mask = np.asarray(prob.fixed_mask)
    np.testing.assert_array_equal(res.X[fixed_mask],
                                  np.asarray(prob.fixed_node)[fixed_mask])
    assert res.method == "incremental"
    assert res.history[-1] <= res.history[0] + 1e-6


def test_resolve_incremental_departure_repacks(topo):
    """changed_rows=[] (a departure): the re-solve never worsens the carried
    placement and stays feasible."""
    vs = _concat(_services(6))
    prob6 = power.build_problem(topo, vs)
    warm = solvers.solve_cfn(prob6, topo, jax.random.PRNGKey(0))
    keep = [0, 1, 3, 4, 5]
    vs_red = vsr.VSRBatch(F=vs.F[keep], H=vs.H[keep], src=vs.src[keep],
                          input_vm=vs.input_vm[keep])
    prob = power.build_problem(topo, vs_red)
    X0 = warm.X[keep]
    start = float(power.objective(prob, jnp.asarray(X0)))
    res = solvers.resolve_incremental(prob, X0, key=jax.random.PRNGKey(1),
                                      changed_rows=[])
    assert res.objective <= start + 1e-6
    assert res.feasible


def test_resolve_incremental_state_only_no_prev_x(topo):
    """Warm callers pass state WITHOUT prev_X: materializing
    ``np.asarray(state.X)`` just to fill an unread argument was a dead
    device->host transfer per churn event (CFN101 hazard class).  The
    state-only call must match the legacy call bit-for-bit."""
    base = _concat(_services(5))
    prob_b = power.build_problem(topo, base)
    warm = solvers.solve_cfn(prob_b, topo, jax.random.PRNGKey(0))
    grown = base.concat(_services(1, seed0=500)[0])
    prob = power.build_problem(topo, grown)
    st = power.warm_state(prob, warm.X)
    kw = dict(changed_rows=[5], key=jax.random.PRNGKey(1))
    res_new = solvers.resolve_incremental(prob, state=st, **kw)
    res_old = solvers.resolve_incremental(prob, np.asarray(st.X), state=st,
                                          **kw)
    np.testing.assert_array_equal(res_new.X, res_old.X)
    assert res_new.objective == res_old.objective
    with pytest.raises(ValueError, match="prev_X or state"):
        solvers.resolve_incremental(prob)


def test_project_eligible_host_side_moved_flag(topo):
    """_project_eligible reports whether projection moved anything as a
    host bool (replacing the old on-device ``(X0 == state.X).all()``
    compare -- a blocking sync per masked churn event).  The flag must be
    exact: False iff the projected array is unchanged."""
    vs = _concat(_services(4))
    prob = power.build_problem(topo, vs)
    st = power.init_state(prob, jnp.zeros((prob.R, prob.V), jnp.int32))
    el = np.ones((prob.R, prob.P), bool)
    proj, moved = solvers._project_eligible(prob, st.X, el)
    assert moved is False
    np.testing.assert_array_equal(np.asarray(proj), np.asarray(st.X))
    # forbid node 0 (where every free VM sits): projection must move them
    el0 = el.copy()
    el0[:, 0] = False
    proj, moved = solvers._project_eligible(prob, st.X, el0)
    assert moved is True
    free = ~np.asarray(prob.fixed_mask)
    rows = np.arange(prob.R)[:, None]
    assert el0[np.broadcast_to(rows, proj.shape)[free],
               np.asarray(proj)[free]].all()
    # the warm masked re-solve path stays inside the mask end-to-end
    res = solvers.resolve_incremental(prob, state=st, eligible=el0,
                                      key=jax.random.PRNGKey(3),
                                      anneal_steps=50, anneal_chains=2)
    assert el0[np.broadcast_to(rows, res.X.shape)[free],
               res.X[free]].all()


# ---------------------------------------------------------------------------
# timelines
# ---------------------------------------------------------------------------

def test_diurnal_rate_profile():
    r = dynamic.diurnal_rate(np.arange(0.0, 48.0, 0.5), 1.0, 5.0,
                             peak_hour=20.0)
    assert r.min() >= 1.0 - 1e-9 and r.max() <= 5.0 + 1e-9
    assert abs(float(dynamic.diurnal_rate(20.0, 1.0, 5.0, 20.0)) - 5.0) < 1e-9
    assert abs(float(dynamic.diurnal_rate(8.0, 1.0, 5.0, 20.0)) - 1.0) < 1e-9
    # 24h periodic
    np.testing.assert_allclose(r[:48], r[48:96], rtol=1e-12)


def test_poisson_timeline_well_formed():
    ev = dynamic.poisson_timeline(24.0, lambda t: 3.0, 2.0, rng=0)
    assert len(ev) > 10
    ts = [e.t for e in ev]
    assert ts == sorted(ts)
    seen = {}
    for e in ev:
        if e.kind == "arrive":
            assert e.sid not in seen
            seen[e.sid] = e.t
        else:
            assert e.sid in seen and e.t >= seen[e.sid]
    # deterministic under the same seed
    ev2 = dynamic.poisson_timeline(24.0, lambda t: 3.0, 2.0, rng=0)
    assert ev == ev2


def test_churn_trace_single_event_granularity():
    ev = dynamic.churn_trace(4, 6, rng=0)
    assert len(ev) == 10
    live = set()
    for e in ev[:4]:
        assert e.kind == "arrive"
        live.add(e.sid)
    for e in ev[4:]:
        if e.kind == "depart":
            assert e.sid in live
            live.discard(e.sid)
        else:
            live.add(e.sid)
    assert len(live) == 4  # alternating events preserve steady state


def test_scenario_presets_sample():
    for name, sc in dynamic.SCENARIOS.items():
        v = sc.sample_vsr(0)
        assert v.R == 1 and v.V == sc.n_vms
        assert callable(sc.rate_fn())


# ---------------------------------------------------------------------------
# the online engine
# ---------------------------------------------------------------------------

def test_online_embedder_event_loop(topo):
    """bootstrap -> add -> remove: state stays consistent with a fresh
    evaluation, per-service watts sum to the fleet total, and the final
    objective is within 2% of a from-scratch portfolio solve."""
    eng = dynamic.OnlineEmbedder(topo, defrag_every=0,
                                 key=jax.random.PRNGKey(0))
    svcs = _services(4)
    eng.bootstrap(svcs)
    assert eng.n_live == 4 and eng.result.method.startswith("cfn-milp")

    eng.add(_services(1, seed0=900)[0])
    assert eng.n_live == 5
    assert eng.result.method == "wave"
    # engine state agrees with a fresh evaluation of its placement
    fresh = power.init_state(eng.problem, jnp.asarray(eng.X))
    assert abs(eng.objective() - float(fresh.obj)) <= \
        1e-3 + 1e-6 * abs(float(fresh.obj))

    per = eng.per_service_power_w()
    assert set(per) == set(eng.sids)
    np.testing.assert_allclose(sum(per.values()), eng.power_w(),
                               rtol=1e-5, atol=1e-3)

    eng.remove(eng.sids[1])
    assert eng.n_live == 4
    # local re-pack stays in the ballpark; defrag() never regresses (small
    # instances leave the most on the table for a purely local re-solve)
    scratch = solvers.solve_cfn(eng.problem, topo, jax.random.PRNGKey(9))
    assert eng.objective() <= scratch.objective * 1.10
    before = eng.objective()
    eng.defrag()
    assert eng.objective() <= before + 1e-6

    # events were recorded
    kinds = [s.event for s in eng.stats]
    assert kinds == ["bootstrap", "add", "remove", "defrag"]


@pytest.mark.slow
def test_online_embedder_defrag_and_drain(topo):
    eng = dynamic.OnlineEmbedder(topo, defrag_every=2,
                                 key=jax.random.PRNGKey(1))
    s = _services(3, seed0=300)
    eng.add(s[0])                     # first event: full solve
    eng.add(s[1])                     # incremental
    eng.add(s[2])                     # 2 events since defrag -> full again
    assert eng.stats[-1].method.startswith(("cfn-milp", "defrag-kept"))
    eng.remove(eng.sids[0])
    eng.remove(eng.sids[0])
    last = eng.remove(eng.sids[0])    # drains the engine
    assert last is None and eng.n_live == 0 and eng.power_w() == 0.0
    # engine is reusable after draining
    eng.add(s[0])
    assert eng.n_live == 1 and eng.objective() > 0


def test_replay_skips_unmaterialized_departures(topo):
    sc = dynamic.SCENARIOS["steady"]
    events = [dynamic.ServiceEvent(0.0, "arrive", 0),
              dynamic.ServiceEvent(0.5, "depart", 99),   # never arrived
              dynamic.ServiceEvent(1.0, "arrive", 1),
              dynamic.ServiceEvent(2.0, "depart", 0)]
    eng = dynamic.OnlineEmbedder(topo, defrag_every=0)
    stats = dynamic.replay(eng, events, lambda sid: sc.sample_vsr(sid))
    assert eng.n_live == 1
    assert [s.event for s in stats] == ["add", "add", "remove"]


def test_online_embedder_rejects_bad_inputs(topo):
    sc = dynamic.SCENARIOS["steady"]
    eng = dynamic.OnlineEmbedder(topo, defrag_every=0)
    with pytest.raises(ValueError):
        dynamic.OnlineEmbedder(topo, method="nope")
    with pytest.raises(ValueError):
        eng.bootstrap([])
    with pytest.raises(ValueError):
        eng.bootstrap([sc.sample_vsr(0)], sids=[1, 2])
    eng.add(sc.sample_vsr(0), sid=5)
    with pytest.raises(ValueError):      # sid already live
        eng.add(sc.sample_vsr(1), sid=5)
    assert eng.sids == [5]               # rejected before any mutation
    eng.add(sc.sample_vsr(1), sid=6)
    assert eng.sids == [5, 6]


def test_replay_departs_bootstrapped_services(topo):
    """Departures of services admitted via bootstrap() (not by this replay)
    must still be executed."""
    sc = dynamic.SCENARIOS["steady"]
    eng = dynamic.OnlineEmbedder(topo, defrag_every=0)
    eng.bootstrap([sc.sample_vsr(0), sc.sample_vsr(1)], sids=[10, 11])
    events = [dynamic.ServiceEvent(1.0, "depart", 10),
              dynamic.ServiceEvent(2.0, "arrive", 12)]
    dynamic.replay(eng, events, lambda sid: sc.sample_vsr(sid))
    assert eng.n_live == 2 and set(eng.sids) == {11, 12}
