"""Delta-evaluation engine tests: exactness of single-VM move deltas, state
consistency over random move sequences (including beta/phi indicator flips),
the vectorized destination sweep, and the fused Pallas annealing kernel.

The yardstick is kernels.ref.placement_delta_ref -- a float64 objective
difference whose own error is ~1e-10 -- so the asserted tolerance measures
the engine's float32 delta math, not reference cancellation noise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import power, solvers, topology, vsr
from repro.kernels import ops, ref
from repro.kernels import placement_power as pp


@pytest.fixture(scope="module")
def topo():
    return topology.paper_topology()


def _problem(topo, n_vsrs=10, seed=0, **kw):
    vs = vsr.random_vsrs(n_vsrs, rng=seed, source_nodes=[0], **kw)
    return power.build_problem(topo, vs)


def _random_moves(prob, aux, rng, n):
    free = np.asarray(aux.free_pos)
    for _ in range(n):
        r, v = free[rng.integers(0, len(free))]
        yield int(r), int(v), int(rng.integers(0, prob.P))


def test_delta_move_exact_feasible_sequence(topo):
    """Paper scale (R=10), feasible-leaning workload: every delta along a
    random 150-move sequence matches the float64 oracle to <= 1e-3."""
    prob = _problem(topo, vm_gflops=(0.5, 2.0))
    aux = power.build_aux(prob)
    rng = np.random.default_rng(1)
    st = power.init_state(prob, solvers.fixed_layer(prob, topo, "iot").X)
    for r, v, p_new in _random_moves(prob, aux, rng, 150):
        got = float(power.delta_move(prob, aux, st, r, v, p_new))
        want = ref.placement_delta_ref(prob, np.asarray(st.X), r, v, p_new)
        assert abs(got - want) <= 1e-3, (r, v, p_new, got, want)
        st = power.apply_move(prob, aux, st, r, v, p_new)


def test_delta_move_exact_violated_sequence(topo):
    """Heavy workload (capacity violations active, PENALTY-scaled terms):
    deltas stay exact to float32 resolution of the violation magnitudes."""
    prob = _problem(topo)
    aux = power.build_aux(prob)
    rng = np.random.default_rng(0)
    X = rng.integers(0, prob.P, size=(prob.R, prob.V)).astype(np.int32)
    st = power.init_state(prob, jnp.asarray(X))
    for r, v, p_new in _random_moves(prob, aux, rng, 150):
        got = float(power.delta_move(prob, aux, st, r, v, p_new))
        want = ref.placement_delta_ref(prob, np.asarray(st.X), r, v, p_new)
        # floor: PENALTY * ulp(float32 load entries) ~= 1e4 * 6e-8 * 60
        # GFLOPS ~= 4e-2, independent of the objective's size -- fp32
        # resolution of the relu'd capacity terms, not engine error
        assert abs(got - want) <= 5e-2, (r, v, p_new, got, want)
        st = power.apply_move(prob, aux, st, r, v, p_new)


def test_state_consistent_with_full_evaluate(topo):
    """After a random move sequence every live tensor (omega, tm, theta,
    lam) and the cached objective agree with a from-scratch evaluation."""
    prob = _problem(topo)
    aux = power.build_aux(prob)
    rng = np.random.default_rng(2)
    X = rng.integers(0, prob.P, size=(prob.R, prob.V)).astype(np.int32)
    st = power.init_state(prob, jnp.asarray(X))
    for r, v, p_new in _random_moves(prob, aux, rng, 300):
        st = power.apply_move(prob, aux, st, r, v, p_new)
    fresh = power.init_state(prob, st.X)
    np.testing.assert_allclose(np.asarray(st.omega), np.asarray(fresh.omega),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st.tm), np.asarray(fresh.tm),
                               rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(np.asarray(st.theta), np.asarray(fresh.theta),
                               rtol=1e-5, atol=1e-2)
    np.testing.assert_allclose(np.asarray(st.lam), np.asarray(fresh.lam),
                               rtol=1e-5, atol=1e-2)
    assert abs(float(st.obj) - float(fresh.obj)) <= \
        1e-3 + 1e-6 * abs(float(fresh.obj))
    bd = power.evaluate(prob, st.X)
    np.testing.assert_allclose(float(fresh.obj), float(bd.objective),
                               rtol=1e-6)


def test_delta_indicator_flips(topo):
    """Moves onto an empty node (phi 0->1) and off it again (1->0), plus a
    beta flip when the last traffic leaves a route, are exact."""
    vs = vsr.VSRBatch(
        F=np.array([[0.5, 8.0]], np.float32),
        H=np.zeros((1, 2, 2), np.float32),
        src=np.array([0], np.int32), input_vm=np.array([0], np.int32))
    vs.H[0, 0, 1] = 20.0
    prob = power.build_problem(topo, vs)
    aux = power.build_aux(prob)
    cdc = topo.proc_index("cdc0")
    st = power.init_state(prob, jnp.asarray([[0, cdc]], jnp.int32))
    # cdc -> empty iot node 5: phi flips ON at 5, OFF at cdc; the metro/core
    # route empties so several beta_n flip OFF
    for p_new in (5, cdc, 0, 7, cdc):
        want = ref.placement_delta_ref(prob, np.asarray(st.X), 0, 1, p_new)
        got = float(power.delta_move(prob, aux, st, 0, 1, p_new))
        assert abs(got - want) <= 1e-3, (p_new, got, want)
        st = power.apply_move(prob, aux, st, 0, 1, p_new)
        fresh = power.init_state(prob, st.X)
        assert abs(float(st.obj) - float(fresh.obj)) <= 1e-3
        # moving the only traffic-bearing VM around must keep lam exact
        np.testing.assert_allclose(np.asarray(st.lam),
                                   np.asarray(fresh.lam), atol=1e-3)


def test_delta_sweep_matches_objective_batch(topo):
    """delta_sweep == objective_batch over the P broadcast candidates."""
    prob = _problem(topo)
    aux = power.build_aux(prob)
    rng = np.random.default_rng(3)
    X = rng.integers(0, prob.P, size=(prob.R, prob.V)).astype(np.int32)
    st = power.init_state(prob, jnp.asarray(X))
    free = np.asarray(aux.free_pos)
    for (r, v) in free[rng.permutation(len(free))[:6]]:
        got = power.delta_sweep(prob, aux, st, int(r), int(v))
        cand = np.broadcast_to(np.asarray(st.X),
                               (prob.P,) + st.X.shape).copy()
        cand[:, r, v] = np.arange(prob.P)
        want = power.objective_batch(prob, jnp.asarray(cand))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=1e-2)


def test_anneal_delta_matches_full_backend(topo):
    """Identical proposal stream -> the incremental and the legacy
    full-objective backends accept the same moves and land on the same
    placement."""
    prob = _problem(topo, n_vsrs=5)
    rng = np.random.default_rng(0)
    X0 = rng.integers(0, prob.P, size=(prob.R, prob.V)).astype(np.int32)
    key = jax.random.PRNGKey(3)
    r_delta = solvers.anneal(prob, key, X0, n_chains=16, n_steps=400)
    r_full = solvers.anneal(prob, key, X0, n_chains=16, n_steps=400,
                            backend="full")
    np.testing.assert_array_equal(r_delta.X, r_full.X)


def test_fused_anneal_kernel(topo):
    """The fused Pallas kernel (interpret mode on CPU) matches the pure-JAX
    incremental backend on the same proposals, and its reported best
    objective is consistent with a full re-evaluation of its best X."""
    prob = _problem(topo, n_vsrs=5)
    aux = power.build_aux(prob)
    rng = np.random.default_rng(1)
    C, S = 6, 250
    X0 = jnp.asarray(rng.integers(0, prob.P, size=(C, prob.R, prob.V)),
                     jnp.int32)
    Xc = jax.vmap(lambda x: power.apply_pins(prob, x))(X0)
    key = jax.random.PRNGKey(7)
    fi, p_prop, u_prop = solvers._anneal_proposals(key, aux, S, C, prob.P)
    j_prop = aux.free_flat[fi]
    temps = jnp.asarray(50.0 * (0.05 / 50.0) ** (np.arange(S) / (S - 1)),
                        jnp.float32)
    bX, stats = ops.fused_anneal(prob, aux, Xc, j_prop.T, p_prop.T,
                                 u_prop.T, temps)
    # self-consistency: reported best == exact objective of best X
    exact = np.array([float(power.objective(prob, bX[c])) for c in range(C)])
    np.testing.assert_allclose(np.asarray(stats[:, 0]), exact,
                               rtol=1e-5, atol=5e-2)
    # agreement with the pure-JAX incremental scan
    bX2, bobj2, _ = solvers._anneal_scan_delta(prob, aux, Xc, j_prop,
                                               p_prop, u_prop, temps)
    assert abs(float(stats[:, 0].min()) - float(bobj2)) <= 5e-2


def test_fused_anneal_chain_padding(topo):
    """Chain counts that don't divide the block size are padded and the
    padding is dropped."""
    prob = _problem(topo, n_vsrs=3)
    aux = power.build_aux(prob)
    rng = np.random.default_rng(2)
    C, S = 5, 60
    Xc = jax.vmap(lambda x: power.apply_pins(prob, x))(
        jnp.asarray(rng.integers(0, prob.P, size=(C, prob.R, prob.V)),
                    jnp.int32))
    key = jax.random.PRNGKey(11)
    fi, p_prop, u_prop = solvers._anneal_proposals(key, aux, S, C, prob.P)
    temps = jnp.full((S,), 1.0, jnp.float32)
    bX, stats = ops.fused_anneal(prob, aux, Xc, aux.free_flat[fi].T,
                                 p_prop.T, u_prop.T, temps)
    assert bX.shape == (C, prob.R, prob.V)
    assert stats.shape == (C, 2)
    exact = np.array([float(power.objective(prob, bX[c])) for c in range(C)])
    np.testing.assert_allclose(np.asarray(stats[:, 0]), exact,
                               rtol=1e-5, atol=5e-2)


def test_route_incidence_matches_dense_paths(topo):
    """The kernels' [P*P, N] route table is the dense path incidence of
    the CSR routes."""
    prob = _problem(topo)
    got = pp.route_incidence(prob.route_idx, prob.N)
    want = topo.dense_path_nodes().reshape(prob.P * prob.P, prob.N)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_auto_backend_size_rule():
    """``anneal(backend="auto")`` picks the fused kernel on a TPU exactly
    where ``fused_fits`` admits the problem, and the delta scan
    everywhere else."""
    pick = solvers.auto_backend
    assert pick("tpu", 23, 13, 30, 2, 4000) == "fused"
    assert pick("tpu", 252, 86, 3072, 2, 4000) == "fused"
    assert pick("tpu", 256, 128, 3072, 2, 12000) == "fused"
    assert pick("tpu", 256, 128, 8192, 8, pp.FUSED_MAX_T) == "fused"
    assert pick("tpu", 256, 128, 4096, 16, pp.FUSED_MAX_T) == "fused"
    assert pick("tpu", 257, 128, 3072, 2, 4000) == "delta"
    assert pick("tpu", 468, 126, 3072, 2, 4000) == "delta"
    assert pick("tpu", 23, 13, 2 * pp.FUSED_MAX_J, 1, 4000) == "delta"
    assert pick("tpu", 23, 13, 30, 2 * pp.FUSED_MAX_D, 4000) == "delta"
    assert pick("tpu", 23, 13, 8192, 16, 4000) == "delta"     # J*D
    assert pick("tpu", 23, 13, 30, 2, pp.FUSED_MAX_T + 1) == "delta"
    assert pick("cpu", 23, 13, 30, 2, 4000) == "delta"


def test_fused_backend_refuses_oversized_substrate():
    """An explicit ``backend="fused"`` past the fit rule is a clear
    ValueError before anything is lowered, not a compiler failure."""
    big = topology.city_scale(n_olt=16, onus_per_olt=4, iot_per_onu=7)
    prob = power.build_problem(big, vsr.random_vsrs(2, rng=0,
                                                    source_nodes=[0]))
    X0 = np.zeros((prob.R, prob.V), np.int32)
    with pytest.raises(ValueError, match="fused_fits"):
        solvers.anneal(prob, jax.random.PRNGKey(0), X0, n_chains=2,
                       n_steps=8, backend="fused")


def test_anneal_only_moves_free_positions(topo):
    """Pinned input VMs are never proposed: every chain keeps them at the
    source node throughout (checked via the returned placement)."""
    prob = _problem(topo, n_vsrs=4)
    rng = np.random.default_rng(0)
    X0 = rng.integers(0, prob.P, size=(prob.R, prob.V)).astype(np.int32)
    res = solvers.anneal(prob, jax.random.PRNGKey(0), X0, n_chains=8,
                         n_steps=200)
    fixed_mask = np.asarray(prob.fixed_mask)
    fixed_node = np.asarray(prob.fixed_node)
    np.testing.assert_array_equal(res.X[fixed_mask], fixed_node[fixed_mask])


def test_dense_route_cache_gate(topo):
    """The guarded [P*P, N] route-row cache exists exactly on small
    substrates (P <= power.DENSE_ROUTE_MAX_P) and never above the gate."""
    prob = _problem(topo)
    assert prob.P <= power.DENSE_ROUTE_MAX_P
    assert prob.route_dense is not None
    assert prob.route_dense.shape == (prob.P * prob.P, prob.N)
    big = topology.city_scale(n_olt=4, onus_per_olt=4, iot_per_onu=4)
    assert big.P > power.DENSE_ROUTE_MAX_P
    vs = vsr.random_vsrs(2, rng=0, source_nodes=[0])
    assert power.build_problem(big, vs).route_dense is None


def test_dense_route_cache_delta_parity(topo):
    """With the dense cache on (paper scale), delta_move / apply_move match
    BOTH the cache-off CSR path and the float64 oracle along a random move
    sequence -- the cache is a pure gather-level substitution."""
    import dataclasses
    prob = _problem(topo, vm_gflops=(0.5, 2.0))
    prob_nc = dataclasses.replace(prob, route_dense=None)
    aux = power.build_aux(prob)
    rng = np.random.default_rng(7)
    X0 = solvers.fixed_layer(prob, topo, "iot").X
    st = power.init_state(prob, X0)
    st_nc = power.init_state(prob_nc, X0)
    for r, v, p_new in _random_moves(prob, aux, rng, 60):
        got = float(power.delta_move(prob, aux, st, r, v, p_new))
        got_nc = float(power.delta_move(prob_nc, aux, st_nc, r, v, p_new))
        want = ref.placement_delta_ref(prob, np.asarray(st.X), r, v, p_new)
        assert abs(got - want) <= 1e-3, (r, v, p_new, got, want)
        assert abs(got - got_nc) <= 1e-3, (r, v, p_new, got, got_nc)
        st = power.apply_move(prob, aux, st, r, v, p_new)
        st_nc = power.apply_move(prob_nc, aux, st_nc, r, v, p_new)
        np.testing.assert_allclose(np.asarray(st.lam),
                                   np.asarray(st_nc.lam),
                                   rtol=1e-5, atol=1e-2)
    # full evaluation through _lam_from_links agrees across the gate too
    obj = float(power.objective(prob, st.X))
    obj_nc = float(power.objective(prob_nc, st.X))
    want = kref_obj = ref.placement_objective_f64(prob, np.asarray(st.X))
    assert abs(obj - obj_nc) <= 1e-3 + 1e-6 * abs(obj_nc)
    assert abs(obj - kref_obj) <= 1e-2 + 1e-5 * abs(want)


def test_coordinate_on_delta_engine_still_descends(topo):
    prob = _problem(topo, n_vsrs=6, seed=5)
    cdc = topo.layer_indices("cdc")[0]
    X0 = np.full((prob.R, prob.V), cdc, dtype=np.int32)
    res = solvers.coordinate(prob, X0)
    hist = res.history
    assert all(hist[i + 1] <= hist[i] + 1e-6 for i in range(len(hist) - 1))
    # the returned incumbent matches its reported objective
    assert abs(res.objective - hist[-1]) <= 1e-3 + 1e-6 * abs(hist[-1])


def _eager_anneal_streams(prob, aux, key, X0, n_chains, n_steps, t0, t1,
                          el=None, cnt=None, cand=None):
    """``solvers.anneal``'s chains' start, ``_anneal_proposals`` stream
    and temperatures as they were drawn op by op before the anneal became
    one device program: ``(Xc, j_prop, p_prop, u_prop, temps)``."""
    R, V, P = prob.R, prob.V, prob.P
    k_init, k_prop = jax.random.split(key)
    X = power.apply_pins(prob, jnp.asarray(X0, jnp.int32))
    if el is not None:
        Xp, _ = solvers._project_eligible(prob, X, el)
        X = power.apply_pins(prob, Xp)
    Xc = jnp.broadcast_to(X, (n_chains, R, V)).copy()
    if el is None:
        rand = jax.random.randint(k_init, (n_chains, R, V), 0, P, jnp.int32)
    else:
        u_r = jax.random.uniform(jax.random.fold_in(k_init, 1),
                                 (n_chains, R, V))
        rand = solvers._sample_eligible(u_r, jnp.arange(R)[None, :, None],
                                        jnp.asarray(cnt), jnp.asarray(cand))
    keep = (jnp.arange(n_chains) == 0)[:, None, None]
    Xc = jax.vmap(lambda x: power.apply_pins(prob, x))(
        jnp.where(keep, Xc, rand))
    temps = t0 * (t1 / t0) ** (jnp.arange(n_steps) / max(1, n_steps - 1))
    fi, p_prop, u_prop = solvers._anneal_proposals(
        k_prop, aux, n_steps, n_chains, P, V=V, cnt=cnt, cand=cand)
    return Xc, aux.free_flat[fi], p_prop, u_prop, temps


def _eager_anneal(prob, key, X0, n_chains, n_steps, t0, t1, backend,
                  eligible=None):
    """``solvers.anneal`` as it ran op by op: the eager streams, the
    backend's chains, the best chain's pick and ``_result``."""
    aux = power.build_aux(prob)
    el, cnt, cand = solvers._eligible_np(eligible)
    Xc, j_prop, p_prop, u_prop, temps = _eager_anneal_streams(
        prob, aux, key, X0, n_chains, n_steps, t0, t1, el, cnt, cand)
    if backend == "fused":
        bXc, stats = ops.fused_anneal(
            prob, aux, Xc, j_prop.T, p_prop.T, u_prop.T, temps,
            eligible=None if el is None else jnp.asarray(el))
        k = int(jnp.argmin(stats[:, 0]))
        return solvers._result(prob, np.asarray(bXc[k]), "anneal(fused)",
                               [float(stats[k, 0])])
    if backend == "full":
        bX, _, hist = solvers._anneal_scan_full(prob, Xc, j_prop, p_prop,
                                                u_prop, temps)
    else:
        bX, _, hist = solvers._anneal_scan_delta(prob, aux, Xc, j_prop,
                                                 p_prop, u_prop, temps)
    tag = "anneal" if backend == "delta" else f"anneal({backend})"
    return solvers._result(prob, np.asarray(bX), tag,
                           [float(h) for h in
                            np.asarray(hist[0][:: max(1, n_steps // 50)])])


@pytest.mark.parametrize("masked", [False, True])
def test_anneal_program_streams_match_eager(topo, masked):
    """For a fixed key, the one-program anneal draws the chains' start,
    the proposal stream and the temperatures the eager ops drew, byte for
    byte, with and without an eligibility mask."""
    prob = _problem(topo, n_vsrs=5, seed=4)
    aux = power.build_aux(prob)
    X0 = solvers.fixed_layer(prob, topo, "iot").X
    el = np.ones((prob.R, prob.P), bool)
    el[:, 1::4] = False
    el, cnt, cand = solvers._eligible_np(el if masked else None)
    key = jax.random.PRNGKey(17)
    S, C, t0, t1 = 90, 5, 9.0, 0.02
    want = _eager_anneal_streams(prob, aux, key, X0, C, S, t0, t1, el, cnt,
                                 cand)
    X = np.asarray(X0, np.int32)
    if masked:
        X, _ = solvers._project_eligible(
            prob, power.apply_pins(prob, jnp.asarray(X)), el)
    got = jax.jit(solvers._anneal_inputs, static_argnums=(6, 7))(
        prob, aux, key, X, t0, t1 / t0, S, C, cnt, cand)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("backend", ["delta", "full", "fused"])
def test_anneal_program_matches_eager_anneal(topo, backend):
    """For a fixed key, ``solvers.anneal`` -- one device program, the
    fused kernel its own launch between two -- commits the placement the
    eager ops did, bit for bit, with the same breakdown, method and
    history; with an eligibility mask the placement and breakdown too."""
    prob = _problem(topo, n_vsrs=5, seed=4)
    X0 = solvers.fixed_layer(prob, topo, "iot").X
    key = jax.random.PRNGKey(13)
    kw = dict(n_chains=6, n_steps=150, t0=9.0, t1=0.02)
    got = solvers.anneal(prob, key, X0, backend=backend, **kw)
    want = _eager_anneal(prob, key, X0, backend=backend, **kw)
    np.testing.assert_array_equal(got.X, want.X)
    assert got.X.dtype == want.X.dtype
    for a, b in zip(got.breakdown, want.breakdown):
        np.testing.assert_array_equal(a, b)
    assert (got.method, got.history) == (want.method, want.history)

    el = np.ones((prob.R, prob.P), bool)
    el[:, 1::4] = False
    got = solvers.anneal(prob, key, X0, backend=backend, eligible=el, **kw)
    want = _eager_anneal(prob, key, X0, backend=backend, eligible=el, **kw)
    np.testing.assert_array_equal(got.X, want.X)
    assert got.objective == want.objective
