"""PRNG stream-discipline regression tests (the CFN106 fixes).

The masked (eligibility-constrained) branches of ``_anneal_proposals``,
``anneal``, ``genetic`` and ``resolve_incremental`` draw from
``fold_in``-derived streams instead of re-consuming the sibling key.
These tests pin the contract: the unmasked streams are byte-identical
whether or not a mask is in play, proposal and acceptance streams are
statistically independent, and the seeded solvers stay deterministic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import power, solvers, topology, vsr


@pytest.fixture(scope="module")
def topo():
    return topology.paper_topology()


@pytest.fixture(scope="module")
def setup(topo):
    vs = vsr.random_vsrs(4, rng=0, source_nodes=topo.layer_indices("iot")[:2])
    problem = power.build_problem(topo, vs)
    return problem, power.build_aux(problem)


def _mask(problem):
    el = np.ones((problem.R, problem.P), bool)
    el[:, ::3] = False          # knock out every third node
    return el


def test_masked_branch_leaves_sibling_streams_byte_identical(setup):
    """fold_in derivation: adding a mask must not perturb the flat-index
    or acceptance streams (they come from kf/ka, which the masked
    destination branch no longer touches)."""
    problem, aux = setup
    key = jax.random.PRNGKey(11)
    _, cnt, cand = solvers._eligible_np(_mask(problem))
    fi_u, p_u, u_u = solvers._anneal_proposals(key, aux, 64, 4, problem.P)
    fi_m, p_m, u_m = solvers._anneal_proposals(key, aux, 64, 4, problem.P,
                                               V=problem.V, cnt=cnt,
                                               cand=cand)
    np.testing.assert_array_equal(np.asarray(fi_u), np.asarray(fi_m))
    np.testing.assert_array_equal(np.asarray(u_u), np.asarray(u_m))
    # masked destinations all land on eligible nodes
    assert bool(np.asarray(cnt).min()) >= 0
    rows = np.asarray(aux.free_flat)[np.asarray(fi_m)] // problem.V
    el = _mask(problem)
    assert el[rows.ravel(), np.asarray(p_m).ravel()].all()


def test_proposal_and_acceptance_streams_independent(setup):
    """The acceptance uniforms must be statistically independent of the
    destination stream (the paper's Metropolis correctness condition --
    the original double-consumption correlated them)."""
    problem, aux = setup
    key = jax.random.PRNGKey(3)
    _, cnt, cand = solvers._eligible_np(_mask(problem))
    _, p_prop, u = solvers._anneal_proposals(key, aux, 2000, 8, problem.P,
                                             V=problem.V, cnt=cnt, cand=cand)
    a = np.asarray(p_prop, np.float64).ravel()
    b = np.asarray(u, np.float64).ravel()
    r = np.corrcoef(a, b)[0, 1]
    assert abs(r) < 0.03, f"proposal/acceptance correlation {r:.4f}"
    # and the destination stream is NOT the acceptance stream in disguise
    assert not np.array_equal(a % 1.0, b)


def test_anneal_deterministic_and_mask_respected(setup):
    problem, _ = setup
    X0 = solvers.fixed_layer(problem, topology.paper_topology(), "iot").X
    el = _mask(problem)
    key = jax.random.PRNGKey(5)
    r1 = solvers.anneal(problem, key, X0, n_chains=4, n_steps=50,
                        backend="delta", eligible=el)
    r2 = solvers.anneal(problem, key, X0, n_chains=4, n_steps=50,
                        backend="delta", eligible=el)
    np.testing.assert_array_equal(np.asarray(r1.X), np.asarray(r2.X))
    free = ~np.asarray(problem.fixed_mask)
    rows, vms = np.where(free)
    assert el[rows, np.asarray(r1.X)[rows, vms]].all()


def test_genetic_deterministic_for_fixed_seed(setup):
    problem, _ = setup
    X0 = solvers.fixed_layer(problem, topology.paper_topology(), "iot").X
    key = jax.random.PRNGKey(9)
    r1 = solvers.genetic(problem, key, X0, pop=8, gens=3,
                         eligible=_mask(problem))
    r2 = solvers.genetic(problem, key, X0, pop=8, gens=3,
                         eligible=_mask(problem))
    np.testing.assert_array_equal(np.asarray(r1.X), np.asarray(r2.X))


def test_resolve_incremental_and_wave_deterministic(setup):
    problem, _ = setup
    X0 = solvers.fixed_layer(problem, topology.paper_topology(), "iot").X
    key = jax.random.PRNGKey(2)
    kw = dict(changed_rows=[0, 1], anneal_steps=40, anneal_chains=4,
              eligible=_mask(problem))
    r1 = solvers.resolve_incremental(problem, prev_X=X0, key=key, **kw)
    r2 = solvers.resolve_incremental(problem, prev_X=X0, key=key, **kw)
    np.testing.assert_array_equal(np.asarray(r1.X), np.asarray(r2.X))
    st = power.init_state(problem, np.asarray(X0, np.int32))
    w1 = solvers.resolve_wave(problem, st, [0, 1], key=key, anneal_steps=40,
                              anneal_chains=4)
    w2 = solvers.resolve_wave(problem, st, [0, 1], key=key, anneal_steps=40,
                              anneal_chains=4)
    np.testing.assert_array_equal(np.asarray(w1.X), np.asarray(w2.X))


# ---------------------------------------------------------------------------
# the re-solve's anneal as one program: same streams as the eager ops
# ---------------------------------------------------------------------------

def _eager_resolve_anneal(problem, aux, key, target, X, t0, decay,
                          cnt=None, cand=None, *, n_steps, n_chains):
    """``resolve_incremental``'s phase 2 as it ran op by op before it
    became one program, on the unpadded ``target`` list: the best chain
    and trace ``solvers._resolve_anneal`` returns, and the streams it fed
    the scan."""
    target = np.asarray(target)
    R, V, P = problem.R, problem.V, problem.P
    S, C = n_steps, n_chains
    flat = jnp.asarray((target[:, 0] * V + target[:, 1]).astype(np.int32))
    kf, kp, ka, kx = jax.random.split(key, 4)
    fi = jax.random.randint(kf, (S, C), 0, flat.shape[0])
    j_prop = flat[fi]
    if cnt is None:
        p_prop = jax.random.randint(kp, (S, C), 0, P, jnp.int32)
    else:
        u_dst = jax.random.uniform(jax.random.fold_in(kp, 1), (S, C))
        p_prop = solvers._sample_eligible(u_dst, j_prop // V,
                                          jnp.asarray(cnt),
                                          jnp.asarray(cand))
    u_prop = jax.random.uniform(ka, (S, C))
    temps = t0 * decay ** (jnp.arange(S) / max(1, S - 1))
    Xc = jnp.broadcast_to(X, (C,) + X.shape)
    if cnt is None:
        rand = jax.random.randint(kx, Xc.shape, 0, P, jnp.int32)
    else:
        u_r = jax.random.uniform(jax.random.fold_in(kx, 1), Xc.shape)
        rand = solvers._sample_eligible(u_r, jnp.arange(R)[None, :, None],
                                        jnp.asarray(cnt), jnp.asarray(cand))
    tgt = np.zeros((R, V), dtype=bool)
    tgt[target[:, 0], target[:, 1]] = True
    keep = (jnp.arange(C) == 0)[:, None, None] | ~jnp.asarray(tgt)[None]
    streams = (jnp.where(keep, Xc, rand), j_prop, p_prop, u_prop, temps)
    bX, _, hist = solvers._anneal_scan_delta(problem, aux, *streams)
    return (bX, hist), streams


@pytest.mark.parametrize("masked", [False, True])
def test_resolve_anneal_program_matches_eager_streams(setup, masked):
    """For a fixed key, the one-program phase 2 draws the same chain
    starts, targets, destinations, acceptance uniforms and temperatures
    as the eager ops did, byte for byte, and its chains end on the same
    placement with the same acceptances.  The best-objective trace may
    differ in float32 rounding: the program's start loads can round
    differently in the last place."""
    problem, aux = setup
    X = power.init_state(problem, np.asarray(solvers.fixed_layer(
        problem, topology.paper_topology(), "iot").X, np.int32)).X
    _, cnt, cand = solvers._eligible_np(_mask(problem) if masked else None)
    # a padded target list: row 1's free VMs, the first one repeated
    free = np.asarray(aux.free_pos)
    target = solvers._pad_positions(free[free[:, 0] == 1], 4)
    key = jax.random.PRNGKey(21)
    rest = (X, 5.0, 0.05 / 5.0)
    kw = dict(n_steps=70, n_chains=4)
    (bX_e, hist_e), streams_e = _eager_resolve_anneal(
        problem, aux, key, target, *rest, cnt, cand, **kw)
    # the program's target list: padded past the bucket, its length traced
    args = (key, solvers._pad_positions(target, 16), len(target)) + rest
    streams = jax.jit(solvers._resolve_anneal_inputs,
                      static_argnums=(6, 7, 8))(*args, 70, 4, problem.P,
                                                cnt, cand)
    for got, want in zip(streams, streams_e):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    bX, hist = solvers._resolve_anneal(problem, aux, *args, cnt, cand, **kw)
    np.testing.assert_array_equal(np.asarray(bX), np.asarray(bX_e))
    np.testing.assert_array_equal(np.asarray(hist[1]), np.asarray(hist_e[1]))
    np.testing.assert_allclose(np.asarray(hist[0]), np.asarray(hist_e[0]),
                               rtol=1e-3)


@pytest.mark.parametrize("masked", [False, True])
def test_resolve_wave_placements_match_eager_anneal(topo, masked,
                                                    monkeypatch):
    """``resolve_wave`` on a fixed wave commits the same placement, bit
    for bit, with the one-program anneal as with the eager ops, and the
    same exact-objective history (12 services: enough that the anneal's
    candidate depends on its streams)."""
    problem = power.build_problem(topo, vsr.random_vsrs(
        12, rng=0, source_nodes=topo.layer_indices("iot")[:2]))
    X0 = np.asarray(solvers.fixed_layer(problem, topology.paper_topology(),
                                        "iot").X, np.int32)
    st = power.init_state(problem, X0)
    kw = dict(key=jax.random.PRNGKey(8), anneal_steps=60, anneal_chains=4,
              eligible=_mask(problem) if masked else None)
    one = solvers.resolve_wave(problem, st, [1, 2], **kw)
    def eager(problem, aux, key, target, n_target, *rest, **kw):
        return _eager_resolve_anneal(problem, aux, key,
                                     np.asarray(target)[:n_target], *rest,
                                     **kw)[0]
    monkeypatch.setattr(solvers, "_resolve_anneal", eager)
    eager = solvers.resolve_wave(problem, st, [1, 2], **kw)
    np.testing.assert_array_equal(one.X, eager.X)
    assert one.history == eager.history
    assert one.objective == eager.objective
