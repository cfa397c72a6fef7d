"""The warm starts as one device program: parity with the host loops.

``solvers.coordinate``, ``fixed_layer`` and ``solve_portfolio``'s warm
starts run in ``solvers._warm_start_program``: the sweeps in a
``while_loop``, the first-fit-decreasing start in a ``fori_loop``, one read.
The references below are the loops they replace, kept verbatim: a
``coordinate`` that reads the objective back after every sweep, and a
first fit that accounts capacity on the host in float64.  With fixed
keys, every case must commit the placement, history and breakdown the
host loops did, bit for bit.
"""
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import PlacementSpec
from repro.core import power, solvers, topology, vsr


@pytest.fixture(scope="module")
def topo():
    return topology.paper_topology()


def _ref_fixed_layer(problem, topo, layer, spill_layer="cdc"):
    """The host first fit: pinned loads first, then the free VMs by
    decreasing GFLOPS, each on the least-loaded node of the layer that
    fits, else of the spill layer, else on the layer's first node."""
    nodes = topo.layer_indices(layer)
    spill = topo.layer_indices(spill_layer)
    cap = np.array([topo.proc_hw[p].cap_gflops * topo.proc_hw[p].n_servers
                    for p in range(topo.P)], dtype=np.float64)
    load = np.zeros(topo.P)
    F = np.asarray(problem.F)
    fixed_mask = np.asarray(problem.fixed_mask)
    fixed_node = np.asarray(problem.fixed_node)
    R, V = F.shape
    for r in range(R):
        for v in range(V):
            if fixed_mask[r, v]:
                load[fixed_node[r, v]] += F[r, v]
    X = np.zeros((R, V), dtype=np.int32)
    order = sorted(((r, v) for r in range(R) for v in range(V)
                    if not fixed_mask[r, v]),
                   key=lambda rv: -F[rv])
    for (r, v) in order:
        placed = False
        for p in sorted(nodes, key=lambda p: load[p]):
            if load[p] + F[r, v] <= cap[p] + 1e-9:
                X[r, v] = p
                load[p] += F[r, v]
                placed = True
                break
        if not placed:
            for p in sorted(spill, key=lambda p: load[p]):
                if load[p] + F[r, v] <= cap[p] + 1e-9:
                    X[r, v] = p
                    load[p] += F[r, v]
                    placed = True
                    break
        if not placed:
            X[r, v] = nodes[0]
            load[nodes[0]] += F[r, v]
    return solvers._result(problem, X, f"fixed:{layer}")


def _ref_coordinate(problem, X0, max_sweeps=12, tol=1e-6, eligible=None):
    """The host descent: one ``_sweep`` launch, one exact refresh and one
    read of the objective per sweep."""
    aux = power.build_aux(problem)
    el_np, _, _ = solvers._eligible_np(eligible)
    el_j = None if el_np is None else jnp.asarray(el_np)
    positions = jnp.asarray(np.asarray(aux.free_pos))
    if positions.shape[0] == 0:
        return solvers._result(problem, jnp.asarray(X0, jnp.int32),
                               "coordinate")
    state = power.init_state(problem, jnp.asarray(X0, jnp.int32))
    best_obj = float("inf") if el_np is not None else float(state.obj)
    best_X = state.X
    history: List[float] = []
    for _ in range(max_sweeps):
        state, _ = solvers._sweep(problem, aux, state, positions, el_j)
        state = power.init_state(problem, state.X)
        obj = float(state.obj)
        if obj < best_obj:
            best_obj, best_X = obj, state.X
        history.append(best_obj)
        if len(history) > 1 and history[-2] - obj < tol:
            break
    return solvers._result(problem, best_X, "coordinate", history)


def _ref_portfolio(problem, topo, key, eligible):
    """``solve_portfolio`` at standard effort as it ran on the host loops:
    two descents, the anneal from the better, the best of the three."""
    cdc = topo.layer_indices("cdc")[0]
    base = np.full((problem.R, problem.V), cdc, np.int32)
    cands = [_ref_coordinate(problem, base, eligible=eligible),
             _ref_coordinate(problem, _ref_fixed_layer(problem, topo,
                                                       "iot").X,
                             eligible=eligible)]
    k1, _ = jax.random.split(key)
    warm = min(cands, key=lambda r: r.objective).X
    cands.append(solvers.anneal(problem, k1, warm, n_steps=4000,
                                eligible=eligible))
    return min(cands, key=lambda r: r.objective)


def _same(got, want):
    np.testing.assert_array_equal(got.X, want.X)
    assert got.X.dtype == want.X.dtype
    for a, b in zip(got.breakdown, want.breakdown):
        np.testing.assert_array_equal(a, b)
    assert got.method == want.method
    assert len(got.history) == len(want.history)
    assert got.history == want.history


def _problem(topo, case):
    kw = dict(n_vsrs=6, rng=case.get("seed", 0), source_nodes=[0])
    kw.update(case.get("vsrs", {}))
    return power.build_problem(topo, vsr.random_vsrs(**kw))


def _eligible(problem, case):
    hops = case.get("max_hops")
    return None if hops is None else PlacementSpec(
        max_hops=hops).masks(problem)


CASES = {
    "paper": dict(),
    "masked": dict(max_hops=2, seed=3),
    "all_pinned": dict(vsrs=dict(n_vms=1)),
    "relax_sweeps": dict(max_sweeps=4, seed=5),
    "masked_relax_sweeps": dict(max_sweeps=4, max_hops=2, seed=6),
}


@pytest.mark.parametrize("start", ["cdc", "iot"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_coordinate_matches_host_loop(topo, case, start):
    """``coordinate`` -- one program, one read -- against the host loop,
    from the all-CDC start and from the IoT first fit."""
    c = CASES[case]
    problem = _problem(topo, c)
    if start == "cdc":
        X0 = np.full((problem.R, problem.V), topo.layer_indices("cdc")[0],
                     np.int32)
    else:
        X0 = _ref_fixed_layer(problem, topo, "iot").X
    kw = dict(max_sweeps=c.get("max_sweeps", 12),
              eligible=_eligible(problem, c))
    _same(solvers.coordinate(problem, X0, **kw),
          _ref_coordinate(problem, X0, **kw))


FFD_CASES = {
    # light VMs: the IoT layer holds them all
    "iot_fits": ("iot", dict(n_vsrs=4, vm_gflops=(0.1, 0.5))),
    # the paper's load: the IoT layer overflows, the rest spills to the CDC
    "iot_spills": ("iot", dict(n_vsrs=20)),
    # nothing fits anywhere: every VM lands on the layer's first node
    "nothing_fits": ("iot", dict(n_vsrs=3, vm_gflops=(1e6, 2e6))),
    "af": ("af", dict(n_vsrs=10)),
    "mf": ("mf", dict(n_vsrs=10, seed=2)),
    "cdc": ("cdc", dict(n_vsrs=10)),
    "all_pinned": ("iot", dict(n_vsrs=5, n_vms=1)),
}


@pytest.mark.parametrize("case", sorted(FFD_CASES))
def test_fixed_layer_matches_host_first_fit(topo, case):
    """The traced first fit places every VM where the host loop did."""
    layer, kw = FFD_CASES[case]
    kw = dict(kw)
    problem = power.build_problem(topo, vsr.random_vsrs(
        kw.pop("n_vsrs"), rng=kw.pop("seed", 0), source_nodes=[0], **kw))
    got = solvers.fixed_layer(problem, topo, layer)
    want = _ref_fixed_layer(problem, topo, layer)
    _same(got, want)
    if case == "iot_spills":
        iot = set(topo.layer_indices("iot"))
        free = ~np.asarray(problem.fixed_mask)
        assert any(p not in iot for p in got.X[free]), "no spill"


@pytest.mark.parametrize("masked", [False, True])
def test_solve_portfolio_matches_host_loops(topo, masked):
    """``solve_portfolio`` at standard effort on a fixed key commits what
    the host loops did."""
    problem = _problem(topo, dict(seed=7))
    spec = PlacementSpec(max_hops=2 if masked else None)
    el = spec.masks(problem)
    key = jax.random.PRNGKey(11)
    got = solvers.solve_portfolio(problem, topo, spec, key)
    want = _ref_portfolio(problem, topo, key, el)
    np.testing.assert_array_equal(got.X, want.X)
    for a, b in zip(got.breakdown, want.breakdown):
        np.testing.assert_array_equal(a, b)
    assert got.method == f"cfn-milp({want.method})"
    assert got.history == want.history
