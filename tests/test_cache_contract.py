"""CFN108 runtime contract: static jit-cache bounds vs measured traces.

``repro.analysis.compute_cache_bounds`` claims a static bound on the
jit-cache key-space of every ``@count_traces`` entry.  These tests replay
real scenarios and cross-check the claim against ``solvers.TRACE_COUNTS``:
for each exercised entry the measured compile count must satisfy

    measured <= bound(scenario) <= 2 * measured

i.e. the static bound is sound (never undercounts) and tight (within 2x
of reality).  Scenario bounds come from ``EntryBound.evaluate`` with the
realized axis cardinalities (the number of shape buckets the trace
actually produced); unexercised call sites are excluded by context.

Shape hygiene: both scenarios use service shapes (``n_vms``) no other
test uses, so the jit cache cannot have been pre-warmed by another test
in the same process and the measured deltas are true compile counts.
"""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.analysis import compute_cache_bounds
from repro.analysis.engine import load_project
from repro.api import FederatedSession, PlacementSpec
from repro.core import federation, power, solvers, topology, vsr

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def bounds():
    project, errs = load_project([str(REPO / "src")])
    assert not errs
    return compute_cache_bounds(project)


def _deltas(before):
    return {k: solvers.TRACE_COUNTS.get(k, 0) - before.get(k, 0)
            for k in set(solvers.TRACE_COUNTS) | set(before)}


def _check(entry, measured, bound):
    assert bound is not None, f"{entry}: scenario bound is unbounded"
    assert measured <= bound, \
        f"{entry}: measured {measured} traces > static bound {bound}"
    assert bound <= 2 * measured, \
        f"{entry}: static bound {bound} not within 2x of measured {measured}"


def test_churn_wave_traces_within_static_bounds(bounds):
    """A two-bucket churn trace through ``resolve_wave``: the realized
    ``sweep`` trace counts sit inside the CFN108 scenario bounds for the
    ``resolve_incremental`` call sites; the anneal program
    (``resolve_anneal``, and ``anneal_delta`` traced inside it) compiles
    once for both wave buckets, as its target list is padded past the
    largest bucket.  Replaying the same waves compiles nothing."""
    topo = topology.paper_topology()
    # n_vms=5 is unique to this test: every compile below is fresh
    vs = vsr.random_vsrs(6, rng=0, n_vms=5,
                         source_nodes=topo.layer_indices("iot")[:3])
    problem = power.build_problem(topo, vs)
    X0 = np.asarray(solvers.fixed_layer(problem, topo, "iot").X, np.int32)
    state = power.init_state(problem, X0)
    key = jax.random.PRNGKey(0)
    kw = dict(anneal_steps=50, anneal_chains=4)

    waves = [[0], [1, 2, 3]]            # two distinct wave-shape buckets
    realized = set()
    for rows in waves:
        fixed = np.asarray(problem.fixed_mask)[rows]
        realized.add(solvers._pow2(int((~fixed).sum())))
    assert len(realized) == 2, "scenario must span two buckets"

    before = dict(solvers.TRACE_COUNTS)
    for rows in waves:
        solvers.resolve_wave(problem, state, rows, key=key, **kw)
    d = _deltas(before)

    cards = {"resolve_incremental.pad_changed_to": len(realized),
             # polish pads to one fixed all-free-VM list per problem shape
             "resolve_incremental.pad_positions_to": 1}
    for entry in ("sweep", "resolve_anneal"):
        bound = bounds[entry].evaluate(sites=["resolve_incremental"],
                                       axis_cards=cards)
        _check(entry, d.get(entry, 0), bound)
    assert d.get("resolve_anneal", 0) == 1
    _check("anneal_delta", d.get("anneal_delta", 0),
           bounds["anneal_delta"].evaluate(sites=["_resolve_anneal"]))

    before = dict(solvers.TRACE_COUNTS)
    for rows in waves:
        solvers.resolve_wave(problem, state, rows, key=key, **kw)
    assert not any(_deltas(before).values()), _deltas(before)


def test_anneal_programs_within_static_bounds(bounds):
    """``solvers.anneal`` on the delta and the fused backend (the kernel
    in interpret mode here): ``anneal_program`` compiles once per backend,
    ``fused_pick`` once, each inside its CFN108 scenario bound for the
    ``anneal`` call site, and a second solve of the same shapes with a new
    key compiles nothing."""
    topo = topology.paper_topology()
    # n_vms=7 is unique to this test: every compile below is fresh
    vs = vsr.random_vsrs(3, rng=1, n_vms=7,
                         source_nodes=topo.layer_indices("iot")[:2])
    problem = power.build_problem(topo, vs)
    X0 = solvers.fixed_layer(problem, topo, "iot").X
    backends = ("delta", "fused")

    def solve_all(key):
        for backend in backends:
            solvers.anneal(problem, key, X0, n_chains=4, n_steps=40,
                           backend=backend)

    before = dict(solvers.TRACE_COUNTS)
    solve_all(jax.random.PRNGKey(0))
    d = _deltas(before)
    assert d.get("anneal_program", 0) == len(backends)
    cards = {"anneal.backend": len(backends)}
    for entry in ("anneal_program", "fused_pick"):
        bound = bounds[entry].evaluate(sites=["anneal"], axis_cards=cards)
        _check(entry, d.get(entry, 0), bound)

    before = dict(solvers.TRACE_COUNTS)
    solve_all(jax.random.PRNGKey(1))
    assert not any(_deltas(before).values()), _deltas(before)


def test_warm_start_program_within_static_bound(bounds):
    """The warm starts' program compiles once per problem shape: a full
    solve compiles it once, a repeated solve with a new key compiles
    nothing, and neither does a ``relax`` (its ``max_sweeps=4`` descent
    runs the same program) nor a ``fixed_layer`` baseline on the same
    problem.  The count sits inside the CFN108 scenario bound for the
    ``_warm_starts`` site (one history bucket)."""
    topo = topology.paper_topology()
    # n_vms=8 is unique to this test: every compile below is fresh
    vs = vsr.random_vsrs(3, rng=4, n_vms=8,
                         source_nodes=topo.layer_indices("iot")[:2])
    problem = power.build_problem(topo, vs)
    spec = PlacementSpec(effort="quick")

    before = dict(solvers.TRACE_COUNTS)
    solvers.solve_portfolio(problem, topo, spec, jax.random.PRNGKey(0))
    d = _deltas(before)
    assert d.get("warm_start_program", 0) == 1
    assert d.get("sweep", 0) == 0
    eb = bounds["warm_start_program"]
    cards = {name: 1 for name, ax in eb.axes().items()
             if ax.kind == "bucket"}               # one history bucket
    _check("warm_start_program", d["warm_start_program"],
           eb.evaluate(sites=["_warm_starts"], axis_cards=cards))

    before = dict(solvers.TRACE_COUNTS)
    solvers.solve_portfolio(problem, topo, spec, jax.random.PRNGKey(1))
    solvers.relax(problem, jax.random.PRNGKey(2), steps=4)
    solvers.fixed_layer(problem, topo, "af")
    assert not any(_deltas(before).values()), _deltas(before)


def test_federated_solve_regions_within_static_bound(bounds):
    """Two same-bucket federated solves compile ``solve_regions`` once;
    the CFN108 scenario bound for the ``solve_portfolio_batched`` site
    (one substrate bucket, one effort tier) agrees within 2x."""
    topo = topology.federated_scale(n_regions=3, n_olt=1, onus_per_olt=2,
                                    iot_per_onu=2, n_core=6)
    part = federation.RegionPartition.from_topology(topo)
    srcs = [int(r.proc_ids[0]) for r in part.regions]
    # n_vms=4 with this R is unique to this test (fresh compiles)
    vs1 = vsr.random_vsrs(6, rng=0, n_vms=4, source_nodes=srcs)
    vs2 = vsr.random_vsrs(6, rng=5, n_vms=4, source_nodes=srcs)
    vs2.src[:] = vs1.src                # same homes -> same shape bucket
    spec = PlacementSpec(effort="quick")

    before = dict(solvers.TRACE_COUNTS)
    FederatedSession(topo, spec).solve(vs1)
    FederatedSession(topo, spec).solve(vs2)
    d = _deltas(before)

    eb = bounds["solve_regions"]
    cards = {name: 1 for name, ax in eb.axes().items()
             if ax.kind in ("bucket", "finite")}   # one bucket, one effort
    bound = eb.evaluate(sites=["solve_portfolio_batched"], axis_cards=cards)
    _check("solve_regions", d.get("solve_regions", 0), bound)


def test_telemetry_attribution_matches_trace_counts(bounds):
    """The telemetry compile-attribution hook records EXACTLY the traces
    ``TRACE_COUNTS`` ticks during a live scenario, and every recorded
    entry stays within its CFN108 static bound (``tel.report(bounds=)``)."""
    from repro.telemetry import Telemetry

    topo = topology.paper_topology()
    # n_vms=6 is unique to this test: compiles below are fresh, so the
    # hook (attached only here) must see every one of them
    vs = vsr.random_vsrs(5, rng=2, n_vms=6,
                         source_nodes=topo.layer_indices("iot")[:3])
    problem = power.build_problem(topo, vs)
    X0 = np.asarray(solvers.fixed_layer(problem, topo, "iot").X, np.int32)
    state = power.init_state(problem, X0)

    tel = Telemetry()
    tel.attach_traces()
    before = dict(solvers.TRACE_COUNTS)
    solvers.resolve_wave(problem, state, [0, 1], key=jax.random.PRNGKey(0),
                         anneal_steps=50, anneal_chains=4)
    measured = {k: v for k, v in _deltas(before).items() if v}
    rep = tel.report(bounds=bounds)
    tel.close()

    assert rep["compiles"]["agree"] is True
    assert rep["compiles"]["recorded"] == measured
    assert measured, "scenario must compile something fresh"
    for entry, chk in rep["compiles"]["bounds"].items():
        assert chk["within"], \
            f"{entry}: recorded compiles exceed CFN108 static bound " \
            f"{chk['static_bound']}"
