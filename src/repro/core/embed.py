"""High-level embedding API: the paper's technique as one call.

    topo = topology.paper_topology()
    vsrs = vsr.random_vsrs(10, rng=0, source_nodes=[0])
    spec = api.PlacementSpec(method="cfn-milp")
    result = api.CFNSession(topo, spec).solve(vsrs)

The canonical path is ``repro.api``: a declarative ``PlacementSpec``
(constraints + solver config) consumed by ``CFNSession`` / ``_embed``.
``embed`` / ``embed_latency_bounded`` remain as deprecated shims that
construct a spec internally; "cfn-milp" is the portfolio stand-in for the
paper's CPLEX run, and "cdc"/"af"/"mf" are the paper's Fig. 3 baselines.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax
import numpy as np

from . import solvers
from .power import PlacementProblem, build_problem
from .topology import CFNTopology
from .vsr import VSRBatch

METHODS = ("cdc", "af", "mf", "iot", "coordinate", "exhaustive", "anneal",
           "genetic", "relax", "cfn-milp")


def _spec(method: str = "cfn-milp", effort: str = "standard",
          max_hops: Optional[int] = None):
    """Build a PlacementSpec (deferred import: api imports this module)."""
    from . import api
    return api.PlacementSpec(method=method, effort=effort, max_hops=max_hops)


def _embed(topo: CFNTopology, vsrs: VSRBatch, spec,
           key: Optional[jax.Array] = None,
           problem: Optional[PlacementProblem] = None,
           telemetry=None) -> solvers.SolveResult:
    """Spec-driven embedding dispatch -- the single batch-path consumer.

    ``spec.masks(problem)`` is built ONCE here and threaded into whichever
    solver ``spec.method`` selects; solvers without native masking (the
    fixed-layer baselines) are forced onto the mask by
    ``solvers.repair_to_eligible`` afterwards, so every method returns an
    eligible placement.  ``telemetry`` (optional) reaches the portfolio's
    phase spans.
    """
    problem = build_problem(topo, vsrs) if problem is None else problem
    key = jax.random.PRNGKey(0) if key is None else key
    eligible = spec.masks(problem)
    m = spec.method
    if m in ("cdc", "af", "mf", "iot"):
        res = solvers.fixed_layer(problem, topo, m)
    elif m == "coordinate":
        cdc = topo.layer_indices("cdc")[0]
        X0 = np.full((problem.R, problem.V), cdc, dtype=np.int32)
        res = solvers.coordinate(problem, X0, eligible=eligible,
                                 telemetry=telemetry)
    elif m == "exhaustive":
        res = solvers.exhaustive(problem, eligible=eligible)
    elif m == "anneal":
        X0 = solvers.fixed_layer(problem, topo, "iot").X
        res = solvers.anneal(problem, key, X0, backend=spec.backend,
                             eligible=eligible)
    elif m == "genetic":
        X0 = solvers.fixed_layer(problem, topo, "iot").X
        # exactly ONE dispatch arm consumes `key` per call; sharing the
        # seed across methods keeps them comparable under a fixed seed
        res = solvers.genetic(problem, key, X0,  # tracelint: allow[CFN106]
                              eligible=eligible)
    elif m == "relax":
        res = solvers.relax(problem, key, eligible=eligible)
    elif m == "cfn-milp":
        res = solvers.solve_portfolio(problem, topo, spec, key,
                                      eligible=eligible, telemetry=telemetry)
    else:
        raise ValueError(f"unknown method {m!r}; choose from {METHODS}")
    if eligible is not None:
        res = solvers.repair_to_eligible(problem, res, eligible)
    return res


def embed(topo: CFNTopology, vsrs: VSRBatch, method: str = "cfn-milp",
          key: Optional[jax.Array] = None, effort: str = "standard",
          problem: Optional[PlacementProblem] = None,
          spec=None) -> solvers.SolveResult:
    """Deprecated shim (kept for the original one-call API): constructs a
    ``PlacementSpec`` from the method/effort kwargs and routes through the
    spec path.  Pass ``spec=`` (or use ``repro.api.CFNSession``) instead."""
    if spec is None:
        warnings.warn(
            "embed(method=..., effort=...) is deprecated; build a "
            "repro.api.PlacementSpec and use repro.api.CFNSession (or pass "
            "spec=)", DeprecationWarning, stacklevel=2)
        spec = _spec(method=method, effort=effort)
    return _embed(topo, vsrs, spec, key=key, problem=problem)


def embed_latency_bounded(topo: CFNTopology, vsrs: VSRBatch,
                          max_hops: int, method: str = "cfn-milp",
                          key: Optional[jax.Array] = None
                          ) -> solvers.SolveResult:
    """Latency-constrained embedding (paper §2: "latency can easily be
    added" to the framework): every VM placed within ``max_hops`` network
    nodes of its VSR's source.

    Deprecated shim preserving the historical semantics (unconstrained
    solve, then masked ``delta_sweep`` repair of each violating VM): the
    hop mask now comes from ``PlacementSpec.masks`` -- the same [R, P]
    surface the native path enforces -- and the repair is
    ``solvers.repair_to_eligible``.  New code should set
    ``PlacementSpec(max_hops=...)`` instead, which threads the mask
    natively through every solver proposal rather than repairing after the
    fact.
    """
    warnings.warn(
        "embed_latency_bounded() is deprecated; set "
        "repro.api.PlacementSpec(max_hops=...) and use repro.api.CFNSession",
        DeprecationWarning, stacklevel=2)
    spec = _spec(method=method, max_hops=max_hops)
    problem = build_problem(topo, vsrs)
    base = _embed(topo, vsrs, spec.replace(max_hops=None), key=key,
                  problem=problem)
    res = solvers.repair_to_eligible(problem, base, spec.masks(problem))
    return solvers._result(problem, res.X,
                           f"latency<={max_hops}({base.method})")


def savings_vs_baseline(topo: CFNTopology, vsrs: VSRBatch,
                        baseline: str = "cdc", method: str = "cfn-milp",
                        key: Optional[jax.Array] = None) -> dict:
    """Paper headline metric: power saving of CFN placement vs the baseline."""
    problem = build_problem(topo, vsrs)
    base = _embed(topo, vsrs, _spec(method=baseline), key=key,
                  problem=problem)
    # paired comparison: baseline and optimized DELIBERATELY share a seed
    opt = _embed(topo, vsrs, _spec(method=method), key=key,  # tracelint: allow[CFN106]
                 problem=problem)
    saving = 1.0 - opt.power / max(base.power, 1e-9)
    return dict(baseline_w=base.power, optimized_w=opt.power,
                saving_frac=saving, baseline=base, optimized=opt)
