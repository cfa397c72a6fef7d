"""Federated fog regions walkthrough: 3 regions x 50 VSRs, cross-region
migration on a regional power-budget breach.

    PYTHONPATH=src python examples/federated_regions.py [--quick]

The paper's CFN is one metro tree; this example runs the multi-region
federation (``topology.federated_scale``): three city-style fog regions --
each its own PON access fabric, metro fog, and regional CDC -- stitched
over a shared IP/WDM core.  The ``FederatedSession``:

  * assigns every service to its HOME region (the region owning its
    source IoT device) and solves all three regional portfolios under ONE
    vmapped compile (``federation.solve_portfolio_batched``) -- the scaling
    move past the single-substrate ceiling: G small problems instead of
    one ever-bigger flat one;

  * accounts power EXACTLY per region (float64 per-node Eq. 1/2): the
    sum of regional + inter-region watts equals a from-scratch oracle
    evaluation of the merged placement;

  * enforces per-region power budgets: when churn pushes a region past
    its ``region_power_budget_w``, the coordinator migrates the arrival
    to the coolest admissible region -- its pinned input VM stays home,
    the cut virtual links are priced along the merged route (home egress
    + shared core + host ingress), which is where inter-region traffic
    enters Eq.(1) network power.  Breaches and migrations are counted on
    a ``fault.monitor.PlacementMonitor``.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from repro.api import FederatedSession, PlacementSpec
from repro.core import federation, topology, vsr
from repro.fault.monitor import PlacementMonitor
from repro.compile_cache import enable_compile_cache


def main(quick: bool = False) -> None:
    t0 = time.time()
    topo = topology.federated_scale(n_regions=3, n_olt=2, onus_per_olt=2,
                                    iot_per_onu=3, n_core=6)
    part = federation.RegionPartition.from_topology(topo)
    print(f"federation: G={part.G} regions x P_r="
          f"{part.regions[0].P} processing nodes (P={topo.P} merged), "
          f"{len(part.core_net_ids)}-node shared core "
          f"[built in {time.time() - t0:.1f}s]")
    print(f"inter-region core hops:\n{part.core_hops}")

    # workload: services sourced from IoT devices across all three regions
    n_vsrs = 12 if quick else 50
    rng = np.random.default_rng(0)
    sources = []
    for reg in part.regions:
        iot_local = reg.topo.layer_indices("iot")
        picks = rng.choice(iot_local, size=min(4, len(iot_local)),
                           replace=False)
        sources += [int(reg.proc_ids[i]) for i in picks]
    vs = vsr.random_vsrs(n_vsrs, rng=0, source_nodes=sources)

    monitor = PlacementMonitor()
    spec = PlacementSpec(effort="quick", anneal_steps=150)
    sess = FederatedSession(topo, spec, monitor=monitor)

    t0 = time.time()
    res = sess.solve(vs)
    bd = res.breakdown
    print(f"\nbatch solve: {n_vsrs} services in {time.time() - t0:.1f}s "
          f"(ONE vmapped compile across {part.G} regional portfolios)")
    per_region = {g: int((res.assignments == g).sum())
                  for g in range(part.G)}
    print(f"assignments: {per_region}  "
          f"(coordinator migrations: {res.migrations})")
    print(f"power: total={bd.total_w:,.1f} W = regional "
          f"{np.round(bd.regional_w, 1)} + inter-region "
          f"{bd.inter_region_w:.1f} W (exact f64 conservation)")

    # churn: cap region 0 just above its current draw, then hammer it with
    # arrivals until the budget breaks and the coordinator migrates
    budgets = np.full(part.G, 1e9)
    budgets[0] = float(bd.regional_w[0]) + 25.0
    sess.spec = sess.spec.replace(region_power_budget_w=budgets)
    print(f"\nchurn: adding services sourced in region 0 "
          f"(budget {budgets[0]:.0f} W on region 0) ...")
    src0 = sources[0]
    n_extra = 3 if quick else 8
    for k in range(n_extra):
        svc = vsr.random_vsrs(1, rng=1000 + k, source_nodes=[src0])
        r = sess.add(svc)
        sid = sess.sids[-1]
        host = sess.assignment(sid)
        w = sess.region_watts()
        tag = "HOME" if host == 0 else f"MIGRATED -> region {host}"
        print(f"  arrival {sid}: {tag:22s} regional W="
              f"{np.round(w, 0)}  admitted={r is not None}")
    print(f"\nmonitor: {monitor.snapshot()}")
    bd = sess.breakdown()
    print(f"final: total={bd.total_w:,.1f} W, inter-region core "
          f"{bd.inter_region_w:.1f} W over {len(part.core_net_ids)} "
          f"shared IP/WDM nodes")
    heavy = max(sess.sids, key=lambda s: sess._plans[s].migrated)
    plan = sess._plans[heavy]
    if plan.migrated:
        print(f"service {heavy}: input VM pinned at home "
              f"'{topo.proc_names[int(plan.vsr.src[0])]}', body hosted in "
              f"region {plan.assigned}, {len(plan.cuts)} cut links priced "
              "over the core")


if __name__ == "__main__":
    enable_compile_cache()
    main(quick="--quick" in sys.argv[1:])
