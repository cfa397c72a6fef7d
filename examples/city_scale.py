"""City-scale CFN embedding walkthrough: 50 VSRs on the P=252 substrate.

    PYTHONPATH=src python examples/city_scale.py [--quick]

The paper evaluates 1-20 VSRs on a 23-node metro substrate; the ROADMAP
north-star is a city.  This example embeds 50 services on the
``topology.city_scale()`` preset (8 OLT access zones x 6 ONUs x 5 IoT
devices, 8 access-fog + 2 metro-fog nodes, a 6-node IP/WDM core ring with 2
CDCs -- 252 processing nodes, 86 network nodes) and shows why the
padded-CSR route table is what makes this tractable:

  * the route state is ``route_idx [P, P, K=14]`` -- ~3.5 MB -- where the
    dense incidence tensor would be [P, P, N] ~ 22 MB and every
    ``delta_sweep`` used to gather [P, D, N] rows of it;
  * ``solvers.coordinate`` / ``resolve_incremental`` run entirely on
    touched-entries scoring: per destination candidate only the candidate
    node's Eq.(2) terms and the <= D*K route node ids of its Eq.(1) terms
    are re-evaluated.

Sources are spread across the city's IoT devices, so CFN placement pulls
services onto their zone's access fog instead of hauling everything to the
CDC -- the paper's Fig. 3 story at city scale.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from repro.core import embed, power, topology, vsr
from repro.compile_cache import enable_compile_cache


def main(quick: bool = False) -> None:
    t0 = time.time()
    topo = topology.city_scale()
    print(f"substrate: P={topo.P} processing nodes, N={topo.N} network "
          f"nodes, K={topo.K} max hops "
          f"(CSR table {topo.P**2 * topo.K * 4 / 1e6:.1f} MB vs dense "
          f"{topo.P**2 * topo.N * 4 / 1e6:.1f} MB)  "
          f"[built in {time.time() - t0:.1f}s]")

    n_vsrs = 10 if quick else 50
    iot = topo.layer_indices("iot")
    rng = np.random.default_rng(0)
    sources = sorted(int(s) for s in
                     rng.choice(iot, size=min(16, len(iot)), replace=False))
    vs = vsr.random_vsrs(n_vsrs, rng=0, source_nodes=sources)
    problem = power.build_problem(topo, vs)
    print(f"workload: {n_vsrs} VSRs x {vs.V} VMs from {len(sources)} "
          f"source zones")

    t0 = time.time()
    base = embed.embed(topo, vs, "cdc", problem=problem)
    print(f"all-in-CDC baseline: {base.power:,.0f} W "
          f"({time.time() - t0:.1f}s)")

    t0 = time.time()
    res = embed.embed(topo, vs, "coordinate", problem=problem)
    print(f"CFN coordinate descent: {res.power:,.0f} W "
          f"({time.time() - t0:.1f}s, feasible={res.feasible})")
    saving = 1.0 - res.power / max(base.power, 1e-9)
    print(f"power saving vs cloud-only: {saving:.1%} "
          f"(paper band at metro scale: 19-91%)")

    # where did the VMs land?
    layers = np.asarray([topo.proc_layer[p] for p in res.X.reshape(-1)])
    for layer in ("iot", "af", "mf", "cdc"):
        n = int((layers == layer).sum())
        if n:
            print(f"  {layer:>4}: {n:3d} VMs")


if __name__ == "__main__":
    enable_compile_cache()
    main(quick="--quick" in sys.argv[1:])
