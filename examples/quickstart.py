"""Quickstart: the paper in ~30 lines, on the unified placement API.

Builds the paper's Cloud-Fog Network, declares the optimization once as a
``PlacementSpec``, embeds DNN-inference VSRs through a ``CFNSession``
(the MILP stand-in), and prints the energy comparison against the
CDC / AF / MF baselines (paper Fig. 3/4).

  PYTHONPATH=src python examples/quickstart.py
"""
from repro.api import CFNSession, PlacementSpec
from repro.core import topology, vsr
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

# 1. the paper's substrate: 20 RPi-class IoT devices in 4 Wi-Fi zones,
#    one Access-Fog and one Metro-Fog server, a Xeon CDC behind the core
topo = topology.paper_topology()

# 2. ten DNN inference services; each VSR = input VM (pinned at the IoT
#    source) + compute VMs with U(3,10) GFLOPS demands, chained by Mbps links
vsrs = vsr.random_vsrs(10, rng=0, source_nodes=[0])

# 3. declare the optimization ONCE: method, effort, and (optionally) SLA
#    constraints / admission budgets all live on the spec -- every solver
#    path enforces the same set.  Bucketing off: one static batch solve.
spec = PlacementSpec(method="cfn-milp", bucket_rows=False, bucket_cols=False)

# 4. optimize the placement (portfolio solver = the CPLEX stand-in)
result = CFNSession(topo, spec).solve(vsrs)
print(f"CFN-MILP : {result.power:8.1f} W  "
      f"(feasible={result.feasible}, method={result.method})")

# 5. the paper's fixed-layer baselines: same spec, different method
for pol in ("cdc", "af", "mf"):
    base = CFNSession(topo, spec.replace(method=pol)).solve(vsrs)
    saving = 1 - result.power / base.power
    print(f"{pol.upper():9s}: {base.power:8.1f} W  -> CFN saves {saving:.1%}")

# 6. where did the VMs land?  (paper: the IoT layer, AF/MF bypassed)
layers = [topo.proc_layer[p] for p in result.X.reshape(-1)]
print("placement layers:", sorted(set(layers)))
