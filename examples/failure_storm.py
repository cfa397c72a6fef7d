"""A failure storm against a live city-scale substrate.

Walkthrough of the fault plane: a ``rack_storm`` preset (staggered fog-node
failures with recoveries an hour later) is merged into a churn timeline and
replayed through a ``CFNSession``.  Each failure flows through the closed
loop -- the substrate degrades in place (failed nodes keep their array
slots with zero capacity, so nothing retraces), displaced services are
mass re-embedded via a warm-started incremental re-solve, services whose
pinned source died are parked in the retry queue, and recoveries drain the
queue back onto the healed substrate.  The ``PlacementMonitor`` integrates
stranded-service-seconds into the availability number an operator would
alert on.

  PYTHONPATH=src python examples/failure_storm.py            # full storm
  PYTHONPATH=src python examples/failure_storm.py --quick    # CI-sized
  PYTHONPATH=src python examples/failure_storm.py --telemetry run.jsonl

Prints a per-event log (watts, live/queued counts) and the storm's
availability / re-embed totals.  With ``--telemetry PATH`` the run
streams spans, monitor events, compile attribution, and the energy
ledger to a JSONL file and closes with the telemetry report summary.
"""
import sys
import time

import numpy as np

from repro.api import CFNSession, PlacementSpec
from repro.core import dynamic, topology, vsr
from repro.fault.monitor import PlacementMonitor
from repro.telemetry import (Telemetry, load_events, render,
                             summarize_events)
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

QUICK = "--quick" in sys.argv
SEED = 0
TEL_PATH = (sys.argv[sys.argv.index("--telemetry") + 1]
            if "--telemetry" in sys.argv else None)

topo = (topology.city_scale(n_olt=2, onus_per_olt=2, iot_per_onu=2)
        if QUICK else
        topology.city_scale(n_olt=3, onus_per_olt=3, iot_per_onu=3))
n_services = 6 if QUICK else 12
iot = topo.layer_indices("iot")


def make_vsr(sid):
    return vsr.random_vsrs(1, rng=np.random.default_rng(SEED + sid),
                           n_vms=3, source_nodes=iot[:max(4, len(iot) // 3)])


monitor = PlacementMonitor()
telemetry = (Telemetry(jsonl_path=TEL_PATH, attribution_every=4)
             if TEL_PATH else None)
spec = PlacementSpec(effort="quick", defrag_every=0)
session = CFNSession(topo, spec, monitor=monitor, telemetry=telemetry)

# the steady state: services admitted before the storm hits
arrivals = [dynamic.ServiceEvent(float(i) * 0.5, "arrive", i)
            for i in range(n_services)]

# aim the storm where it hurts: a probe placement finds the busiest
# hosting nodes, and the storm takes those plus one pinned source (that
# service can only wait in the retry queue until recovery)
probe = CFNSession(topo, spec)
for ev in arrivals:
    probe.add(make_vsr(ev.sid), sid=ev.sid)
srcs = {int(make_vsr(i).src[0]) for i in range(n_services)}
cnt = {}
Xp = np.asarray(probe.X)
for r in range(probe.n_live):
    for x in Xp[r, :probe.engine._vsrs[r].V]:
        if int(x) not in srcs:
            cnt[int(x)] = cnt.get(int(x), 0) + 1
hot = sorted(cnt, key=lambda n: -cnt[n])
targets = (hot[:1 if QUICK else 3]
           + [int(make_vsr(0).src[0])])[:2 if QUICK else 4]
storm = dynamic.fault_preset("rack_storm", topo, nodes=targets,
                             t_fail=4.0, stagger_h=0.25, outage_h=1.5)
# one departure mid-storm: churn and faults share a single merged clock
churn = arrivals + [dynamic.ServiceEvent(4.6, "depart", 0)]
events = dynamic.merge_timelines(churn, storm)
horizon = max(e.t for e in events) + 1.0

print(f"substrate: P={topo.P} N={topo.N}; {n_services} services, "
      f"storm of {sum(e.kind == 'fail_node' for e in storm)} node failures")


def log_event(ev, res):
    queued = len(session.engine._queue)
    kind = getattr(ev, "kind", "?")
    target = f" node={ev.target}" if isinstance(ev, dynamic.FaultEvent) else ""
    print(f"  t={ev.t:5.2f}h {kind:13s}{target:9s} "
          f"live={session.n_live:2d} queued={queued} "
          f"power={session.power_w():7.1f}W")


t0 = time.time()
session.replay(events, make_vsr, on_event=log_event)
wall = time.time() - t0

monitor.close_strands(horizon)
snap = monitor.snapshot()
print(f"\nstorm of {snap.get('node_failed', 0)} failures / "
      f"{snap.get('node_recovered', 0)} recoveries in {wall:.1f}s wall:")
print(f"  services stranded   : {snap.get('service_stranded', 0)} "
      f"({monitor.stranded_service_s:.2f} service-hours dark)")
print(f"  re-embeds           : {snap.get('re_embedded', 0)} "
      "(mass re-embeds + queue drains)")
print(f"  availability        : "
      f"{monitor.availability(horizon, n_services):.4f}")
print(f"  final live services : {session.n_live} "
      f"(queue={len(session.engine._queue)}, "
      f"substrate healthy={session.health is None or session.health.all_up})")
assert not session.engine._queue, "recovery must drain the retry queue"

if telemetry is not None:
    telemetry.close()
    print(f"\ntelemetry -> {TEL_PATH}")
    print(render(summarize_events(load_events(TEL_PATH))))
