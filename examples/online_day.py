"""A 24-hour day of service churn on the cloud-fog substrate.

Simulates the `diurnal24` scenario (Poisson arrivals under a raised-cosine
diurnal rate profile, exponential lifetimes -- the regime of Yosuf et al.'s
IoT service-distribution study) against the paper topology, serving every
event through a ``CFNSession``: arrivals and departures are warm-start
incremental re-embeddings (`solvers.resolve_incremental`), with a periodic
full-portfolio defrag -- masked by the same ``PlacementSpec`` as every
other path -- re-packing the substrate.

  PYTHONPATH=src python examples/online_day.py
  PYTHONPATH=src python examples/online_day.py --telemetry day.jsonl

Prints an hourly log of live services, fleet power, per-event re-solve
latency, and the day's totals.  (First-time shapes pay jit compiles; the
steady-state per-event latencies are the numbers to look at, and
BENCH_online.json tracks them rigorously.)  With ``--telemetry PATH``
the day streams spans, compile attribution, and the energy ledger to a
JSONL file and closes with the telemetry report: the day's joules split
into the paper's Eq.(1) networking vs Eq.(2) processing terms, by fog
tier and by tenant.
"""
import sys
import time

import numpy as np

from repro.api import CFNSession, PlacementSpec
from repro.core import dynamic, topology
from repro.telemetry import (Telemetry, load_events, render,
                             summarize_events)
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

SEED = 0
SCENARIO = dynamic.SCENARIOS["diurnal24"]
TEL_PATH = (sys.argv[sys.argv.index("--telemetry") + 1]
            if "--telemetry" in sys.argv else None)

topo = topology.paper_topology()
events = SCENARIO.timeline(rng=SEED)
print(f"scenario {SCENARIO.name}: {len(events)} events over "
      f"{SCENARIO.duration_h:.0f}h "
      f"(rate {SCENARIO.base_rate:.0f}->{SCENARIO.peak_rate:.0f}/h, "
      f"mean lifetime {SCENARIO.mean_lifetime_h:.0f}h)")

# one declarative spec: defrag cadence + (R, V) shape bucketing; add
# max_hops= / power_budget_w= here and every event path enforces them
telemetry = (Telemetry(jsonl_path=TEL_PATH, attribution_every=16)
             if TEL_PATH else None)
session = CFNSession(topo, PlacementSpec(defrag_every=8),
                     telemetry=telemetry)
lat, hour_mark = [], 0.0


def log_event(ev, dt):
    global hour_mark
    lat.append(dt)
    if ev.t >= hour_mark:
        rate = SCENARIO.rate_fn()(ev.t)
        print(f"  t={ev.t:5.1f}h rate={rate:4.1f}/h live={session.n_live:2d} "
              f"power={session.power_w():7.1f}W last={ev.kind:7s} "
              f"({dt * 1e3:6.1f} ms)")
        hour_mark = np.floor(ev.t) + 1.0


t_day = time.time()
live = set()
for ev in events:
    session.tick(ev.t)   # the ledger integrates against this clock
    t0 = time.time()   # per-event solve latency (print I/O excluded)
    if ev.kind == "arrive":
        session.add(SCENARIO.sample_vsr(1000 + ev.sid), sid=ev.sid)
        live.add(ev.sid)
    else:
        if ev.sid not in live:
            continue
        session.remove(ev.sid)
        live.discard(ev.sid)
    log_event(ev, time.time() - t0)

n_events = len(lat)
methods = [s.method for s in session.stats]
n_inc = sum(1 for m in methods if m == "incremental")
print(f"\nday done: {n_events} churn events in {time.time() - t_day:.1f}s "
      f"wall ({n_inc} incremental, {n_events - n_inc} full/defrag)")
print(f"re-solve latency: median={np.median(lat) * 1e3:.1f}ms "
      f"p90={np.percentile(lat, 90) * 1e3:.1f}ms "
      f"(includes first-shape jit compiles)")
if session.n_live:
    per = session.attribute()
    top = sorted(per.items(), key=lambda kv: -kv[1])[:3]
    print(f"end of day: {session.n_live} live services, "
          f"{session.power_w():.1f}W fleet "
          f"(top tenants: "
          + ", ".join(f"svc{sid}={w:.1f}W" for sid, w in top) + ")")

if telemetry is not None:
    telemetry.close()
    print(f"\ntelemetry -> {TEL_PATH}")
    print(render(summarize_events(load_events(TEL_PATH))))
