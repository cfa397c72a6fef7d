#!/usr/bin/env python3
"""Rate sweep for one configuration, on the chip, in one process.

    python chipbench/sweep.py --config <name> --traffic <mix> \
        --rates 100,150,200 --seconds 10 --seed <n>

Serves the mix at each offered rate (events/s) with no drain after the
window, one short window per rate, and prints one JSON line per rate: the
events committed over those due, the events left unserved at the window's
end, the rate committed, and the latency median and 95th percentile.  The
highest sustained rate is the highest one at which at least 99% of the due
events commit and fewer than one wave cap's events are left unserved.
Compiled programs are shared across the rates, so only the first pays the
compile.  Not run by the benchmark itself: its output sets the rates in
``traffic/*.json``.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    jax = run.init_jax()
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    cfg = json.loads((run.HERE / "configs" / f"{args.config}.json")
                     .read_text())
    base = json.loads((run.HERE / "traffic" / f"{args.traffic}.json")
                      .read_text())
    kind = jax.devices()[0].device_kind
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(base, rate_events_per_s=rate, drain_s=0.0)
        r, checks, counts, _ = run.serve(cfg, traffic, args.seed + k,
                                         args.seconds, None, kind)
        due = 2 * max(1, int(round(rate * args.seconds / 2)))
        lat = r.latencies_ms
        print(json.dumps(dict(
            rate=rate, due=due, committed=r.committed,
            share=r.committed / due, unserved=counts["unserved"],
            events_per_s=r.committed / r.elapsed_s,
            p50_ms=float(np.percentile(lat, 50)) if len(lat) else None,
            p95_ms=float(np.percentile(lat, 95)) if len(lat) else None,
            setup_s=r.setup_s, compiled=r.compiled,
            objective_gap=checks["objective_gap"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
