#!/usr/bin/env python3
"""Chip smoke: the placement service's main path, once, on one TPU chip.

    python3 chip_smoke.py

A smoke test, not a benchmark.  It drives the normal entry points
(``repro.api.CFNSession`` and the ``OnlineEmbedder`` engine behind it) on
the chip and checks every committed placement against the float64 oracle
(``repro.kernels.ref.placement_objective_f64``).  The wall times it prints
include compilation and one-off work and say nothing about speed.

Phases, all in this one process:

  device  platform, device kind and count; fails unless JAX sees a TPU
          (there is no CPU fallback).
  paper   the quickstart solve on ``paper_topology()`` with 10 VSRs at the
          default effort and backend, where ``auto`` picks the fused Pallas
          anneal kernel compiled by Mosaic; the same solve on the delta
          backend with the same key; the CDC baseline.  Both solves must be
          feasible, match the oracle, and agree with each other.
  city    ``city_scale(16, 4, 7)`` (P=468) carrying 1,024 live 3-VM
          services: flash-crowd replace waves through ``apply_wave``, a
          rack storm at the busiest hosting nodes through ``apply_fault``,
          one ``defrag_tick`` and one full ``defrag()`` at the default
          effort.  Every commit must match the oracle, every arrival must
          end admitted, rejected or queued, and the waves after the warm-up
          wave must trace nothing new.

Every phase runs even when an earlier one fails.  The last line of stdout
is ``{"ok": true, "device": {...}}`` only when all passed; otherwise the
exit code is 1 and no such line is printed.
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

# committed objective vs the float64 oracle, relative
ORACLE_TOL = 1e-4
# fused vs delta anneal from one key: the same proposal stream, with f32
# deltas summed in a different order, so a near-tie can tip one Metropolis
# decision; the two best placements must still agree within 1% of watts
FUSED_DELTA_TOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (JAX's own
    monitoring events), so each phase can report them apart."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def device_phase() -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    return info


def _oracle_gap(prob, X, objective: float) -> tuple:
    from repro.kernels import ref
    oracle = float(ref.placement_objective_f64(prob, X))
    return oracle, abs(oracle - objective) / abs(oracle)


def paper_phase(backend: str = "auto") -> None:
    """``backend`` is the spec's default ``auto`` on the chip; a CPU
    rehearsal passes ``fused`` to run the same kernel in interpret mode."""
    from repro.api import CFNSession, PlacementSpec
    from repro.core import topology, vsr

    topo = topology.paper_topology()
    vsrs = vsr.random_vsrs(10, rng=0, source_nodes=[0])
    spec = PlacementSpec(method="cfn-milp", bucket_rows=False,
                         bucket_cols=False)
    oracles = {}
    for be in (backend, "delta"):
        s = CFNSession(topo, spec.replace(backend=be),
                       key=jax.random.PRNGKey(0))
        res = s.solve(vsrs)
        oracle, gap = _oracle_gap(s.problem, res.X, res.objective)
        print(f"paper backend={be}: method={res.method} "
              f"power={res.power:.4f} W feasible={res.feasible} "
              f"oracle={oracle:.6f} gap={gap:.2e}", flush=True)
        check(res.feasible, f"paper {be} solve infeasible")
        check(gap <= ORACLE_TOL, f"paper {be} oracle gap {gap:.2e}")
        want = "anneal(fused)" if be != "delta" else "(anneal)"
        check(want in res.method,
              f"paper {be}: method {res.method!r} lacks {want!r}")
        oracles[be] = (oracle, res.power)
    (o_f, w_f), (o_d, _) = oracles[backend], oracles["delta"]
    fd = abs(o_f - o_d) / abs(o_d)
    print(f"paper fused vs delta: relative gap {fd:.2e} "
          f"(limit {FUSED_DELTA_TOL:.0e})", flush=True)
    check(fd <= FUSED_DELTA_TOL, f"fused vs delta gap {fd:.2e}")
    cdc = CFNSession(topo, spec.replace(method="cdc")).solve(vsrs)
    saving = 1.0 - w_f / cdc.power
    print(f"paper saving vs cdc: {saving:.4f} ({cdc.power:.4f} W)",
          flush=True)
    check(saving > 0, f"no saving against cdc ({saving:.4f})")


def city_phase(n_olt: int = 16, onus_per_olt: int = 4, iot_per_onu: int = 7,
               n_live: int = 1024, wave_size: int = 64,
               n_waves: int = 3) -> None:
    from repro.api import CFNSession, PlacementSpec
    from repro.core import dynamic, power, solvers, topology, vsr

    topo = topology.city_scale(n_olt=n_olt, onus_per_olt=onus_per_olt,
                               iot_per_onu=iot_per_onu)
    iot = topo.layer_indices("iot")
    mk = lambda sid: vsr.random_vsrs(
        1, rng=np.random.default_rng(sid), n_vms=3,
        source_nodes=iot[:max(8, len(iot) // 4)])
    services = [mk(sid) for sid in range(n_live)]
    # load-balanced greedy start over the serving tiers (as the churn
    # benchmark bootstraps its steady state)
    hosts = [p for layer in ("mf", "af", "cdc")
             for p in topo.layer_indices(layer)]
    load = {p: 0.0 for p in hosts}
    X0 = np.zeros((n_live, 3), np.int32)
    for r, sv in enumerate(services):
        for v in range(3):
            p = min(hosts, key=load.get)
            X0[r, v] = p
            load[p] += float(sv.F[0, v])

    s = CFNSession(topo, PlacementSpec(anneal_steps=0, defrag_every=0,
                                       polish_sweeps=1,
                                       defrag_rows_per_tick=8),
                   key=jax.random.PRNGKey(0))
    worst = [0.0, ""]

    def commit(label: str) -> None:
        if s.result is None:
            return
        vs = s.engine.vsr_batch()
        prob = power.build_problem(topo, vs)
        if s.health is not None and not s.health.all_up:
            prob = s.health.degrade(prob)
        _, gap = _oracle_gap(prob, s.X[:vs.R, :vs.V], s.objective())
        if gap >= worst[0]:
            worst[:] = [gap, label]
        check(gap <= ORACLE_TOL, f"city {label}: oracle gap {gap:.2e}")

    s.engine.bootstrap(services, X0=X0)
    commit("bootstrap")
    print(f"city P={topo.P} N={topo.N} K={topo.K} live={s.n_live}",
          flush=True)

    events = dynamic.flash_crowd_trace(n_live, n_waves, wave_size, rng=0,
                                       replace=True)
    before = None
    for i, group in enumerate(list(dynamic.iter_waves(events))[1:]):
        if i == 1:       # wave 0 warms the wave path's compile buckets
            before = dict(solvers.TRACE_COUNTS)
        deps = [ev.sid for ev in group if ev.kind == "depart"]
        arrs = [(mk(ev.sid), ev.sid) for ev in group if ev.kind == "arrive"]
        wr = s.apply_wave(arrs, deps)
        verdicts = wr.admitted + wr.rejected + wr.queued
        check(sorted(verdicts) == sorted(wr.sids),
              f"wave {i}: arrivals without one verdict each")
        commit(f"wave {i}")
        print(f"city wave {i}: {len(deps)} departed, "
              f"{len(wr.admitted)} admitted, {len(wr.rejected)} rejected, "
              f"{len(wr.queued)} queued", flush=True)
    fresh = {k: v - before.get(k, 0) for k, v in solvers.TRACE_COUNTS.items()
             if v != before.get(k, 0)}
    print(f"city fresh traces after the warm-up wave: {fresh or 0}",
          flush=True)
    check(not fresh, f"waves after warm-up traced again: {fresh}")

    # the storm hits the busiest hosting nodes plus one pinned source, so
    # it both re-embeds and strands
    srcs = {int(sv.src[0]) for sv in services}
    used = np.bincount(s.X[:s.n_live, :3].reshape(-1), minlength=topo.P)
    hot = [int(p) for p in np.argsort(-used, kind="stable")
           if int(p) not in srcs and used[p] > 0]
    src0 = int(s.engine.vsr_batch().src[0])
    storm = dynamic.fault_preset("rack_storm", topo, nodes=hot[:3] + [src0])
    for ev in storm:
        s.tick(ev.t)
        s.apply_fault(ev)
        commit(f"{ev.kind}({ev.target})")
    print(f"city rack storm: {len(storm)} events on nodes "
          f"{hot[:3] + [src0]}; live={s.n_live} queued="
          f"{len(s.engine.queued_sids)}", flush=True)

    s.defrag_tick()
    commit("defrag_tick")
    prob = s.problem
    pick = solvers.auto_backend(jax.default_backend(), prob.P, prob.N,
                                prob.R * prob.V,
                                power.build_aux(prob).inc_h.shape[1], 4000)
    res = s.defrag()
    commit("defrag")
    print(f"city defrag: auto anneal backend at P={prob.P} is {pick}; "
          f"method={res.method}", flush=True)
    print(f"city worst oracle gap {worst[0]:.2e} at {worst[1]}", flush=True)


def main() -> int:
    info = device_phase()
    if info["platform"] != "tpu":
        print(f"FAIL device: JAX found no TPU (platform "
              f"{info['platform']!r}); this smoke has no CPU fallback",
              flush=True)
        return 1
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    clock = CompileClock()
    failed = []
    for name, phase in (("paper", paper_phase), ("city", city_phase)):
        t0, c0 = time.perf_counter(), clock.seconds
        try:
            phase()
            status = "ok"
        except Exception:            # report the phase, run the next one
            traceback.print_exc()
            status = "FAILED"
            failed.append(name)
        print(f"phase {name}: {status}; smoke wall {time.perf_counter() - t0:.1f}"
              f" s, of which compile {clock.seconds - c0:.1f} s "
              "(a smoke, not a benchmark)", flush=True)
    if failed:
        print(f"FAIL phases: {', '.join(failed)}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
