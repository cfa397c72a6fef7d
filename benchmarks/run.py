"""Benchmark driver: one entry per paper table/figure + framework benches.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig3 gap   # subset

Outputs CSVs under experiments/benchmarks/, machine-readable BENCH_*.json
at the repo root (the single canonical location), and prints name,value
summaries.
"""
from __future__ import annotations

import sys
import time

from repro.compile_cache import enable_compile_cache

from . import kernel_bench, paper_figures, roofline_table


def run_fig3():
    rows = paper_figures.fig3()
    stats = rows[-1]
    print(f"fig3: CFN-vs-CDC savings avg={stats['saving_vs_cdc']:.2%} "
          f"min={stats['saving_min']:.2%} max={stats['saving_max']:.2%} "
          f"(paper: 68% / 19% / 91%)")
    spill = [r for r in rows[:-1] if "cdc" in str(r["layers_used"])]
    print(f"fig3: CDC spill at n_vsrs={[r['n_vsrs'] for r in spill]} "
          "(paper: spike at 20)")
    fog = [r for r in rows[:-1]
           if "af" in str(r["layers_used"]).split("+")
           or "mf" in str(r["layers_used"]).split("+")]
    print(f"fig3: AF/MF selected in {len(fog)}/20 runs (paper: never)")


def run_fig4():
    rows = paper_figures.fig4()
    for r in rows:
        print(f"fig4: {r['policy']:9s} net={r['net_w']:9.1f}W "
              f"proc={r['proc_w']:9.1f}W total={r['total_w']:9.1f}W")


def run_gap():
    rows = paper_figures.solver_gap()
    import statistics
    for m in ("coordinate", "anneal", "genetic", "relax", "cfn-milp"):
        gaps = [r[f"{m}_gap"] for r in rows]
        print(f"gap: {m:11s} mean={statistics.mean(gaps):.4%} "
              f"max={max(gaps):.4%}")


def run_placement():
    rows = kernel_bench.placement_throughput()
    for r in rows:
        print(f"placement-throughput: B={r['batch']:5d} "
              f"batched={r['batched_evals_per_s']}/s "
              f"kernel(interp)={r['kernel_evals_per_s']}/s "
              f"loop={r['loop_evals_per_s']}/s")


def run_solver():
    out = kernel_bench.solver_moves()
    a, c = out["anneal"], out["coordinate_sweep"]
    print(f"solver-moves: anneal full={a['full_moves_per_s']:,.0f}/s "
          f"delta={a['delta_moves_per_s']:,.0f}/s "
          f"fused={a['fused_moves_per_s']:,.0f}/s "
          f"(delta {a['speedup_delta_vs_full']}x)")
    print(f"solver-moves: sweep legacy={c['legacy_scores_per_s']:,.0f}/s "
          f"delta={c['delta_scores_per_s']:,.0f}/s "
          f"({c['speedup_delta_vs_full']}x) -> BENCH_solver.json")


def run_sparse():
    out = kernel_bench.sparse_routes()
    for r in out["sweeps"]:
        print(f"sparse-routes: {r['scenario']:10s} P={r['P']:4d} "
              f"N={r['N']:3d} K={r['K']:2d} "
              f"csr={r['sweep_s_csr']*1e3:.1f}ms "
              f"dense={r['sweep_s_dense']*1e3:.1f}ms "
              f"({r['speedup_csr_vs_dense']}x) "
              f"traffic {r['traffic_reduction']}x lower")
    par = out["f64_parity_paper_scale"]
    print(f"sparse-routes: f64 lam gap={par['lam_max_abs_gap']:.2e} "
          f"objective_gap={par['objective_gap']} -> BENCH_sparse.json")


def run_online():
    out = kernel_bench.online_resolve()
    s = out["summary"]
    print(f"online-resolve: incremental={s['median_incremental_s']*1e3:.1f}ms"
          f"/event scratch={s['median_scratch_s']*1e3:.1f}ms/event "
          f"({s['speedup_vs_scratch']}x) gap mean={s['mean_gap']:.3%} "
          f"max={s['max_gap']:.3%}")
    for d in out["defrag_sweep"]:
        print(f"online-resolve: defrag_every={d['defrag_every']:2d} "
              f"mean_gap={d['mean_gap']:.3%} max_gap={d['max_gap']:.3%} "
              f"mean_event={d['mean_event_s']*1e3:.1f}ms -> BENCH_online.json")


def run_quality():
    out = kernel_bench.solver_quality()
    for row in out["quality"]:
        r = row["reference"]
        print(f"quality: {row['scenario']:10s} P={row['P']:4d} "
              f"reference obj={r['objective']:.1f} "
              f"({r['steps']} steps, {r['wall_s']}s)")
        for effort, e in row["efforts"].items():
            print(f"quality:   {effort:9s} obj={e['objective']:.1f} "
                  f"gap={e['gap_vs_reference']:+.3%} "
                  f"wall={e['wall_s']}s ({e['method']})"
                  f" -> BENCH_quality.json")


def run_federated():
    out = kernel_bench.federated_solve()
    f, d = out["flat"], out["federated"]
    print(f"federated: flat={f['wall_s']*1e3:.0f}ms "
          f"obj={f['objective']:.1f} | "
          f"federated={d['wall_s']*1e3:.0f}ms "
          f"(cold {d['wall_cold_s']*1e3:.0f}ms, "
          f"{d['compiles_first_solve']} compile) "
          f"obj={d['objective']:.1f} "
          f"({out['speedup_vs_flat']}x, "
          f"ratio {out['objective_ratio_fed_vs_flat']})")
    print(f"federated: regional W={d['regional_w']} "
          f"inter={d['inter_region_w']}W "
          f"conservation_gap={d['conservation_gap']:.2e} "
          f"-> BENCH_federated.json")


def run_fault():
    out = kernel_bench.fault_storm()
    for name, s in out["storms"].items():
        lat = s["recovery_latency_events"]
        print(f"fault-storm: {name:12s} availability={s['availability']:.4f} "
              f"stranded={s['stranded_service_s']:.1f}svc-h "
              f"recovery={'n/a' if lat is None else lat}ev "
              f"watts {s['healthy_w']:.1f}->{s['degraded_peak_w']:.1f}W "
              f"(overhead/live {s['overhead_per_live_service']:+.2%})")
        print(f"fault-storm: {name:12s} "
              f"conservation_gap={s['conservation_gap_degraded']:.2e} "
              f"fresh_compiles={s['fresh_compiles_measured_run']}")
    f = out["federated"]
    print(f"fault-storm: region-evac evacuated={f['n_evacuated']} "
          f"stranded={f['n_stranded']} readmitted={f['n_readmitted']} "
          f"availability={f['availability']:.4f} "
          f"dark_region={f['dark_region_w']}W")
    print(f"fault-storm: region-evac fleet "
          f"{f['healthy_fleet_w']:.1f}->{f['degraded_fleet_w']:.1f}W "
          f"conservation_gap={f['conservation_gap_degraded']:.2e} "
          f"-> BENCH_fault.json")


def _print_churn(out) -> None:
    s, w, e = out["scenario"], out["wave"], out["per_event"]
    print(f"churn-waves: {s['topology']} R={s['R']} "
          f"wave_size={s['wave_size']} x{s['n_waves']}")
    print(f"churn-waves: wave={w['events_per_s']:.1f} ev/s "
          f"({w['mean_event_ms']:.2f}ms/ev) "
          f"per_event={e['events_per_s']:.1f} ev/s "
          f"({e['mean_event_ms']:.2f}ms/ev) "
          f"speedup={out['speedup_wave_vs_per_event']}x")
    g, d = out["objective_gap"], out["defrag"]
    print(f"churn-waves: gap mean={g['mean']:.3%} max={g['max']:.3%} "
          f"fresh_compiles={w['fresh_compiles_measured']} "
          f"defrag_tick={d['mean_tick_s']*1e3:.1f}ms "
          f"({d['rows_per_tick']} rows, off the event path) "
          f"-> BENCH_churn.json")


def run_churn():
    out = kernel_bench.churn_waves()
    _print_churn(out)
    assert out["speedup_wave_vs_per_event"] >= 3.0, \
        "acceptance: >= 3x events/s vs the per-event baseline"
    assert abs(out["objective_gap"]["mean"]) <= 0.01, \
        "acceptance: mean objective gap <= 1% vs per-event resolution"


def run_churn_smoke():
    _print_churn(kernel_bench.churn_waves(
        n_live=32, wave_size=8, n_waves=2, n_olt=2, onus_per_olt=2,
        iot_per_onu=3, defrag_rows_per_tick=4))


def _print_obs(out) -> None:
    s = out["scenario"]
    print(f"obs: {s['topology']} R={s['R']} wave_size={s['wave_size']} "
          f"x{s['n_waves']} (best of {s['runs']})")
    print(f"obs: off={out['off']['events_per_s']:.1f} ev/s "
          f"on={out['on']['events_per_s']:.1f} ev/s "
          f"overhead={out['overhead_pct']:+.2f}% "
          f"identical_placements={out['identical_placements']}")
    m = out["micro_ns_per_call"]
    print(f"obs: micro inc={m['counter_inc']:.0f}ns "
          f"observe={m['histogram_observe']:.0f}ns span={m['span']:.0f}ns "
          f"jsonl={out['on']['jsonl_bytes']}B/"
          f"{out['on']['events_emitted']}ev -> BENCH_obs.json")


def run_obs():
    out = kernel_bench.telemetry_overhead()
    _print_obs(out)
    assert out["identical_placements"], \
        "acceptance: telemetry must not perturb placements"
    assert out["overhead_pct"] < 2.0, \
        "acceptance: enabled telemetry < 2% on the churn-wave bench"


def run_obs_smoke():
    # CI scale: the identity/zero-retrace asserts still run inside the
    # bench; the 2% timing gate is full-scale-only (ms waves = timer noise)
    _print_obs(kernel_bench.telemetry_overhead(
        n_live=32, wave_size=8, n_waves=2, n_olt=2, onus_per_olt=2,
        iot_per_onu=3, runs=1))


def run_flash():
    rows = kernel_bench.flash_cases()
    for r in rows:
        print(f"flash: {r['shape']} ref={r['ref_ms']}ms "
              f"({r['ref_gflops']} GF/s cpu) kernel_err={r['kernel_max_err']}")


def run_roofline():
    rows = roofline_table.write_table()
    n = len(rows)
    fits = sum(1 for r in rows if r["fits_16gb"])
    doms = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    print(f"roofline: {n} cells aggregated -> experiments/benchmarks/"
          f"roofline.csv ; fits-16GB {fits}/{n} ; dominant={doms}")


BENCHES = dict(fig3=run_fig3, fig4=run_fig4, gap=run_gap,
               placement=run_placement, solver=run_solver,
               sparse=run_sparse, online=run_online, quality=run_quality,
               federated=run_federated, fault=run_fault, churn=run_churn,
               obs=run_obs, flash=run_flash, roofline=run_roofline)
BENCHES["churn-smoke"] = run_churn_smoke
BENCHES["obs-smoke"] = run_obs_smoke
_SMOKE = ("churn-smoke", "obs-smoke")


def main() -> None:
    enable_compile_cache()
    # the -smoke names are CI-scale variants: they would overwrite their
    # BENCH_*.json with test-scale numbers, so only run them by name
    names = sys.argv[1:] or [n for n in BENCHES if n not in _SMOKE]
    for name in names:
        t0 = time.time()
        print(f"== {name} ==", flush=True)
        BENCHES[name]()
        print(f"== {name} done in {time.time() - t0:.1f}s ==", flush=True)


if __name__ == "__main__":
    main()
