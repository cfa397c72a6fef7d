#!/usr/bin/env python3
"""The placement service's chip benchmark: one cell, one seeded run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``chipbench/configs/<config>.json``: substrate, live fleet, placement spec,
bootstrap) under a traffic mix (``chipbench/traffic/<mix>.json``: its
stream, rate, wave cap, defrag-tick cadence).  In one process, in order:

1. draw the run's inputs from the seed (``plan``): the pool of services
   from the configuration's fleet generator, the warm-up waves and the
   window's due-ordered events from the mix's stream; build the
   configuration on the program's normal path (``CFNSession``);
2. bootstrap the live fleet (one full solve, or adopt a greedy placement);
3. warm up exactly the cell's compile buckets: the stream's warm-up waves
   (every wave bucket and row bucket its trace visits), plus a
   ``defrag_tick`` where the mix runs one;
4. serve the open-loop event stream for ``--seconds``, due times fixed
   before the window opens: whenever the loop is free it takes every due
   event into one ``apply_wave``, up to the cap (a replace pair counts
   two), cut before the departure of a service that arrives in the same
   wave, and sleeps until the next due time when nothing is due.  The
   departure of a service refused at admission is dropped and counted;
5. check what the window committed against the float64 reference
   (``oracle.py``), built from the benchmark's own record of the live set;
6. print one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``), and last
   ``checks``: each number compared, beside its limit.

A deployment is files that the harness finds by name, so a new one needs no
edit to a file that is here:

* a fleet generator ``chipbench/fleets/<name>.py``, named by the
  configuration's ``fleet.generator`` (default ``paper_chains``), exports
  ``services(rng, n, sources, **fleet["args"]) -> gen.Services``:
  rectangular ``F [n, V]``, ``H [n, V, V]``, ``src [n]``, a smaller service
  zero-padded (a zero-demand VM with no link changes no objective, in the
  program or in ``oracle.py``; ``H`` may be any DAG).  It imports numpy and
  ``gen`` and nothing of the program (``repro``);
* a stream ``chipbench/streams/<name>.py``, named by the mix's ``kind``
  (default ``replace_pairs``), exports ``events(rng, seconds, live,
  first_sid, **traffic) -> [gen.Event]`` (due-ordered arrivals,
  departures and replace pairs; arrival sids count up from ``first_sid``;
  the live count along it depends on how many services are live, not on
  which) and ``warm_plan(rng, live, first_sid, cap_events, counts,
  **traffic) -> [(arriving sids, departing sids)]``: warm-up waves that
  reach every wave and row bucket of a trace whose live counts are
  ``counts``, ending at the count they start from;
* a configuration and a mix (the JSON files above), and for each new
  per-layer metric a reader ``chipbench/metrics/<name>.py`` (or ``<name up
  to its first dot>.py``) whose ``read(run)`` takes the metric from the
  reduced trace (``run.trace``: device time, the harness's annotations and
  the program's ``repro.*`` spans), the telemetry counters of the window
  (``run.counters``) and the run's records, and gives None where there is
  nothing to read.

An event's latency runs from its due time to the return of the
``apply_wave`` that committed it, so it counts the wait behind earlier
waves.  With ``--trace 0`` the metrics are the cell's end-to-end metrics;
with ``--trace 1`` the program's telemetry is attached, the window's last
``trace_seconds`` run under the profiler, and the metrics are the cell's
per-layer metrics.

The benchmark runs only on a TPU: with no TPU, or fewer chips than the
cell asks for, it exits 3 and prints no result.  JAX's persistent
compilation cache is kept at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import copy  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import gen  # noqa: E402
import oracle  # noqa: E402

WINDOW = "chipbench.window"
WAVE = "chipbench.apply_wave"
TICK = "chipbench.defrag_tick"
WAIT = "chipbench.wait_due"
CHECK_NAMES = ("objective_gap", "fused_gap", "unpolished_share",
               "bookkeeping_errors", "failed_events")
POLISH_SAMPLE = 128         # wave commits whose polish the check reads
UNPOLISHED = 3e-4           # a move worth this share of the objective


class NoChip(RuntimeError):
    pass


@dataclass
class Commit:
    t: float                    # seconds after the window opened
    X: np.ndarray               # engine placement [R_bucket, V_bucket]
    sids: List[int]             # engine row order
    mine: frozenset             # the benchmark's record of the live set
    objective: float            # what the engine committed
    method: str
    history: List[float]


@dataclass
class Run:
    """What a metric reader sees of one run."""
    seconds: float
    device_kind: str
    setup_s: float = 0.0
    latencies_ms: np.ndarray = field(default_factory=lambda: np.zeros(0))
    committed: int = 0
    elapsed_s: float = 0.0
    compiles: int = 0
    compiled: Dict[str, int] = field(default_factory=dict)
    watts_ratios: List[tuple] = field(default_factory=list)  # (s, ratio)
    trace: Optional[object] = None          # xplane.Reduced
    check_seed: int = 0                     # draws the check's sample
    commits: List[Commit] = field(default_factory=list)
    pool: Optional[gen.Services] = None
    # the program's telemetry counters, window close less window open
    # (``--trace 1`` runs only)
    counters: Dict[str, float] = field(default_factory=dict)
    live_range: tuple = (0, 0)              # least and most live, planned


class CompileCounter:
    """Programs that JAX builds (backend compiles, persistent-cache loads
    included), by name, from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.on = False
        self.names: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and event == self.EVENT:
            name = str(kw.get("fun_name", "?"))
            self.names[name] = self.names.get(name, 0) + 1


def load_cell(root: Path, name: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / cfgs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{cell['traffic']}.json")
        .read_text())
    return bench, cell, cfg, traffic


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics this cell reports in this kind of run."""
    e2e = bench["end_to_end"]
    mine = [m for m in e2e if cell in m.get("workloads", [cell])]
    if not trace:
        return mine
    names = {m["name"] for m in mine}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


def load(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of the benchmark: a fleet generator
    (``fleets``), an event stream (``streams``) or a metric reader
    (``metrics``), found by its name."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """``metrics/<name>.py``, else ``metrics/<name up to its first dot>.py``:
    its ``read(run)`` gives the value, or None where there is nothing to
    read."""
    for stem in (name, name.split(".")[0]):
        if (HERE / "metrics" / f"{stem}.py").is_file():
            return load("metrics", stem).read
    raise FileNotFoundError(f"no reader for metric {name!r}")


def _seeds(seed: int, n: int) -> List[np.random.Generator]:
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(seed).spawn(n)]


@dataclass
class Plan:
    """A run's inputs, drawn from its seed before the program starts."""
    pool: gen.Services          # every service, bootstrap first, by sid
    boot: List[int]             # sids of the bootstrapped live fleet
    warm: List[tuple]           # warm-up waves: (arriving, departing sids)
    events: List[gen.Event]     # the window's trace, in due order
    counts: List[int]           # live counts along the trace


def _layers(cfg: dict) -> List[str]:
    return oracle.node_layers(cfg["substrate"]["preset"],
                              cfg["substrate"]["args"])[1]


def plan(cfg: dict, traffic: dict, seed: int, seconds: float) -> Plan:
    """The pool from the configuration's fleet generator, and the warm-up
    and the window's events from the mix's stream (``kind``, by default
    ``replace_pairs``).  The stream's warm-up plan is given the live counts
    its trace will pass through, read from a copy of the trace's generator
    before the warm-up: they depend on how many services are live, not on
    which, and the warm-up ends at the count it started from."""
    rng_fleet, rng_warm, rng_trace = _seeds(seed, 5)[:3]
    fleet = cfg["fleet"]
    stream = load("streams", traffic.get("kind", "replace_pairs"))
    n_live = int(fleet["live"])
    boot = list(range(n_live))
    counts = gen.live_counts(stream.events(copy.deepcopy(rng_trace), seconds,
                                           boot, n_live, **traffic), n_live)
    warm = stream.warm_plan(rng_warm, boot, n_live,
                            int(traffic["wave_cap_events"]), counts,
                            **traffic)
    live, nxt = set(boot), n_live
    for arrive, depart in warm:
        live.difference_update(depart)
        live.update(arrive)
        nxt = max([nxt] + [s + 1 for s in arrive])
    if len(live) != n_live:
        raise ValueError(f"the warm-up ends at {len(live)} live services, "
                         f"not the {n_live} it started from")
    events = stream.events(rng_trace, seconds, sorted(live), nxt, **traffic)
    if gen.live_counts(events, n_live) != counts:
        raise ValueError("the stream's live counts depend on which services "
                         "are live")
    n = max([nxt] + [e.sid + 1 for e in events if e.sid is not None])
    pick = fleet["sources"]
    layer_nodes = [i for i, t in enumerate(_layers(cfg))
                   if t == pick["layer"]]
    sources = layer_nodes[:max(pick.get("min", 1),
                               int(len(layer_nodes) * pick["fraction"]))]
    pool = load("fleets", fleet.get("generator", "paper_chains")).services(
        rng_fleet, n, sources, **fleet.get("args", {}))
    return Plan(pool=pool, boot=boot, warm=warm, events=events,
                counts=counts)


def serve(cfg: dict, traffic: dict, seed: int, seconds: float,
          trace_dir: Optional[str], device_kind: str,
          session_hook=None) -> tuple:
    """Steps 1-5 for one seed; returns (Run, checks, counts, peak device
    memory in bytes)."""
    import jax
    from repro.api import CFNSession, PlacementSpec
    from repro.core import solvers, topology, vsr

    counter = CompileCounter(jax)
    p = plan(cfg, traffic, seed, seconds)
    rng_key, rng_check = _seeds(seed, 5)[3:]
    pool, events = p.pool, p.events

    # 1. configuration
    topo = getattr(topology, cfg["substrate"]["preset"])(
        **cfg["substrate"]["args"])
    svc = [vsr.VSRBatch(F=pool.F[i:i + 1], H=pool.H[i:i + 1],
                        src=pool.src[i:i + 1],
                        input_vm=np.zeros(1, np.int32))
           for i in range(len(pool))]
    cap_events = int(traffic["wave_cap_events"])
    tick_every = int(traffic.get("defrag_tick_every_events", 0))
    spec = PlacementSpec(**cfg["spec"])
    key = jax.random.PRNGKey(int(rng_key.integers(2 ** 31 - 1)))
    s = CFNSession(topo, spec, key=key)
    tel = None
    if trace_dir:
        # the program's spans (``repro.*`` on the profiler's clock) and
        # counters, in traced runs only
        from repro.telemetry import Telemetry
        tel = Telemetry(convergence=False)
        s.attach_telemetry(tel)
    if session_hook is not None:
        session_hook(s)

    # 2. bootstrap
    live = set(p.boot)
    boot = [svc[i] for i in p.boot]
    if cfg["bootstrap"] == "greedy":
        layers = _layers(cfg)
        hosts = [i for tag in cfg["greedy_hosts"]
                 for i, t in enumerate(layers) if t == tag]
        X0 = gen.greedy_bootstrap(pool.F[p.boot], hosts)
        s.engine.bootstrap(boot, X0=X0)
    else:
        s.engine.bootstrap(boot)

    commits: List[Commit] = []
    counts = dict(attempted=0, failed=0, errors=0, unserved=0, dropped=0)

    def record(t: float) -> None:
        res = s.result
        commits.append(Commit(t=t, X=s.X, sids=s.sids,
                              mine=frozenset(live),
                              objective=float(res.objective),
                              method=res.method,
                              history=list(res.history)))

    def wave(arrive: List[int], depart: List[int]):
        wr = s.apply_wave([(svc[i], i) for i in arrive], depart)
        live.difference_update(depart)
        live.update(wr.admitted)
        return len(arrive) - len(wr.admitted)

    # 3. warm-up: every wave and row bucket the window can use, and a
    # defrag tick
    for arrive, depart in p.warm:
        if wave(arrive, depart):
            raise RuntimeError("the warm-up had an arrival refused")
    if tick_every:
        s.defrag_tick()
    traces0 = dict(solvers.TRACE_COUNTS)

    # 4. the window
    drain_s = float(traffic.get("drain_s", 0.0))
    lat: List[float] = []
    annotate = (jax.profiler.TraceAnnotation if trace_dir
                else lambda name: contextlib.nullcontext())
    # the profiler records the window's last ``trace_seconds`` (all of it
    # by default): a device trace holds an event per operation run, some
    # hundred thousand a second here, so a whole window would not read back
    # in a run's time
    trace_from = seconds - float(traffic.get("trace_seconds", seconds))
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    record(0.0)
    tel0 = dict(tel.counters) if tel is not None else {}
    counter.on = True
    clock = time.perf_counter
    t0 = clock()
    setup_s = t0 - T_PROCESS
    i, since_tick, t_end, tracing = 0, 0, 0.0, False
    with contextlib.ExitStack() as traced:
        while i < len(events):
            now = clock() - t0
            if now >= seconds + drain_s:
                break
            if trace_dir and not tracing and now >= trace_from:
                tracing = True
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                traced.callback(jax.profiler.stop_trace)
                traced.enter_context(annotate(WINDOW))
            if events[i].due > now:
                with annotate(WAIT):
                    time.sleep(events[i].due - now)
                continue
            j = gen.cut_wave(events, i, now, cap_events)
            batch = events[i:j]
            i = j
            arrive = [e.sid for e in batch if e.sid is not None]
            # the departure of a service refused at admission is dropped
            depart = [e.victim for e in batch
                      if e.victim is not None and e.victim in live]
            n_events = len(arrive) + len(depart)
            counts["dropped"] += sum(e.victim is not None
                                     for e in batch) - len(depart)
            if not n_events:
                continue
            try:
                with annotate(WAVE):
                    refused = wave(arrive, depart)
            except Exception:
                traceback.print_exc()
                counts["errors"] += 1
                counts["attempted"] += n_events
                counts["failed"] += n_events
                break
            t_end = clock() - t0
            counts["attempted"] += n_events
            counts["failed"] += refused
            gone = set(depart)
            for e in batch:
                # the departure, and the arrival where it was admitted
                n = (e.victim in gone) + (e.sid is not None and e.sid in live)
                lat += [t_end - e.due] * n
            record(t_end)
            if tick_every:
                since_tick += n_events
                if since_tick >= tick_every:
                    since_tick -= tick_every
                    with annotate(TICK):
                        res = s.defrag_tick()
                    if res is not None:
                        record(clock() - t0)
    counter.on = False
    counts["unserved"] = sum(e.size for e in events[i:])
    compiled = dict(counter.names)
    for name, n in solvers.TRACE_COUNTS.items():
        if n != traces0.get(name, 0):
            compiled[f"trace:{name}"] = n - traces0.get(name, 0)
    mem = jax.devices()[0].memory_stats() or {}
    run = Run(seconds=seconds, device_kind=device_kind, setup_s=setup_s,
              latencies_ms=1e3 * np.asarray(lat),
              committed=counts["attempted"] - counts["failed"],
              elapsed_s=max(seconds, t_end),
              compiles=sum(compiled.values()), compiled=compiled,
              live_range=(min(p.counts), max(p.counts)))
    if tel is not None:
        run.counters = {k: v - tel0.get(k, 0)
                        for k, v in tel.counters.items()
                        if v != tel0.get(k, 0)}
        tel.detach_traces()
    del s

    # 5. the check, against the benchmark's own record
    run.check_seed = int(rng_check.integers(2 ** 63))
    t_check = time.perf_counter()
    checks = check(cfg, pool, commits, seconds, run.check_seed)
    checks["check_s"] = time.perf_counter() - t_check
    run.watts_ratios = checks.pop("watts_ratios")
    run.commits, run.pool = commits, pool
    checks["failed_events"] = counts["failed"]
    return run, checks, counts, int(mem.get("peak_bytes_in_use", 0))


def check(cfg: dict, pool: gen.Services, commits: List[Commit],
          seconds: float, check_seed: int, control: bool = False) -> dict:
    """Each committed placement of the window against the float64
    reference, built from the benchmark's own record of the live set:

    * ``bookkeeping_errors``: commits whose live set differs from the
      record, plus input VMs off their sources;
    * ``objective_gap``: the widest relative gap between an objective the
      program reported for a commit (the committed one, and a wave's
      carried best, the last entry of its history) and the reference
      objective of the committed placement;
    * ``fused_gap``: the mean, over the commits a full solve took from the
      fused anneal kernel, of the relative gap between the kernel's own
      best objective (carried by float32 deltas over all its steps) and
      the reference objective of the placement it returned.  The mean is
      read, since the widest of a window swings with the drift of a few
      launches;
    * ``unpolished_share``: the share of a sample of the wave commits, drawn
      from ``check_seed``, in which the best single move of one free VM
      would still save more than ``UNPOLISHED`` of the objective.  A wave
      ends in full polish sweeps, which leave nearly every VM on its best
      node.  A share is read, not the mean saving: two sweeps need not
      converge, and on a few commits of a sound run the last VM left on a
      CDC server could still move and switch the server off, a saving of
      half the objective that would set the mean alone.

    ``control`` puts the reference in the program's place one precision
    below its float32: every reported objective is the reference's in
    bfloat16, and every sampled placement gets one more polish sweep scored
    in bfloat16."""
    sub = oracle.build_substrate(cfg["substrate"]["preset"],
                                 cfg["substrate"]["args"])
    V = pool.F.shape[1]
    # a wave's commit, also where a full solve kept it: "defrag-kept(wave)"
    waves = [k for k, c in enumerate(commits) if "wave" in c.method]
    rng = np.random.default_rng(check_seed)
    sample = set(rng.choice(waves, size=min(len(waves), POLISH_SAMPLE),
                            replace=False).tolist()) if waves else set()
    waves = set(waves)
    bad, gap, fgap, unpolished, worst = 0, 0.0, [], [], {}
    per_commit = []
    rel = lambda got, want: abs(got - want) / abs(want)
    for k, c in enumerate(commits):
        rows = np.asarray(c.sids, np.int64)
        X = c.X[:len(rows), :V]
        svc = (sub, pool.F[rows], pool.H[rows], pool.src[rows])
        if set(c.sids) != c.mine or len(c.sids) != len(c.mine):
            bad += 1
        bad += int((X[:, 0] != pool.src[rows]).sum())
        ref = oracle.objective(*svc, X)
        per_commit.append(ref)
        low = (oracle.objective(*svc, X, cast=oracle.bf16)["objective"]
               if control else None)
        said = [c.objective] + ([c.history[-1]] if k in waves and c.history
                                else [])
        g = max(rel(low if control else o, ref["objective"]) for o in said)
        if g >= gap:
            gap, worst = g, dict(t=c.t, violation=ref["violation"],
                                 watts=ref["watts"], method=c.method)
        if "anneal(fused)" in c.method and c.history:
            fgap.append(rel(low if control else c.history[0],
                            ref["objective"]))
        if k in sample:
            Xp = oracle.sweep(*svc, X, cast=oracle.bf16) if control else X
            unpolished.append(oracle.polish_residual(*svc, Xp) > UNPOLISHED)
    # each committed placement stands from its commit to the next; the
    # ratio is averaged over the window by the time each one stood
    ends = [c.t for c in commits[1:]] + [seconds]
    ratios = []
    for c, ref, end in zip(commits, per_commit, ends):
        dur = min(end, seconds) - min(c.t, seconds)
        if dur > 0:
            rows = np.asarray(c.sids, np.int64)
            cdc = oracle.objective(
                sub, pool.F[rows], pool.H[rows], pool.src[rows],
                oracle.cdc_placement(sub, pool.F[rows], pool.src[rows]))
            ratios.append((dur, ref["watts"] / cdc["watts"]))
    return dict(objective_gap=gap, worst_commit=worst,
                fused_gap=float(np.mean(fgap)) if fgap else 0.0,
                fused_commits=len(fgap),
                unpolished_share=(float(np.mean(unpolished)) if unpolished
                                  else 0.0),
                polished_commits=len(sample),
                bookkeeping_errors=bad, commits=len(commits),
                watts_ratios=ratios)


# idle-gap labels of the harness's own annotations
HARNESS_LABELS = {WAVE: "engine host work inside apply_wave",
                  TICK: "defrag_tick host work",
                  WAIT: "waiting for a due event"}


def breakdown(red, window) -> dict:
    """The device programs that took most time, and the device's idle time
    by what the host was doing in it: the innermost program span
    (``repro.*``) that holds a gap's midpoint, else the harness's
    annotation that holds it, else the loop's own bookkeeping."""
    import xplane as tr
    progs = sorted(red.programs.items(), key=lambda kv: -kv[1])[:10]
    a, b = window
    gaps = tr.gaps(red.busy, a, b)
    mids = gaps.mean(axis=1)            # sorted: the gaps are disjoint
    what = np.full(len(gaps), "loop bookkeeping between waves", object)
    spans = [(HARNESS_LABELS.get(name, name), s0, s1)
             for name, iv in red.spans.items()
             if name in HARNESS_LABELS or name.startswith(tr.PROGRAM)
             for s0, s1 in iv]
    # longest first, so that the innermost span holding a midpoint labels
    # it last; a program span lies inside the harness's annotations
    spans.sort(key=lambda sp: (sp[0] in HARNESS_LABELS.values(),
                               sp[2] - sp[1]), reverse=True)
    for label, s0, s1 in spans:
        what[np.searchsorted(mids, s0):
             np.searchsorted(mids, s1, side="right")] = label
    idle: Dict[str, list] = {}
    for label, (g0, g1) in zip(what, gaps):
        idle.setdefault(label, []).append((g1 - g0) * 1e-9)
    gaps_out = sorted(([f"{k}: {len(v)} gaps, longest {max(v) * 1e3:.3f} ms",
                        float(sum(v))] for k, v in idle.items()),
                      key=lambda e: -e[1])[:10]
    return {"device_ops": [[n, ns * 1e-9] for n, ns in progs],
            "idle_gaps": gaps_out}


def init_jax():
    """JAX with its persistent compilation cache at a fixed path inside the
    checkout (every program cached, however small), and the program on the
    path."""
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    # the TPU runtime logs under a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    return jax


def run_cell(bench: dict, cell: dict, cfg: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, require_tpu: bool = True,
             session_hook=None, trace_dir: Optional[str] = None) -> dict:
    """One run of one cell; returns the result line as a dict."""
    jax = init_jax()
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell["chips"]):
        raise NoChip(f"JAX found {len(devs)} {devs[0].platform} device(s); "
                     f"the cell needs {cell['chips']} TPU chip(s)")
    kind = devs[0].device_kind
    if trace and trace_dir is None:
        trace_dir = str(ROOT / ".chipbench_trace")
    run, checks, counts, mem = serve(cfg, traffic, seed, seconds,
                                     trace_dir if trace else None, kind,
                                     session_hook)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {}
    if trace:
        import xplane as tr
        red = tr.reduce(tr.find_xplane(trace_dir),
                        (WINDOW, WAVE, TICK, WAIT))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.trace = red
        if red.spans[WINDOW]:
            a, b = red.window(WINDOW)
            device["busy_s"] = red.busy_in(a, b) * 1e-9
            device["window_s"] = (b - a) * 1e-9
            out["breakdown"] = breakdown(red, (a, b))
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    limits = cfg["limits"]
    shown = {k: {"value": checks[k], "limit": limits[k]}
             for k in CHECK_NAMES}
    correct = (counts["errors"] == 0 and counts["attempted"] > 0
               and all(v["value"] <= v["limit"] for v in shown.values()))
    result = {"correct": bool(correct), "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": metrics,
              "device": device}
    result.update(out)
    result["info"] = {"unserved": counts["unserved"],
                      "departures_dropped": counts["dropped"],
                      "live_range": list(run.live_range),
                      "commits": checks["commits"],
                      "fused_commits": checks["fused_commits"],
                      "fused_gap": checks["fused_gap"],
                      "polished_commits": checks["polished_commits"],
                      "check_s": checks["check_s"],
                      "worst_commit": checks["worst_commit"],
                      "compiled_in_window": run.compiled,
                      "setup_s": run.setup_s}
    if trace:
        result["info"]["counters"] = run.counters
    result["checks"] = shown
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chipbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    bench, cell, cfg, traffic = load_cell(ROOT, args.workload)
    try:
        result = run_cell(bench, cell, cfg, traffic, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 3
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
