"""Unified telemetry plane tests.

The contract under test: telemetry OBSERVES the serving stack, it never
perturbs it.  Disabled (telemetry=None) the engine must be bit-identical
to a never-instrumented one -- same placements, same power, same
admission decisions, zero extra compiles -- and enabled it must record
faithfully: span nesting and exception safety (on the host clock and on
the profiler's trace), the wave's phase spans, bounded event rings,
histogram bucket edges,
JSONL round-trips through the report pipeline, the energy ledger summing
exactly to the per-tenant/per-region attribution, the monitor mirror
staying in lockstep with the standalone counters, and the compile
attribution agreeing with ``solvers.TRACE_COUNTS``.  The package itself
must lint clean under tracelint with an empty baseline.
"""
import glob
import json
import math
from collections import deque
from pathlib import Path

import numpy as np
import pytest

from repro.api import CFNSession, FederatedSession, PlacementSpec
from repro.core import dynamic, federation, power, solvers, topology, vsr
from repro.fault.monitor import PlacementMonitor
from repro.kernels import ref as kref
from repro.telemetry import (EnergyLedger, Telemetry, load_events, span,
                             summarize_events, tiers_of, validate_events)
from repro.telemetry.registry import _bucket_edge

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    return topology.paper_topology()


def _svc(seed, n_vms=3):
    return vsr.random_vsrs(1, rng=np.random.default_rng(seed),
                           n_vms=n_vms)


def _spec(**kw):
    return PlacementSpec(method="anneal", effort="quick", **kw)


def _churn(sess):
    """A small deterministic churn sequence: 3 adds, 1 remove, 1 wave."""
    for seed in (0, 1, 2):
        sess.engine.tick(float(seed))
        sess.add(_svc(seed))
    sess.engine.tick(3.0)
    sess.remove(sess.sids[0])
    sess.engine.tick(4.0)
    sess.apply_wave([(_svc(7), None, 0), (_svc(8), None, 0)],
                    [sess.sids[0]])
    return sess


# ---------------------------------------------------------------------------
# disabled path: a no-op by construction
# ---------------------------------------------------------------------------

def test_disabled_telemetry_is_identical(topo, tmp_path):
    """telemetry=None vs a live Telemetry: same placements (bitwise),
    same f64-oracle power, same admission outcomes, and the instrumented
    run adds ZERO fresh solver compiles beyond the baseline run's.  The
    churn's wave is the sixth event since the last full solve, so it runs
    the spec's full portfolio solve (with its phase spans) too."""
    spec = PlacementSpec(method="cfn-milp", effort="quick", defrag_every=6)
    plain = _churn(CFNSession(topo, spec, telemetry=None))
    before = dict(solvers.TRACE_COUNTS)
    tel = Telemetry(jsonl_path=str(tmp_path / "run.jsonl"))
    instr = _churn(CFNSession(topo, spec, telemetry=tel))
    fresh = {k: solvers.TRACE_COUNTS.get(k, 0) - before.get(k, 0)
             for k in solvers.TRACE_COUNTS
             if solvers.TRACE_COUNTS.get(k, 0) != before.get(k, 0)}
    assert not fresh, \
        f"instrumented replay of an identical scenario retraced: {fresh}"

    assert plain.sids == instr.sids          # same admissions, same order
    assert [st.method for st in plain.stats] == \
        [st.method for st in instr.stats]
    assert instr.stats[-1].event == "wave"
    assert instr.stats[-1].method != "wave"  # the wave's full solve won
    names = {e["name"] for e in tel.events if e["type"] == "span"}
    assert {"wave.full_solve", "portfolio.warm_starts"} <= names
    Xp, Xi = np.asarray(plain.X), np.asarray(instr.X)
    assert np.array_equal(Xp, Xi)
    assert plain.power_w() == instr.power_w()

    # pin the power both engines agree on against the f64 oracle
    eng = instr.engine
    vs = eng._vsrs[0]
    for b in eng._vsrs[1:]:
        vs = vs.concat(b)
    prob = power.build_problem(topo, vs)
    oracle = kref.placement_objective_f64(prob, Xi[:vs.R, :vs.V])
    assert instr.power_w() == pytest.approx(oracle, rel=1e-5)
    tel.close()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_parents():
    tel = Telemetry()
    with tel.span("outer") as so:
        with tel.span("inner") as si:
            assert si.parent == so.id
        with tel.span("inner") as s2:
            assert s2.parent == so.id
    assert so.parent is None
    assert not tel._span_stack
    assert tel.counters["span.outer"] == 1
    assert tel.counters["span.inner"] == 2
    evs = [e for e in tel.events if e["type"] == "span"]
    by_id = {e["id"]: e for e in evs}
    inner = [e for e in evs if e["name"] == "inner"]
    assert all(by_id[e["parent"]]["name"] == "outer" for e in inner)


def test_span_exception_safe():
    tel = Telemetry()
    with pytest.raises(ValueError):
        with tel.span("boom"):
            raise ValueError("no")
    assert not tel._span_stack               # stack popped
    ev = [e for e in tel.events if e["type"] == "span"][-1]
    assert ev["ok"] is False and ev["err"] == "ValueError"
    assert tel.hists["span.boom.ms"].count == 1   # duration still recorded


def test_span_helper_disabled_is_shared_noop():
    """``span(None, ...)`` is one shared no-op context (nothing allocated,
    nothing recorded); ``span(tel, ...)`` is a live ``Span``."""
    a, b = span(None, "x"), span(None, "y", r_bucket=4)
    assert a is b
    with a as sp:
        assert sp is None
    tel = Telemetry()
    with span(tel, "x", r_bucket=4) as sp:
        assert sp.attrs == {"r_bucket": 4}
    assert tel.counters["span.x"] == 1


def test_event_rings_keep_bound_and_order():
    """``Telemetry.events`` and ``PlacementMonitor.events`` are bounded
    rings: the newest ``max_events``, oldest first, also across a merge."""
    tel = Telemetry(max_events=3)
    for i in range(7):
        tel.emit("event", kind="k", n=i)
    assert isinstance(tel.events, deque)
    assert [e["n"] for e in tel.events] == [4, 5, 6]
    mon = PlacementMonitor(max_events=3)
    for i in range(5):
        mon.count("k", detail=str(i))
    assert list(mon.events) == [("k", "2"), ("k", "3"), ("k", "4")]
    other = PlacementMonitor()
    other.count("j", detail="a")
    other.count("j", detail="b")
    mon.merge(other)
    assert list(mon.events) == [("k", "4"), ("j", "a"), ("j", "b")]
    assert mon.events.maxlen == 3
    mon.reset()
    assert not mon.events and mon.events.maxlen == 3


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

RESOLVE_PHASES = ("prep", "targeted", "anneal", "select", "polish",
                  "result")


# the host event of one XLA program launch on the CPU backend
LAUNCH = "PjRtCpuExecutable::Execute"


def _profiled(trace_dir, fn):
    """Run ``fn`` under the JAX profiler and return the host plane's
    ``repro.*`` events and program launches (under ``LAUNCH``):
    {name: [(start_ns, end_ns), ...]}."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro.") or ev.name == LAUNCH:
                    a = float(ev.start_ns)
                    spans.setdefault(ev.name, []).append(
                        (a, a + float(ev.duration_ns)))
    return spans


def _inside(spans, child, parent):
    """Every ``child`` event lies inside some ``parent`` event."""
    assert spans.get(child), f"no {child} in the trace"
    for a, b in spans[child]:
        assert any(pa <= a and b <= pb for pa, pb in spans[parent]), \
            f"{child} at [{a}, {b}] lies in no {parent}"


@pytest.fixture(scope="module")
def wave_trace(topo, tmp_path_factory):
    """One instrumented session, traced over two replace waves: the first
    a plain wave, the second the one that makes a full solve due."""
    tel = Telemetry(convergence=False)
    sess = CFNSession(topo, PlacementSpec(effort="standard",
                                          defrag_every=6), telemetry=tel)
    sess.solve(vsr.random_vsrs(4, rng=np.random.default_rng(3), n_vms=3))
    seeds = iter(range(20, 30))

    def replace():
        sess.apply_wave([(_svc(next(seeds)), None)], [sess.sids[0]])

    replace()                                  # compiles outside the trace
    trace_dir = tmp_path_factory.mktemp("trace")
    waves = []

    def both():
        for _ in range(2):
            replace()
            waves.append(sess.stats[-1].method)

    spans = _profiled(trace_dir, both)
    return spans, waves, tel


def test_wave_phase_spans_nest_on_profiler_clock(wave_trace):
    """Every span is also a ``repro.<name>`` profiler annotation:
    ``apply_wave`` holds ``resolve_wave``, which holds the six resolve
    phases, and the wave's own phases lie inside ``apply_wave``."""
    spans, waves, _ = wave_trace
    assert len(spans["repro.apply_wave"]) == 2
    assert len(spans["repro.resolve_wave"]) == 2
    _inside(spans, "repro.resolve_wave", "repro.apply_wave")
    for phase in RESOLVE_PHASES:
        _inside(spans, f"repro.resolve.{phase}", "repro.resolve_wave")
        assert len(spans[f"repro.resolve.{phase}"]) == 2
    for phase in ("detach", "rebuild", "warm_state", "commit", "drain"):
        _inside(spans, f"repro.wave.{phase}", "repro.apply_wave")
        assert len(spans[f"repro.wave.{phase}"]) == 2
    assert "repro.wave.admission" not in spans   # no admission budget
    # the phases follow one another: none overlaps the next
    order = ["repro.wave.detach", "repro.wave.rebuild",
             "repro.wave.warm_state", "repro.resolve_wave",
             "repro.wave.commit", "repro.wave.drain"]
    for w in range(2):
        ends = [spans[n][w] for n in order]
        assert all(e0[1] <= e1[0] for e0, e1 in zip(ends, ends[1:]))


def test_full_solve_wave_spans_portfolio_phases(wave_trace):
    """The wave that makes a full solve due holds ``wave.full_solve``,
    which holds the portfolio's phases, and commits after it."""
    spans, waves, _ = wave_trace
    assert waves[0] == "wave" and waves[1] != "wave"
    full, = spans["repro.wave.full_solve"]
    second = spans["repro.apply_wave"][1]
    assert second[0] <= full[0] and full[1] <= second[1]
    _inside(spans, "repro.portfolio.warm_starts", "repro.wave.full_solve")
    _inside(spans, "repro.portfolio.anneal", "repro.wave.full_solve")
    commit = spans["repro.wave.commit"][1]
    assert full[1] <= commit[0]


def test_single_events_run_the_wave_path(topo, tmp_path):
    """``add`` and ``remove`` are waves of one: each holds exactly one
    ``resolve_wave`` and the wave's phases, in order, and neither is
    counted as a public ``apply_wave`` call."""
    tel = Telemetry(convergence=False)
    sess = CFNSession(topo, PlacementSpec(effort="quick", defrag_every=0),
                      telemetry=tel)
    sess.solve(vsr.random_vsrs(4, rng=np.random.default_rng(5), n_vms=3))

    def add_remove():
        sess.add(_svc(40), sid=40)
        sess.remove(sess.sids[0])

    spans = _profiled(tmp_path, add_remove)
    assert "repro.apply_wave" not in spans
    assert len(spans["repro.resolve_wave"]) == 2
    phases = {"repro.add": ["repro.wave.rebuild", "repro.wave.warm_state",
                            "repro.resolve_wave", "repro.wave.commit"],
              "repro.remove": ["repro.wave.detach", "repro.wave.rebuild",
                               "repro.wave.warm_state", "repro.resolve_wave",
                               "repro.wave.commit", "repro.wave.drain"]}
    for event, order in phases.items():
        (a, b), = spans[event]
        inside = [[s for s in spans.get(n, []) if a <= s[0] and s[1] <= b]
                  for n in order]
        assert all(len(s) == 1 for s in inside), (event, inside)
        ends = [s[0] for s in inside]
        assert all(e0[1] <= e1[0] for e0, e1 in zip(ends, ends[1:]))
    assert [s.event for s in sess.stats[-2:]] == ["add", "remove"]
    assert [s.method for s in sess.stats[-2:]] == ["wave", "wave"]
    solves = [e for e in tel.events if e["type"] == "solve"]
    assert [e["event"] for e in solves[-2:]] == ["add", "remove"]


ANNEAL_SPANS = ("repro.resolve.anneal", "repro.portfolio.anneal")


def test_each_anneal_span_launches_one_program(wave_trace):
    """Each anneal -- the wave's re-solve and the full solve's portfolio
    anneal -- is one XLA program launch on the profiler's trace: its
    chains' start, proposal draws, chains and best-chain pick no longer
    dispatch op by op."""
    spans, _, _ = wave_trace
    launches = spans[LAUNCH]
    assert len(spans["repro.resolve.anneal"]) == 2
    assert len(spans["repro.portfolio.anneal"]) == 1
    for name in ANNEAL_SPANS:
        for a, b in spans[name]:
            inside = [x for x, y in launches if a <= x and y <= b]
            assert len(inside) == 1, (name, len(inside))


def test_anneal_program_counter_matches_spans(wave_trace):
    """``solver.anneal_programs`` counts one program per anneal span over
    the session's life (its first solve, the untraced wave and the two
    traced ones)."""
    _, _, tel = wave_trace
    programs = {k: v for k, v in tel.counters.items()
                if k.startswith("solver.anneal_programs")}
    n_spans = sum(tel.hists[f"span.{name[len('repro.'):]}.ms"].count
                  for name in ANNEAL_SPANS)
    assert sum(programs.values()) == n_spans >= 5
    assert set(programs) == {
        "solver.anneal_programs{backend=delta,site=portfolio}",
        "solver.anneal_programs{backend=delta,site=resolve}"}


def test_warm_starts_span_launches_one_program(wave_trace):
    """The full solve's warm starts -- both coordinate descents and the
    IoT first fit -- are one XLA program launch on the profiler's trace,
    and ``solver.warm_start_programs`` counts one program per
    ``portfolio.warm_starts`` span over the session's life."""
    spans, _, tel = wave_trace
    launches = spans[LAUNCH]
    (a, b), = spans["repro.portfolio.warm_starts"]
    assert len([x for x, y in launches if a <= x and y <= b]) == 1
    programs = {k: v for k, v in tel.counters.items()
                if k.startswith("solver.warm_start_programs")}
    n_spans = tel.hists["span.portfolio.warm_starts.ms"].count
    assert programs == {
        "solver.warm_start_programs{site=portfolio}": n_spans}
    assert n_spans >= 2


def test_coordinate_sweeps_counter_sums_histories(topo):
    """``solver.coordinate_sweeps`` adds the sweeps each warm-start program
    ran, read from the program's own count: the summed history lengths
    of the descents, per site."""
    problem = power.build_problem(topo, vsr.random_vsrs(
        6, rng=np.random.default_rng(9), n_vms=3))
    cdc = topo.layer_indices("cdc")[0]
    base = np.full((problem.R, problem.V), cdc, np.int32)
    ffd = solvers.fixed_layer(problem, topo, "iot").X
    want = [len(solvers.coordinate(problem, X0).history)
            for X0 in (base, ffd)]
    tel = Telemetry()
    solvers.solve_portfolio(problem, topo, PlacementSpec(effort="quick"),
                            telemetry=tel)
    res = solvers.coordinate(problem, base, max_sweeps=4, telemetry=tel)
    assert {k: v for k, v in tel.counters.items()
            if k.startswith("solver.")} == {
        "solver.warm_start_programs{site=portfolio}": 1,
        "solver.coordinate_sweeps{site=portfolio}": sum(want),
        "solver.warm_start_programs{site=coordinate}": 1,
        "solver.coordinate_sweeps{site=coordinate}": len(res.history)}
    assert 1 < sum(want) and len(res.history) == min(4, want[0])


def test_span_annotation_closes_on_exception(tmp_path):
    """A span that raises still closes its profiler annotation: the next
    span is its sibling on the trace, not its child."""
    tel = Telemetry()

    def work():
        with pytest.raises(ValueError):
            with span(tel, "boom"):
                raise ValueError("no")
        with span(tel, "after"):
            pass

    spans = _profiled(tmp_path, work)
    (a0, b0), = spans["repro.boom"]
    (a1, b1), = spans["repro.after"]
    assert b0 <= a1
    assert tel.hists["span.boom.ms"].count == 1


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_bucket_edges():
    # exact powers of two land on their own edge; everything else rounds
    # up to the next power of two; non-positive values pool at 0
    assert _bucket_edge(1.0) == 1.0
    assert _bucket_edge(1.5) == 2.0
    assert _bucket_edge(2.0) == 2.0
    assert _bucket_edge(2.1) == 4.0
    assert _bucket_edge(0.75) == 1.0
    assert _bucket_edge(0.5) == 0.5
    assert _bucket_edge(0.0) == 0.0
    assert _bucket_edge(-3.0) == 0.0
    for v in (1e-6, 0.3, 7.0, 1234.5):
        e = _bucket_edge(v)
        assert v <= e < 2 * v
        m, _ = math.frexp(e)
        assert m == 0.5                       # an exact power of two


def test_histogram_stats_and_prometheus():
    tel = Telemetry()
    for v in (1.0, 1.5, 2.0, 2.1, 100.0):
        tel.observe("lat.ms", v)
    h = tel.hists["lat.ms"]
    assert h.count == 5 and h.min == 1.0 and h.max == 100.0
    assert h.sum == pytest.approx(106.6)
    assert h.buckets == {1.0: 1, 2.0: 2, 4.0: 1, 128.0: 1}
    text = tel.prometheus()
    assert 'repro_lat_ms_bucket{le="+Inf"} 5' in text
    assert "repro_lat_ms_count 5" in text
    # le buckets are cumulative
    assert 'repro_lat_ms_bucket{le="2.0"} 3' in text


def test_metric_labels_flatten_sorted():
    tel = Telemetry()
    tel.inc("waves", b="y", a=1)
    tel.inc("waves", a=1, b="y")
    assert tel.counters == {"waves{a=1,b=y}": 2}


# ---------------------------------------------------------------------------
# JSONL round-trip
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip(tmp_path):
    path = tmp_path / "run.jsonl"
    with Telemetry(jsonl_path=str(path)) as tel:
        with tel.span("work", r_bucket=4):
            tel.inc("things")
        tel.ledger.tick(0.0, total_w=10.0, net_w=4.0, proc_w=6.0)
        tel.ledger.tick(2.0, total_w=20.0, net_w=8.0, proc_w=12.0)
        tel.emit("event", kind="node_failed", detail="p3", n=1)
    evs = load_events(str(path))
    assert validate_events(evs) == []
    assert evs[0]["type"] == "meta" and evs[0]["version"] == 1
    assert evs[-1]["type"] == "summary"
    s = summarize_events(evs)
    assert s["spans"]["work"]["count"] == 1
    # left-hold: 10 W held for 2 h = 72 kJ, final sample extends nothing
    assert s["energy"]["joules_total"] == pytest.approx(10.0 * 2 * 3600)
    assert s["energy"]["joules_net"] == pytest.approx(4.0 * 2 * 3600)
    assert s["monitor"] == {"node_failed": 1}


def test_load_events_rejects_bad_lines(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"type": "meta", "ts": 0, "version": 1}\nnot json\n')
    with pytest.raises(ValueError):
        load_events(str(path))


def test_validate_flags_missing_fields():
    assert validate_events([{"type": "span", "ts": 1.0}])  # no name/dur
    assert validate_events([{"ts": 1.0}])                  # no type


# ---------------------------------------------------------------------------
# energy ledger
# ---------------------------------------------------------------------------

def test_ledger_integration_left_hold():
    led = EnergyLedger()
    led.tick(0.0, total_w=100.0, net_w=40.0, proc_w=60.0)
    led.tick(1.0, total_w=50.0, net_w=20.0, proc_w=30.0)
    out = led.integrate(t_end=3.0)
    # 100 W for 1 h + 50 W for 2 h = 200 Wh = 720 kJ
    assert out["joules_total"] == pytest.approx(200.0 * 3600)
    assert out["joules_net"] == pytest.approx(80.0 * 3600)
    assert out["joules_proc"] == pytest.approx(120.0 * 3600)
    assert out["joules_net"] + out["joules_proc"] == \
        pytest.approx(out["joules_total"])


def test_ledger_tenant_attribution_exact(topo):
    """With per-commit attribution, every sample's tenant watts sum to
    the sample total EXACTLY (attribute_power's conservation), and the
    integrated per-tenant joules sum to the total joules."""
    tel = Telemetry(attribution_every=1)
    sess = _churn(CFNSession(topo, _spec(), telemetry=tel))
    assert tel.ledger.samples
    for s in tel.ledger.samples:
        assert "tenant_w" in s
        # tenant split is f64-exact; the sample total is the engine's f32
        # breakdown, so they agree to f32 precision
        assert sum(s["tenant_w"].values()) == pytest.approx(
            s["total_w"], rel=1e-6)
        assert s["net_w"] + s["proc_w"] == pytest.approx(
            s["total_w"], rel=1e-6)
    # cross-check the LAST sample against a fresh attribute_power call
    eng = sess.engine
    per = eng.per_service_power_w()
    last = tel.ledger.samples[-1]["tenant_w"]
    assert set(last) == {str(s) for s in per}
    for sid, w in per.items():
        assert last[str(sid)] == pytest.approx(w, rel=1e-9)
    out = tel.ledger.integrate()
    assert sum(out["joules_by_tenant"].values()) == pytest.approx(
        out["joules_total"], rel=1e-6)
    # per-tier proc watts decompose the Eq.(2) term
    tier = tel.ledger.samples[-1]["tier_w"]
    assert set(tier) == set(tiers_of(topo))
    assert sum(tier.values()) == pytest.approx(
        tel.ledger.samples[-1]["proc_w"], rel=1e-6)


def test_federated_ledger_regions_sum_exact():
    topo = topology.federated_scale(n_regions=3, n_olt=1, onus_per_olt=2,
                                    iot_per_onu=2, n_core=6)
    part = federation.RegionPartition.from_topology(topo)
    srcs = [int(r.proc_ids[0]) for r in part.regions]
    tel = Telemetry()
    sess = FederatedSession(topo, _spec(), telemetry=tel)
    sess.solve(vsr.random_vsrs(6, rng=1, n_vms=3, source_nodes=srcs))
    sess.tick(1.0)
    sess.add(vsr.random_vsrs(1, rng=9, n_vms=3, source_nodes=[srcs[1]]))
    assert tel.ledger.samples
    for s in tel.ledger.samples:
        assert sum(s["region_w"].values()) == pytest.approx(
            s["total_w"], rel=1e-9)
        assert s["net_w"] + s["proc_w"] == pytest.approx(
            s["total_w"], rel=1e-9)
    bd = sess.breakdown()
    last = tel.ledger.samples[-1]
    assert last["total_w"] == pytest.approx(bd.total_w, rel=1e-12)
    for g, w in enumerate(np.asarray(bd.regional_w)):
        assert last["region_w"][str(g)] == pytest.approx(float(w))


# ---------------------------------------------------------------------------
# convergence traces
# ---------------------------------------------------------------------------

def test_convergence_trace_fixed_length(topo):
    vs = vsr.random_vsrs(4, rng=0, n_vms=3)
    prob = power.build_problem(topo, vs)
    import jax
    X0 = np.zeros((prob.R, prob.V), np.int32)
    res = solvers.anneal(prob, jax.random.PRNGKey(0), X0, n_steps=64,
                         backend="delta", record_conv=True)
    assert set(res.conv) == {"best_obj", "accept_rate"}
    assert len(res.conv["best_obj"]) == 64
    assert len(res.conv["accept_rate"]) == 64
    bo = np.asarray(res.conv["best_obj"])
    assert (np.diff(bo) <= 1e-6).all()        # best objective is monotone
    ar = np.asarray(res.conv["accept_rate"])
    assert (ar >= 0).all() and (ar <= 1).all()
    # flag off -> no trace, and the jit cache key-space is UNTOUCHED
    before = dict(solvers.TRACE_COUNTS)
    res2 = solvers.anneal(prob, jax.random.PRNGKey(0), X0, n_steps=64,
                          backend="delta")
    assert res2.conv is None
    assert dict(solvers.TRACE_COUNTS) == before


def test_commit_records_convergence(topo):
    tel = Telemetry()
    sess = CFNSession(topo, _spec(), telemetry=tel)
    sess.add(_svc(0))
    sess.add(_svc(1))
    solves = [e for e in tel.events if e["type"] == "solve"]
    assert any("conv" in e for e in solves)
    ev = next(e for e in solves if "conv" in e)
    assert len(ev["conv"]["best_obj"]) <= 64  # downsampled payload bound


# ---------------------------------------------------------------------------
# monitor delegation
# ---------------------------------------------------------------------------

def test_monitor_mirror_parity():
    plain, tel = PlacementMonitor(), Telemetry()
    mirrored = PlacementMonitor()
    mirrored.attach_telemetry(tel)
    for mon in (plain, mirrored):
        for _ in range(3):
            mon.count("admission_rejected", detail="sla")
        mon.count("node_failed", n=2)
        mon.strand(7, t=1.0)
        mon.unstrand(7, t=3.5)
    assert mirrored.snapshot() == plain.snapshot()   # standalone unchanged
    assert mirrored.events == plain.events
    assert tel.counters["monitor.admission_rejected"] == 3
    assert tel.counters["monitor.node_failed"] == 2
    assert tel.gauges["monitor.stranded_open"] == 0
    assert tel.gauges["monitor.stranded_service_s"] == pytest.approx(2.5)


def test_monitor_ring_bound_unchanged_with_telemetry():
    tel = Telemetry()
    mon = PlacementMonitor(max_events=4)
    mon.attach_telemetry(tel)
    for i in range(10):
        mon.count("k", detail=str(i))
    assert len(mon.events) == 4
    assert mon.counters["k"] == 10 and tel.counters["monitor.k"] == 10


def test_monitor_merge_no_double_count():
    tel = Telemetry()
    a, b = PlacementMonitor(), PlacementMonitor()
    a.attach_telemetry(tel)
    b.attach_telemetry(tel)        # same registry: counts already there
    a.count("x")
    b.count("x")
    a.merge(b)
    assert a.counters["x"] == 2
    assert tel.counters["monitor.x"] == 2    # merge did NOT re-count
    c = PlacementMonitor()         # un-mirrored: merge must fold it in
    c.count("x", n=3)
    a.merge(c)
    assert a.counters["x"] == 5 and tel.counters["monitor.x"] == 5


# ---------------------------------------------------------------------------
# compile attribution
# ---------------------------------------------------------------------------

def test_compile_attribution_agrees(topo):
    tel = Telemetry()
    # unique shape (n_vms=5) so this scenario really compiles fresh
    sess = CFNSession(topo, _spec(), telemetry=tel)
    sess.add(_svc(0, n_vms=5))
    sess.add(_svc(1, n_vms=5))
    rep = tel.report()
    assert rep["compiles"]["agree"] is True
    assert rep["compiles"]["recorded"] == rep["compiles"]["live"]
    for rec in tel.compile_attribution():
        assert rec["entry"] in solvers.TRACE_COUNTS
        assert "[" in rec["fingerprint"]      # carries abstract shapes
    tel.close()                               # detaches the hook
    assert tel._trace_hook is None
    assert not solvers.TRACE_HOOKS or tel._trace_hook not in \
        solvers.TRACE_HOOKS


# ---------------------------------------------------------------------------
# the package lints clean
# ---------------------------------------------------------------------------

def test_telemetry_package_tracelint_clean():
    from repro.analysis import analyze_paths
    findings = analyze_paths([str(REPO / "src" / "repro" / "telemetry")])
    assert findings == [], [f"{f.rule}:{f.path}:{f.line}" for f in findings]


def test_report_cli_roundtrip(tmp_path):
    import subprocess
    import sys
    path = tmp_path / "cli.jsonl"
    with Telemetry(jsonl_path=str(path)) as tel:
        with tel.span("work"):
            pass
        tel.ledger.tick(0.0, total_w=5.0, net_w=2.0, proc_w=3.0)
    env_path = str(REPO / "src")
    for args in (["validate", str(path)], ["report", str(path), "--json"]):
        out = subprocess.run(
            [sys.executable, "-m", "repro.telemetry", *args],
            capture_output=True, text=True,
            env={"PYTHONPATH": env_path, "PATH": "/usr/bin:/bin"})
        assert out.returncode == 0, out.stderr
    rep = json.loads(subprocess.run(
        [sys.executable, "-m", "repro.telemetry", "report", str(path),
         "--json"], capture_output=True, text=True,
        env={"PYTHONPATH": env_path, "PATH": "/usr/bin:/bin"}).stdout)
    assert rep["spans"]["work"]["count"] == 1
