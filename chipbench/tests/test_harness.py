"""The harness end to end on the CPU at a light load, with the look for a
chip skipped: a sound run is correct, and a run whose timed path is broken
underneath is not, for each fault a placement cell can have.  The exchange
between chips is not among them: every cell runs on one chip."""
import contextlib
import json
import os
import subprocess
import sys
from pathlib import Path
from unittest import mock

import pytest

import control
import run

ROOT = Path(__file__).resolve().parents[2]


def _tiny():
    """``paper.steady`` at 20 events/s, with a full solve every 4 events
    and its anneal on the fused kernel (in interpret mode off the chip)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"]
                if w["name"] == "paper.steady")
    cfg = json.loads((ROOT / "chipbench/configs/paper.json").read_text())
    cfg["spec"] = dict(cfg["spec"], backend="fused", defrag_every=4)
    traffic = json.loads(
        (ROOT / "chipbench/traffic/paper_steady.json").read_text())
    traffic.update(rate_events_per_s=20.0, wave_cap_events=8)
    return bench, cell, cfg, traffic


def _run(hook=None, seconds=3.0, seed=2 ** 32 + 6, ticks=False,
         defrag_every=None):
    bench, cell, cfg, traffic = _tiny()
    if defrag_every is not None:
        cfg["spec"]["defrag_every"] = defrag_every
    if ticks:
        # the paths that no cell takes yet: a greedy start with no solve,
        # and a defrag tick of 4 rows after every 8 events in place of the
        # full solves
        cfg.update(bootstrap="greedy", greedy_hosts=["af", "mf", "cdc"])
        cfg["spec"].update(defrag_every=0, defrag_rows_per_tick=4)
        traffic["defrag_tick_every_events"] = 8
    return run.run_cell(bench, cell, cfg, traffic, seed, seconds, False,
                        require_tpu=False, session_hook=hook)


@pytest.mark.parametrize("ticks", [False, True])
def test_sound_run_is_correct(ticks):
    out = _run(ticks=ticks)
    assert out["correct"] is True
    assert out["attempted"] == 60 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(run.CHECK_NAMES)
    assert (out["info"]["fused_commits"] > 0) is not ticks
    for m in ("event_p50_ms", "events_per_s", "watts_vs_cdc", "setup_s"):
        assert out["metrics"][m]["value"] > 0


def _unchanged(s, stack):
    """A step that returns its state unchanged (and claims the arrivals)."""
    from repro.core import dynamic

    def apply_wave(arrivals=(), departures=()):
        return dynamic.WaveResult(result=s.result,
                                  sids=[a[1] for a in arrivals],
                                  departed=list(departures),
                                  admitted=[a[1] for a in arrivals])
    s.apply_wave = apply_wave


def _half(s, stack):
    """Half of each wave left out: only the first half of its arrivals and
    departures reach the engine; the rest are claimed done."""
    real = s.apply_wave

    def apply_wave(arrivals=(), departures=()):
        arrivals, departures = list(arrivals), list(departures)
        wr = real(arrivals[:max(1, len(arrivals) // 2)],
                  departures[:max(1, len(departures) // 2)])
        wr.admitted = [a[1] for a in arrivals]
        return wr
    s.apply_wave = apply_wave


def _altered_objective(s, stack):
    """The committed objective altered where it is produced, by 0.1%."""
    eng = s.engine
    real = eng._commit

    def commit(res, event):
        real(res, event)
        bd = eng._result.breakdown
        eng._result.breakdown = bd._replace(total=bd.total * 1.001)
    eng._commit = commit


def _moved_input_vm(s, stack):
    """A committed placement altered where it is produced: one input VM off
    its source node."""
    eng = s.engine
    real = eng._commit

    def commit(res, event):
        real(res, event)
        eng._X = eng._X.copy()
        eng._X[0, 0] = (eng._X[0, 0] + 1) % eng.problem.P
    eng._commit = commit


def _sweep_returns_input(s, stack):
    """The delta engine's sweep returns the state it was given: waves keep
    whatever the anneal alone found, and nothing re-packs the survivors."""
    from repro.core import solvers
    stack.enter_context(mock.patch.object(
        solvers, "_sweep", lambda problem, aux, state, positions,
        eligible=None: (state, state.obj)))


# the sweep's fault shows only in the wave commits, where it leaves VMs a
# move worth more than the check's threshold: it is read at the cell's own
# full-solve cadence, over a window that holds some thirty waves (the few
# of a shorter one read from 0 to 1, by seed)
_sweep_returns_input.run = dict(seconds=10.0, defrag_every=16)


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered_objective,
                                   _moved_input_vm, _sweep_returns_input])
def test_broken_timed_path_is_not_correct(fault):
    with contextlib.ExitStack() as stack:
        out = _run(lambda s: fault(s, stack), **getattr(fault, "run", {}))
    assert out["correct"] is False


def test_kernel_on_bfloat16_data_shows_in_fused_gap():
    """The fused anneal kernel fed bfloat16 data: its own best objective
    leaves the reference's on the full solve it wins on this seed (one of
    the window's ten), far past what a sound run reads, and the run is not
    correct."""
    sound = _run()
    with control.bf16_kernel():
        low = _run()
    assert low["info"]["fused_commits"] > 0
    assert sound["checks"]["fused_gap"]["value"] < 1e-3 < 1e-2 \
        < low["checks"]["fused_gap"]["value"]
    assert sound["correct"] is True and low["correct"] is False


def test_control_fails_the_checks():
    """The reference in the program's place at bfloat16 reads past the
    objective and polish limits, on the cell as configured."""
    bench, cell, cfg, traffic = run.load_cell(ROOT, "paper.steady")
    run.init_jax()
    r, checks, _, _ = run.serve(cfg, traffic, 11, 3.0, None, "cpu")
    ctrl = run.check(cfg, r.pool, r.commits, 3.0, r.check_seed,
                     control=True)
    for k in ("objective_gap", "unpolished_share"):
        assert checks[k] <= cfg["limits"][k] < ctrl[k], k


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "paper.steady", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_bare_directory_no_result(tmp_path):
    (tmp_path / "chipbench").mkdir()
    for f in ("BENCHMARK.json",):
        (tmp_path / f).write_text((ROOT / f).read_text())
    for f in (ROOT / "chipbench").rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts:
            dst = tmp_path / f.relative_to(ROOT)
            dst.parent.mkdir(parents=True, exist_ok=True)
            dst.write_bytes(f.read_bytes())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "paper.steady", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def _churn_tiny():
    """The mix ``paper_poisson_churn`` on ``paper`` at 5 arrivals/s with 4 s
    lifetimes (still ~20 live), a full solve every 4 events."""
    bench, cell, cfg, _ = run.load_cell(ROOT, "paper.steady")
    cfg["spec"] = dict(cfg["spec"], defrag_every=4)
    traffic = json.loads(
        (ROOT / "chipbench/traffic/paper_poisson_churn.json").read_text())
    traffic.update(arrivals_per_s=5.0, mean_lifetime_s=4.0,
                   wave_cap_events=8)
    return bench, cell, cfg, traffic


@pytest.mark.parametrize("refuse", [False, True])
def test_churn_run(refuse):
    """A sound churn run is correct.  An arrival refused at admission fails
    the run, and its departure, due later in the window, is dropped and
    counted, not handed to the engine."""
    bench, cell, cfg, traffic = _churn_tiny()
    seed, seconds = 2 ** 32 + 21, 3.0
    p = run.plan(cfg, traffic, seed, seconds)
    departs = {e.victim for e in p.events if e.victim is not None}
    victim = next(e.sid for e in p.events if e.sid in departs)

    def hook(s):
        real = s.apply_wave

        def apply_wave(arrivals=(), departures=()):
            assert victim not in departures
            keep = [a for a in arrivals if a[1] != victim]
            wr = real(keep, departures) if keep or departures else \
                mock.Mock(admitted=[])
            return wr
        s.apply_wave = apply_wave

    out = run.run_cell(bench, cell, cfg, traffic, seed, seconds, False,
                       require_tpu=False, session_hook=hook if refuse
                       else None)
    assert out["info"]["live_range"][0] < 20 < out["info"]["live_range"][1]
    if refuse:
        assert out["failed"] == 1 and out["correct"] is False
        assert out["info"]["departures_dropped"] == 1
        assert out["checks"]["bookkeeping_errors"]["value"] == 0
    else:
        assert out["correct"] is True and out["failed"] == 0
        assert out["info"]["departures_dropped"] == 0
        assert out["info"]["compiled_in_window"]


# a deployment that the benchmark did not have: services that are diamonds
# with a zero-demand VM of padding, and a stream of replace pairs at even
# gaps in which the oldest service leaves
DIAMONDS = '''
import numpy as np
import gen


def services(rng, n, sources, pad=1):
    V = 4 + pad
    F = np.zeros((n, V), np.float32)
    F[:, 0] = rng.uniform(0.1, 1.0, size=n)
    F[:, 1:4] = rng.uniform(3.0, 10.0, size=(n, 3))
    H = np.zeros((n, V, V), np.float32)
    for a, b in ((0, 1), (0, 2), (1, 3), (2, 3)):
        H[:, a, b] = rng.uniform(5.0, 50.0, size=n)
    src = np.asarray(rng.choice(np.asarray(sources), size=n), np.int32)
    return gen.Services(F=F, H=H, src=src)
'''
FIFO = '''
import gen


def events(rng, seconds, live, first_sid, pairs_per_s, **_):
    n = int(pairs_per_s * seconds)
    live = sorted(live)
    return [gen.Event((k + 0.5) * seconds / n, victim=live[k] if k <
                      len(live) else first_sid + k - len(live),
                      sid=first_sid + k) for k in range(n)]


def warm_plan(rng, live, first_sid, cap_events, counts, **_):
    live, waves, k = sorted(live), [], 1
    while k <= min(cap_events // 2, len(live)):
        waves.append((list(range(first_sid, first_sid + k)), live[:k]))
        live = live[k:] + waves[-1][0]
        first_sid += k
        k *= 2
    return waves
'''


def test_new_deployment_is_new_files_only(tmp_path):
    """A fleet generator, a stream, a configuration and a mix added as new
    files beside a copy of the benchmark run as a cell, with no edit to a
    file the benchmark has."""
    bench_dir = tmp_path / "chipbench"
    for f in (ROOT / "chipbench").rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts:
            dst = tmp_path / f.relative_to(ROOT)
            dst.parent.mkdir(parents=True, exist_ok=True)
            dst.write_bytes(f.read_bytes())
    (bench_dir / "fleets" / "diamonds.py").write_text(DIAMONDS)
    (bench_dir / "streams" / "fifo_pairs.py").write_text(FIFO)
    cfg = json.loads((ROOT / "chipbench/configs/paper.json").read_text())
    cfg["fleet"].update(live=12, generator="diamonds", args={"pad": 1})
    cfg["spec"] = {"defrag_every": 4}
    (bench_dir / "configs" / "diamonds.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "fifo.json").write_text(json.dumps(
        {"kind": "fifo_pairs", "pairs_per_s": 8, "wave_cap_events": 8}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="diamonds",
                                 file="chipbench/configs/diamonds.json"))
    bench["workloads"].append({"name": "diamonds.fifo", "config": "diamonds",
                               "traffic": "fifo", "chips": 1, "why": "-"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with mock.patch.object(run, "HERE", bench_dir):
        bench, cell, cfg, traffic = run.load_cell(tmp_path, "diamonds.fifo")
        p = run.plan(cfg, traffic, 5, 3.0)
        assert p.pool.F.shape[1:] == (5,) and not p.pool.F[:, 4].any()
        assert len(p.events) == 24 and len(p.warm) == 3
        out = run.run_cell(bench, cell, cfg, traffic, 5, 3.0, False,
                           require_tpu=False)
    assert out["correct"] is True
    assert out["attempted"] == 48 and out["failed"] == 0
    assert out["info"]["live_range"] == [12, 12]
