"""The trace reduction: interval arithmetic, a small trace recorded on a
TPU v5e (the last 0.06 s of a 1 s ``paper.steady`` window, committed
beside this file), the program's spans kept from a trace recorded here,
and the idle labels and phase readers over a synthetic trace."""
from pathlib import Path

import numpy as np
import pytest
import xplane

TRACE = Path(__file__).resolve().parent / "data" / "paper_tiny.xplane.pb.gz"
SPANS = ("chipbench.window", "chipbench.apply_wave", "chipbench.defrag_tick",
         "chipbench.wait_due")


def test_merge_covered_gaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [12, 13]], float)
    m = xplane.merge(iv)
    np.testing.assert_array_equal(m, [[0, 3], [5, 9], [12, 13]])
    assert xplane.covered(m, 0, 20) == 8
    assert xplane.covered(m, 2, 6) == 2
    assert xplane.covered(m, 10, 11) == 0
    np.testing.assert_array_equal(xplane.gaps(m, 1, 14),
                                  [[3, 5], [9, 12], [13, 14]])
    np.testing.assert_array_equal(xplane.gaps(m, 10, 11), [[10, 11]])
    assert xplane.merge(np.zeros((0, 2))).shape == (0, 2)


def test_recorded_tpu_trace():
    red = xplane.reduce(str(TRACE), SPANS)
    assert red.n_devices == 1
    a, b = red.window("chipbench.window")
    assert b > a
    waves = red.spans["chipbench.apply_wave"]
    assert waves and all(a <= w0 < w1 <= b for w0, w1 in waves)
    busy = red.busy_in(a, b)
    assert 0 < busy < b - a
    # device work lies on the host annotations' clock: most of it inside
    # the waves
    assert sum(red.busy_in(w0, w1) for w0, w1 in waves) > 0.5 * busy
    assert red.programs and red.kernels
    assert max(red.programs.values()) > 0


def test_reduce_keeps_program_spans(tmp_path):
    """A trace recorded here with the program's telemetry: every
    ``repro.*`` span is kept beside the harness's annotations, nested as
    opened, and nothing else of the host."""
    import jax
    from repro.telemetry import Telemetry, span
    tel = Telemetry(convergence=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(3):
                with span(tel, "apply_wave"), span(tel, "wave.rebuild"):
                    jax.numpy.ones(8).block_until_ready()
            with jax.profiler.TraceAnnotation("other.annotation"):
                pass
    finally:
        jax.profiler.stop_trace()
    red = xplane.reduce(xplane.find_xplane(str(tmp_path)), SPANS)
    assert len(red.spans["chipbench.window"]) == 1
    outer, inner = (red.spans["repro.apply_wave"],
                    red.spans["repro.wave.rebuild"])
    assert len(outer) == len(inner) == 3
    for (a, b), (c, d) in zip(outer, inner):
        assert a <= c <= d <= b
    assert "other.annotation" not in red.spans
    assert not red.spans["chipbench.apply_wave"]


def _synthetic():
    """Two waves on one device, the program's spans inside the first."""
    spans = {"chipbench.window": [(0.0, 100.0)],
             "chipbench.apply_wave": [(10.0, 50.0), (60.0, 90.0)],
             "chipbench.wait_due": [(50.0, 60.0)],
             "chipbench.defrag_tick": [],
             "repro.apply_wave": [(11.0, 49.0)],
             "repro.wave.rebuild": [(12.0, 20.0)],
             "repro.wave.warm_state": [(20.0, 30.0)],
             "repro.resolve_wave": [(30.0, 45.0)],
             "repro.wave.full_solve": [(45.0, 48.0), (62.0, 70.0)]}
    # idle: [5, 8], [13, 15], [22, 26], [33, 34], [46, 47], [48.2, 48.8],
    # [52, 58], [61, 61.5], [63, 65], [92, 99]
    busy = xplane.merge(np.array([
        [0, 5], [8, 13], [15, 22], [26, 33], [34, 46], [47, 48.2],
        [48.8, 52], [58, 61], [61.5, 63], [65, 92], [99, 100]], float))
    return xplane.Reduced(busy_per_device=[busy], kernels=[], programs={},
                          spans=spans)


def test_breakdown_labels_gaps_by_innermost_program_span():
    import run
    out = run.breakdown(_synthetic(), (0.0, 100.0))
    labels = {e[0].split(":")[0]: e[1] for e in out["idle_gaps"]}
    assert labels == pytest.approx({
        "loop bookkeeping between waves": 10e-9,
        "repro.wave.rebuild": 2e-9,
        "repro.wave.warm_state": 4e-9,
        "repro.resolve_wave": 1e-9,
        "repro.wave.full_solve": 3e-9,
        "repro.apply_wave": 0.6e-9,
        "waiting for a due event": 6e-9,
        "engine host work inside apply_wave": 0.5e-9}, rel=1e-9)


@pytest.mark.parametrize("name,want", [
    ("rebuild_commit_host_ms", 1e-6 * (2 + 4) / 2),
    ("resolve_host_ms", 1e-6 * 1 / 2),
    ("full_solve_ms", 1e-6 * (3 + 8) / 2)])
def test_phase_readers(name, want):
    """Each reads the synthetic spans as its layer's host or wall time, and
    nothing where the trace holds no program span, as the recorded trace
    (no telemetry attached) does not."""
    from types import SimpleNamespace

    import run
    read = run.reader(f"{name}.steady")
    assert read(SimpleNamespace(trace=_synthetic())) == pytest.approx(want)
    assert read(SimpleNamespace(trace=xplane.reduce(str(TRACE), SPANS))) \
        is None
    assert read(SimpleNamespace(trace=None)) is None
