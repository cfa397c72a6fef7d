"""The trace reduction: interval arithmetic, and a small trace recorded on
a TPU v5e (the last 0.06 s of a 1 s ``paper.steady`` window, committed
beside this file)."""
from pathlib import Path

import numpy as np
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
