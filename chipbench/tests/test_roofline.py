"""The peaks table and the fused anneal kernel's operation and byte
count."""
import pytest

import roofline


def test_v5e_peaks_and_unknown_device():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9
    assert "TPU v5e" in pk["source"]
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_fused_anneal_cost_at_paper_shapes():
    P, N, J, D, C, T = 23, 13, 128, 2, 32, 4000
    flops, nbytes = roofline.fused_anneal_cost(P, N, J, D, C, T)
    assert flops == (2 * 529 * 13 + 2 * 4 * 529) * 32 * 4000
    words = (3 * C * T + T + 2 * C * J + C * (2 * P + N + 1) + 2 * C
             + P * P * N + 3 * J * D + J + 9 * P + 5 * N)
    assert nbytes == 4 * words
    t, bound = roofline.least_seconds(flops, nbytes, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(flops / 197e12)


def test_fused_roofline_reader_on_recorded_trace():
    """One launch in the recorded trace: its shapes come from its event, and
    its share is the least time of one launch over its device time."""
    import importlib.util
    from pathlib import Path
    from types import SimpleNamespace

    import xplane
    here = Path(__file__).resolve().parent
    red = xplane.reduce(str(here / "data" / "paper_tiny.xplane.pb.gz"),
                        ("chipbench.window",))
    launches = [(n, b - a) for n, a, b in red.kernels
                if n.startswith("%fused_anneal")]
    assert len(launches) == 1
    # 32 chains over 20 services padded to 32 rows of 4 VMs; 4000 steps
    # travel as 32 chunks of 128, of which the count takes the fewest
    shape = roofline.launch_shape(launches[0][0])
    assert shape == dict(P=23, N=13, J=128, D=2, C=32, T=31 * 128 + 1)
    spec = importlib.util.spec_from_file_location(
        "m", here.parent / "metrics" / "fused_anneal_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    run = SimpleNamespace(trace=red, device_kind="TPU v5 lite")
    least, _ = roofline.least_seconds(*roofline.fused_anneal_cost(**shape),
                                      "TPU v5 lite")
    share = mod.read(run)
    assert share == pytest.approx(100 * least / (launches[0][1] * 1e-9))
    assert 0 < share < 100
    red.kernels = [k for k in red.kernels
                   if not k[0].startswith("%fused_anneal")]
    assert mod.read(run) is None
