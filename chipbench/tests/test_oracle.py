"""The benchmark's reference against the program's own f64 oracle, on the
program's own problem, at CPU sizes; and its substrate against the
program's."""
import numpy as np
import pytest

import gen
import oracle

PRESETS = [("paper_topology", {"n_iot": 20, "n_zones": 4}),
           ("paper_topology", {"n_iot": 6, "n_zones": 2})]


@pytest.mark.parametrize("preset,args", PRESETS)
def test_substrate_matches_program(preset, args):
    from repro.core import topology
    topo = getattr(topology, preset)(**args)
    sub = oracle.build_substrate(preset, args)
    assert sub.proc_names == topo.proc_names
    assert sub.proc_layer == topo.proc_layer
    np.testing.assert_array_equal(sub.route_idx, topo.route_idx)
    for k, v in topo.proc_param_arrays().items():
        np.testing.assert_allclose(sub.pp[k], v, rtol=1e-6)
    for k, v in topo.net_param_arrays().items():
        np.testing.assert_allclose(sub.nn[k], v, rtol=1e-6)


def _engine(preset, args, n, seed, sources_frac=0.25):
    """A bootstrapped engine on the program's normal path, and the
    benchmark's own record of its services."""
    import jax
    from repro.api import CFNSession, PlacementSpec
    from repro.core import topology, vsr
    topo = getattr(topology, preset)(**args)
    iot = topo.layer_indices("iot")
    pool = gen.services(np.random.default_rng(seed), n, 3,
                        iot[:max(1, int(len(iot) * sources_frac))])
    s = CFNSession(topo, PlacementSpec(effort="quick", anneal_steps=0),
                   key=jax.random.PRNGKey(seed))
    s.engine.bootstrap([vsr.VSRBatch(F=pool.F[i:i + 1], H=pool.H[i:i + 1],
                                     src=pool.src[i:i + 1],
                                     input_vm=np.zeros(1, np.int32))
                        for i in range(n)])
    return topo, s, pool


@pytest.mark.parametrize("preset,args,n", [(*PRESETS[0], 20),
                                           (*PRESETS[1], 12)])
def test_objective_matches_program_oracle(preset, args, n):
    from repro.kernels import ref
    topo, s, pool = _engine(preset, args, n, seed=3)
    X = s.X
    want = ref.placement_objective_f64(s.problem, X)
    sub = oracle.build_substrate(preset, args)
    rows = np.asarray(s.sids)
    got = oracle.objective(sub, pool.F[rows], pool.H[rows], pool.src[rows],
                           X[:len(rows), :3])
    assert got["objective"] == pytest.approx(want, rel=1e-6)
    assert got["objective"] == pytest.approx(s.objective(), rel=1e-5)
    # a random placement of the same services too
    Xr = np.random.default_rng(0).integers(0, topo.P, size=X.shape)
    Xr[:len(rows), 0] = pool.src[rows]
    Xr[len(rows):] = np.asarray(s.problem.fixed_node)[len(rows):]
    want = ref.placement_objective_f64(s.problem, Xr)
    got = oracle.objective(sub, pool.F[rows], pool.H[rows], pool.src[rows],
                           Xr[:len(rows), :3])
    assert got["objective"] == pytest.approx(want, rel=1e-6)


def test_cdc_placement_matches_program_baseline():
    from repro.core import solvers
    topo, s, pool = _engine(*PRESETS[1], 12, seed=5)
    base = solvers.fixed_layer(s.problem, topo, "cdc")
    sub = oracle.build_substrate(*PRESETS[1])
    rows = np.asarray(s.sids)
    X = oracle.cdc_placement(sub, pool.F[rows], pool.src[rows])
    np.testing.assert_array_equal(X, base.X[:len(rows), :3])


def test_bf16_control_reads_far_above_float32():
    topo, s, pool = _engine(*PRESETS[1], 12, seed=7)
    sub = oracle.build_substrate(*PRESETS[1])
    rows = np.asarray(s.sids)
    args = (sub, pool.F[rows], pool.H[rows], pool.src[rows],
            s.X[:len(rows), :3])
    ref = oracle.objective(*args)["objective"]
    low = oracle.objective(*args, cast=oracle.bf16)["objective"]
    f32_gap = abs(s.objective() - ref) / ref
    bf16_gap = abs(low - ref) / ref
    assert f32_gap < 1e-6 < 1e-4 < bf16_gap
