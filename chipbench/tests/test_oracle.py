"""The benchmark's reference against the program's own f64 oracle, on the
program's own problem, at CPU sizes, for chains and for DAG services with
a zero-demand VM; and its substrate against the program's."""
import numpy as np
import pytest

import gen
import oracle
import run

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


def _dag(rng, n, sources):
    """Diamonds: VM 0 feeds VMs 1 and 2, both feed VM 3; VM 4 is zero
    padding, with no demand and no link."""
    F = np.zeros((n, 5), np.float32)
    F[:, 0] = rng.uniform(0.1, 1.0, size=n)
    F[:, 1:4] = rng.uniform(3.0, 10.0, size=(n, 3))
    H = np.zeros((n, 5, 5), np.float32)
    for a, b in ((0, 1), (0, 2), (1, 3), (2, 3)):
        H[:, a, b] = rng.uniform(5.0, 50.0, size=n)
    src = np.asarray(rng.choice(np.asarray(sources), size=n), np.int32)
    return gen.Services(F=F, H=H, src=src)


def _engine(preset, args, n, seed, sources_frac=0.25, dag=False):
    """A bootstrapped engine on the program's normal path, and the
    benchmark's own record of its services."""
    import jax
    from repro.api import CFNSession, PlacementSpec
    from repro.core import topology, vsr
    topo = getattr(topology, preset)(**args)
    iot = topo.layer_indices("iot")
    sources = iot[:max(1, int(len(iot) * sources_frac))]
    rng = np.random.default_rng(seed)
    pool = (_dag(rng, n, sources) if dag else
            run.load("fleets", "paper_chains").services(rng, n, sources))
    s = CFNSession(topo, PlacementSpec(effort="quick", anneal_steps=0),
                   key=jax.random.PRNGKey(seed))
    s.engine.bootstrap([vsr.VSRBatch(F=pool.F[i:i + 1], H=pool.H[i:i + 1],
                                     src=pool.src[i:i + 1],
                                     input_vm=np.zeros(1, np.int32))
                        for i in range(n)])
    return topo, s, pool


@pytest.mark.parametrize("preset,args,n,dag", [(*PRESETS[0], 20, False),
                                               (*PRESETS[1], 12, False),
                                               (*PRESETS[1], 12, True)])
def test_objective_matches_program_oracle(preset, args, n, dag):
    from repro.kernels import ref
    topo, s, pool = _engine(preset, args, n, seed=3, dag=dag)
    X = s.X
    V = pool.F.shape[1]
    want = ref.placement_objective_f64(s.problem, X)
    sub = oracle.build_substrate(preset, args)
    rows = np.asarray(s.sids)
    got = oracle.objective(sub, pool.F[rows], pool.H[rows], pool.src[rows],
                           X[:len(rows), :V])
    assert got["objective"] == pytest.approx(want, rel=1e-6)
    assert got["objective"] == pytest.approx(s.objective(), rel=1e-5)
    # a random placement of the same services too
    Xr = np.random.default_rng(0).integers(0, topo.P, size=X.shape)
    Xr[:len(rows), 0] = pool.src[rows]
    Xr[len(rows):] = np.asarray(s.problem.fixed_node)[len(rows):]
    want = ref.placement_objective_f64(s.problem, Xr)
    got = oracle.objective(sub, pool.F[rows], pool.H[rows], pool.src[rows],
                           Xr[:len(rows), :V])
    assert got["objective"] == pytest.approx(want, rel=1e-6)
    if dag:
        # the zero-demand VM moves nowhere that changes the objective
        Xz = Xr[:len(rows), :V].copy()
        Xz[:, 4] = (Xz[:, 4] + 7) % topo.P
        moved = oracle.objective(sub, pool.F[rows], pool.H[rows],
                                 pool.src[rows], Xz)
        assert moved["objective"] == got["objective"]
        Xp = Xr.copy()
        Xp[:len(rows), 4] = Xz[:, 4]
        assert ref.placement_objective_f64(s.problem, Xp) == \
            pytest.approx(want, rel=1e-12)


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
