"""Multi-device semantics tests (run in a subprocess with 8 host devices so
the main test process keeps its single-device view).

Covers: production-mesh construction, sharded train_step numerics vs the
single-device step, int8-compressed pod gradient sync, and elastic
checkpoint re-shard onto a different mesh shape.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch import mesh as mesh_mod
    from repro.launch import specs as S
    from repro.optim import adamw
    from repro.parallel import sharding as sh
    from repro.serve import cache as C
    from repro.checkpoint import CheckpointStore
    from repro.train.step import init_state, make_train_step

    cfg = dataclasses.replace(configs.get_smoke("qwen3-4b"), n_layers=2)
    dcfg = DataConfig(seed=0, batch=8, seq_len=32)
    opt = adamw.AdamWConfig(lr=1e-3)
    batch = {k: jnp.asarray(v) for k, v in
             make_batch(cfg, dcfg, 0).items()}

    # 1) single-device reference
    state0, axes = init_state(cfg, jax.random.PRNGKey(0))
    step1 = jax.jit(make_train_step(cfg, opt))
    sref, mref = step1(state0, batch)
    loss_ref = float(mref["loss"])

    # 2) sharded (2, 2, 2) pod/data/model mesh
    mesh = mesh_mod.make_mesh((2, 2, 2), ("pod", "data", "model"))
    with sh.mesh_context(mesh):
        state0b, _ = init_state(cfg, jax.random.PRNGKey(0))
        state_sh = sh.shard_params(
            state0b, __import__("repro.launch.specs", fromlist=["x"])
            .train_state_specs(cfg)[1], mesh)
        step2 = jax.jit(make_train_step(cfg, opt),
                        in_shardings=(state_sh, None),
                        out_shardings=(state_sh, None))
        s2, m2 = step2(state0b, batch)
        loss_sh = float(m2["loss"])
    assert abs(loss_sh - loss_ref) < 5e-2, (loss_sh, loss_ref)
    print("SHARDED_STEP_OK", loss_ref, loss_sh)

    # 3) compressed pod sync step compiles + runs, loss close to reference
    with sh.mesh_context(mesh, rules={"batch": ("data",)}):
        st_c, _ = init_state(cfg, jax.random.PRNGKey(0), compress_pod=True)
        stepc = jax.jit(make_train_step(cfg, opt, compress_pod=True,
                                        mesh=mesh))
        sc, mc = stepc(st_c, batch)
        loss_c = float(mc["loss"])
    assert abs(loss_c - loss_ref) < 5e-2, (loss_c, loss_ref)
    print("COMPRESSED_STEP_OK", loss_c)

    # 4) elastic re-shard: save on (2,2,2), restore on (4, 2) mesh
    store = CheckpointStore("/tmp/elastic_ck")
    store.save(1, s2, extra=dict(data_step=1))
    store.wait()
    mesh2 = mesh_mod.make_mesh((4, 2), ("data", "model"))
    with sh.mesh_context(mesh2):
        like = jax.eval_shape(
            lambda: init_state(cfg, jax.random.PRNGKey(0))[0])
        sh_tree = sh.shard_params(
            like, __import__("repro.launch.specs", fromlist=["x"])
            .train_state_specs(cfg)[1], mesh2)
        restored, _ = store.restore(None, like, sh_tree)
        step3 = jax.jit(make_train_step(cfg, opt))
        s3, m3 = step3(restored, batch)
    # the re-sharded state continues training bit-compatibly
    s2b, m2b = step1(jax.device_get(s2), batch)
    assert abs(float(m3["loss"]) - float(m2b["loss"])) < 5e-3
    print("ELASTIC_OK", float(m3["loss"]), float(m2b["loss"]))

    # 5) decode on the mesh with sharded cache
    with sh.mesh_context(mesh):
        params_sds, axes2, batch_sds, extra, spec = S.serve_specs(
            cfg, 8, 64, "decode")
        csh = C.shardings(spec, mesh)
        print("CACHE_SHARDINGS_OK", len(jax.tree_util.tree_leaves(csh)))
    print("ALL_OK")
""")


@pytest.mark.slow
def test_multidevice_semantics(tmp_path):
    script = tmp_path / "md.py"
    script.write_text(SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    assert "ALL_OK" in res.stdout
