"""Compile the placement kernels for a TPU v5e that is described, not
attached: the chip's own compiler (Mosaic, then XLA:TPU) refuses here what
the chip would refuse -- unsupported layouts and primitives, and more VMEM
than the chip has -- which interpret mode never sees.

The topology is described inside a module-scoped fixture, never at import,
so every test worker collects the same tests and only the worker running
this file loads the TPU compiler.  Keep all such compiles in this file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import topology
from repro.kernels import placement_power as pp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _fused(one_chip, P, N, J=30, D=2, C=32, T=4000):
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    i32 = jnp.int32
    args = (S((C, J), i32), S((C, T), i32), S((C, T), i32), S((C, T)),
            S((T,)), S((J, D), i32), S((J, D)), S((J, D)),
            S((C, P)), S((C, P)), S((C, N)), S((C,)),
            S((J,)), S((P * P, N)), S((9, P)), S((5, N)))
    return jax.jit(pp.fused_anneal_tpu).lower(*args).compile()


def _full(one_chip, P, N, B=64, J=30, L=20):
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    args = (S((B, J), jnp.int32), S((L,), jnp.int32), S((L,), jnp.int32),
            S((J,)), S((L,)), S((P * P, N)), S((9, P)), S((5, N)))
    return jax.jit(pp.placement_power_tpu).lower(*args).compile()


def _paper_dims():
    t = topology.paper_topology()
    return t.P, t.N


def test_fused_anneal_compiles_at_paper_scale(one_chip, no_compile_cache):
    P, N = _paper_dims()
    assert pp.fused_fits(P, N, 30, 2, 4000)
    compiled = _fused(one_chip, P, N)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("corner", ["max_J", "max_D"])
def test_fused_anneal_compiles_at_largest_admitted_size(one_chip,
                                                         no_compile_cache,
                                                         corner):
    """The fit rule's corners compile: the largest P it admits with a
    128-lane network axis, at its step-count limit and at either end of
    its J*D limit."""
    if corner == "max_J":
        J = pp.FUSED_MAX_J
        D = pp.FUSED_MAX_JD // J
    else:
        D = pp.FUSED_MAX_D
        J = pp.FUSED_MAX_JD // D
    N, T = 120, pp.FUSED_MAX_T
    P = max(p for p in range(1, 512) if pp.fused_fits(p, N, J, D, T))
    assert not pp.fused_fits(P + 1, N, J, D, T)
    compiled = _fused(one_chip, P, N, J=J, D=D, T=T)
    assert "tpu_custom_call" in compiled.as_text()


def test_placement_power_compiles_at_paper_scale(one_chip, no_compile_cache):
    P, N = _paper_dims()
    compiled = _full(one_chip, P, N)
    assert "tpu_custom_call" in compiled.as_text()


def test_fit_rule_rejects_city_p468():
    """``city_scale`` at P=468 (K=14): the route table alone is 107 MiB,
    which the chip's VMEM cannot hold next to the kernel's working set."""
    t = topology.city_scale(n_olt=16, onus_per_olt=4, iot_per_onu=7)
    assert (t.P, t.K) == (468, 14)
    assert pp.route_table_bytes(t.P, t.N) > 100 * 2**20
    assert not pp.fused_fits(t.P, t.N, 1024 * 3, 2, 4000)
