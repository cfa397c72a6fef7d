"""Jit'd public wrappers for the Pallas kernels.

On a TPU the kernels compile through Mosaic; on any other backend they run
in Pallas interpret mode (the kernel body as jnp ops).  ``interpret=None``
picks by ``jax.default_backend()``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.power import (PlacementProblem, apply_pins, batched_hard_loads)
from . import flash_attention as fa
from . import placement_power as pp


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "logit_cap",
                                             "q_offset", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    logit_cap: Optional[float] = None, q_offset: int = 0,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Pallas flash attention; q [B, H, Sq, D], k/v [B, KH, Skv, D]."""
    interpret = _default_interpret() if interpret is None else interpret
    return fa.flash_attention_tpu(q, k, v, causal=causal, window=window,
                                  logit_cap=logit_cap, q_offset=q_offset,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def placement_objective(problem: PlacementProblem, Xb: jax.Array, *,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Batched placement objective: Xb [B, R, V] -> [B, 4].

    Columns: (objective = power + penalty*violation, net W, proc W,
    violation).  Matches kernels.ref.placement_objective_ref bit-for-bit up
    to float accumulation order.
    """
    interpret = _default_interpret() if interpret is None else interpret
    B = Xb.shape[0]
    Xp = jax.vmap(lambda X: apply_pins(problem, X))(Xb)
    Xflat = Xp.reshape(B, -1).astype(jnp.int32)
    operands = pp.pack_problem(problem)
    return pp.placement_power_tpu(Xflat, *operands, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_anneal(problem: PlacementProblem, aux, Xc: jax.Array,
                 j_prop: jax.Array, p_prop: jax.Array, u_prop: jax.Array,
                 temps: jax.Array, eligible: Optional[jax.Array] = None, *,
                 interpret: Optional[bool] = None):
    """Fused Metropolis annealing: whole chains in ONE kernel launch.

    Xc [C, R, V] int32 starting placements (pins applied by the caller);
    j_prop/p_prop/u_prop [C, T] proposals (flat free-VM index, destination
    node, uniform draw); temps [T]; aux = core.power.build_aux(problem).
    ``eligible`` [R, P] bool (optional) masks the proposal stream onto each
    service row's eligible node set (pp.mask_proposals) before it reaches
    the kernel -- the SLA constraint surface of repro.api.PlacementSpec
    enforced identically to the pure-JAX backends.
    Returns (best_X [C, R, V], stats [C, 2] = (best obj, final obj)).
    Chain state (placement + live load tensors) stays resident in VMEM
    across all T steps -- no per-step objective launch.  Initial loads are
    one batched evaluation out here.  Raises ``ValueError`` when the
    substrate is past ``pp.fused_fits``.
    """
    interpret = _default_interpret() if interpret is None else interpret
    C, R, V = Xc.shape
    if eligible is not None:
        p_prop = pp.mask_proposals(j_prop, p_prop, eligible, V)
    Xflat = Xc.reshape(C, -1).astype(jnp.int32)
    omega0, theta0, lam0, obj0 = batched_hard_loads(problem, Xc)
    (_, _, F, _, route_inc, proc_params, net_params) = \
        pp.pack_problem(problem)
    bX, stats = pp.fused_anneal_tpu(
        Xflat, j_prop.astype(jnp.int32), p_prop.astype(jnp.int32), u_prop,
        temps, *pp.pack_aux(aux), omega0, theta0, lam0, obj0,
        F, route_inc, proc_params, net_params, interpret=interpret)
    return bX.reshape(C, R, V), stats
