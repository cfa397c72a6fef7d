"""Logical-axis sharding: mesh-agnostic models, mesh-specific placement.

Models annotate tensors with *logical* axes ("batch", "heads", "embed", ...);
this module resolves them to mesh axes under the active mesh and applies
``with_sharding_constraint``.  Resolution silently drops a mesh axis whenever
the dimension is not divisible by it (e.g. hymba's 25 heads on a 16-way
'model' axis, internvl2's 92553 vocab), so every architecture shards as far
as its shapes allow and replicates the rest -- no per-arch special cases.

Default rules (overridable per-context, the perf hillclimb uses this):
  batch   -> ('pod', 'data')     activations' batch dim (pure DP across pods)
  fsdp    -> 'data'              parameter / optimizer-state sharding (ZeRO-3)
  tp      -> 'model'             tensor-parallel dim (heads / ffn / vocab)
  kv_seq  -> 'model'             decode KV-cache sequence when heads < TP
  expert  -> 'model'             expert parallelism for MoE weight stacks
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, None]


def shard_map_compat(f, mesh, in_specs, out_specs, axis_names, check=False):
    """``jax.shard_map`` manual over ``axis_names`` only (the other mesh
    axes stay automatic)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset(axis_names), check_vma=check)

DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),
    "tp": ("model",),
    "heads": ("model",),
    "q_seq": ("model",),     # sequence parallelism when heads % tp != 0
    "kv_seq": ("model",),
    "expert": ("model",),
    "vocab": ("model",),
    "seq": (),
    "embed": (),
    "none": (),
}


class MeshContext(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Dict[str, Tuple[str, ...]] = dict(DEFAULT_RULES)


_CTX = MeshContext()


@contextmanager
def mesh_context(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    prev_mesh, prev_rules = _CTX.mesh, _CTX.rules
    _CTX.mesh = mesh
    _CTX.rules = {**DEFAULT_RULES, **(rules or {})}
    try:
        with mesh:
            yield mesh
    finally:
        _CTX.mesh, _CTX.rules = prev_mesh, prev_rules


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def axis_size(name: str) -> int:
    """Size of a mesh axis under the active mesh (1 when absent)."""
    mesh = _CTX.mesh
    if mesh is None or name not in mesh.shape:
        return 1
    return int(mesh.shape[name])


def _resolve(logical: Sequence[Axis], shape: Sequence[int],
             mesh: Mesh) -> P:
    """Map logical axis names to mesh axes, dropping non-divisible ones."""
    out = []
    used: set = set()
    for dim, name in zip(shape, logical):
        if name is None:
            out.append(None)
            continue
        mesh_axes = _CTX.rules.get(name, (name,) if name in mesh.shape else ())
        picked = []
        size = 1
        for ax in mesh_axes:
            if ax in used or ax not in mesh.shape:
                continue
            nsize = size * mesh.shape[ax]
            if dim % nsize == 0:
                picked.append(ax)
                used.add(ax)
                size = nsize
        out.append(tuple(picked) if len(picked) > 1 else
                   (picked[0] if picked else None))
    return P(*out)


def logical_spec(logical: Sequence[Axis], shape: Sequence[int],
                 mesh: Optional[Mesh] = None) -> P:
    mesh = mesh or _CTX.mesh
    if mesh is None:
        return P()
    return _resolve(logical, shape, mesh)


def shard(x: jax.Array, *logical: Axis) -> jax.Array:
    """Apply a sharding constraint resolved from logical axis names.

    No-op outside a mesh context so tests / single-device runs are untouched.
    """
    mesh = _CTX.mesh
    if mesh is None:
        return x
    if len(logical) != x.ndim:
        raise ValueError(f"{len(logical)} axes for rank-{x.ndim} tensor")
    spec = _resolve(logical, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_params(params, axes_tree, mesh: Optional[Mesh] = None):
    """Build a NamedSharding pytree for a param tree + logical-axes tree."""
    mesh = mesh or _CTX.mesh

    def one(x, axes):
        if mesh is None:
            return None
        spec = _resolve(axes, np.shape(x), mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(one, params, axes_tree,
                                  is_leaf=lambda a: isinstance(a, tuple)
                                  and all(isinstance(e, (str, type(None)))
                                          for e in a))
