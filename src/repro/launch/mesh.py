"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before calling.  Axes:

  pod    -- data parallelism between pods (slow DCN axis; gradients only)
  data   -- FSDP/ZeRO: params + optimizer state sharded, batch sharded
  model  -- tensor parallelism (heads / ffn / experts / vocab)
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, smoke dry-runs on few host devices)."""
    return _make_mesh(shape, axes)


# TPU v5e-class hardware constants (roofline denominators).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
