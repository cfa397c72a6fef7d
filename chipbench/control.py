#!/usr/bin/env python3
"""The correctness control, on the chip, at a cell's own size and load.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 5

For each seed, in one process, serves the cell's mix for a short window
and reads the numbers that decide ``correct`` three ways:

* ``program``: as the benchmark does, what the program committed against
  the float64 reference;
* ``control``: the reference itself put in the program's place one
  precision below its float32 (``run.check(..., control=True)``: every
  reported objective the reference's in bfloat16, one more polish sweep
  scored in bfloat16);
* ``kernel_bf16``: the program again, with the fused anneal kernel fed
  its inputs in bfloat16 (``bf16_kernel``), read by ``fused_gap``.

The control has to read past the limit; the lower and upper readings of
``PERF.md`` come from this output and the benchmark's own runs.  One JSON
line per seed.  Not run by the benchmark itself.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys

import run

READ = ("objective_gap", "fused_gap", "unpolished_share")


@contextlib.contextmanager
def bf16_kernel():
    """The fused anneal kernel scoring its proposals on bfloat16 data: its
    demands, link bitrates, node parameters and start loads rounded to
    bfloat16 on the way in, its arithmetic left as it is."""
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels import placement_power as pp
    real = pp.fused_anneal_tpu

    def low(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(jnp.bfloat16).astype(x.dtype)
        return x

    def kernel(X, j_prop, p_prop, u_prop, temps, *rest, **kw):
        return real(X, j_prop, p_prop, u_prop, temps,
                    *(low(a) for a in rest), **kw)
    # the kernel is called inside a jitted wrapper: drop what that wrapper
    # traced, before and after, so each side traces its own kernel
    ops.fused_anneal.clear_cache()
    pp.fused_anneal_tpu = kernel
    try:
        yield
    finally:
        pp.fused_anneal_tpu = real
        ops.fused_anneal.clear_cache()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    jax = run.init_jax()
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    _, cell, cfg, traffic = run.load_cell(run.ROOT, args.workload)
    kind = jax.devices()[0].device_kind
    for seed in (int(s) for s in args.seeds.split(",")):
        r, checks, counts, _ = run.serve(cfg, traffic, seed, args.seconds,
                                         None, kind)
        ctrl = run.check(cfg, r.pool, r.commits, args.seconds, r.check_seed,
                         control=True)
        with bf16_kernel():
            _, low, _, _ = run.serve(cfg, traffic, seed, args.seconds, None,
                                     kind)
        print(json.dumps(dict(
            workload=args.workload, seed=seed, commits=checks["commits"],
            fused_commits=checks["fused_commits"],
            polished_commits=checks["polished_commits"],
            program={k: checks[k] for k in READ + ("bookkeeping_errors",
                                                    "failed_events")},
            control={k: ctrl[k] for k in READ},
            kernel_bf16=dict(fused_gap=low["fused_gap"],
                             fused_commits=low["fused_commits"]),
            limits=cfg["limits"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
