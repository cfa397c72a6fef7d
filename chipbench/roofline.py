"""Peaks of the chip and the operations and bytes of the fused anneal
kernel, for its share of the roofline.

``peaks(kind)`` reads ``peaks.json`` by ``device_kind``; a device missing
from the table is an error, never a default.

``launch_shape`` reads those shapes from the launch's own event in the
device trace, whose name is the custom call's HLO text with every operand's
type.  ``fused_anneal_cost`` counts what one launch of the fused Metropolis
kernel needs at its shapes: P processing nodes, N network nodes, J = R*V
VM slots, D incident-link slots per VM, C chains and T steps.  Per step and
chain the kernel turns the moved VM's 2*D incident links (removal and
insertion legs) into a bitrate-weighted row over the P*P ordered node
pairs (a compare and a multiply-add per element and leg) and multiplies it
by the [P*P, N] route incidence table (2*P*P*N).  The lower-order work over
J, P and N per step is left out, so the count is a floor and the share
cannot be overstated.  Bytes are what must cross HBM once: proposals,
temperatures, start and best placements, start loads, the incidence and
link tables and the node parameters.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, Tuple

HERE = Path(__file__).resolve().parent


def peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def fused_anneal_cost(P: int, N: int, J: int, D: int, C: int,
                      T: int) -> Tuple[float, float]:
    """(operations, HBM bytes) of one fused anneal launch."""
    per_step = 2.0 * P * P * N + 2.0 * (2 * D) * P * P
    flops = per_step * C * T
    words = (3 * C * T          # proposals: VM, destination, uniform draw
             + T                # temperatures
             + 2 * C * J        # start and best placements
             + C * (2 * P + N + 1)   # start loads and objective
             + 2 * C            # best and final objective
             + P * P * N        # route incidence table
             + 3 * J * D + J    # incident-link tables and demands
             + 9 * P + 5 * N)   # node parameters
    return flops, 4.0 * words


OPERAND = re.compile(r"[a-z]+\d+\[([\d,]*)\]")
STEP_CHUNK = 128        # proposal steps per chunk of the kernel's streams


def launch_shape(hlo: str) -> Dict[str, int]:
    """(P, N, J, D, C, T) of one fused anneal launch, from its trace event's
    HLO text.  The operands come in the kernel's order: placements [C, J],
    three proposal streams [T/128, C, 128] and the temperatures, demands
    [1, J], three incident-link tables [D, 1, J], the route incidence
    [P*P, N], node parameters [9, P] and [5, N], and the start loads.  The
    streams carry T rounded up to whole chunks of 128 steps, so T is taken
    as the fewest steps those chunks can hold, and the count stays a
    floor."""
    args = hlo[hlo.index("custom-call(") + len("custom-call("):]
    args = args.split("custom_call_target")[0]
    dims = [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in OPERAND.finditer(args)]
    (C, J), chunks = dims[0], dims[1][0]
    return dict(P=dims[10][1], N=dims[11][1], J=J, D=dims[6][0], C=C,
                T=STEP_CHUNK * (chunks - 1) + 1)


def least_seconds(flops: float, nbytes: float, kind: str) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    pk = peaks(kind)
    t_c = flops / pk["bf16_flops_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
