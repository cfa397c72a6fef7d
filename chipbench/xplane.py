"""Reduction of one profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy intervals, device time per program, the Pallas
kernels' events, the benchmark's own host annotations and the program's
spans, all on the trace's one clock (nanoseconds).

Device planes are ``/device:TPU:<n>``.  On each, the line ``XLA Ops``
holds one event per operation that ran, named by its HLO text (a Pallas
kernel is a ``custom-call``), and ``XLA Modules`` one per program run,
named after the jitted function.  Host annotations are the
``jax.profiler.TraceAnnotation`` spans that the serving loop opens, found
on any line of the host plane by name; the program's own spans are the
annotations whose names start with ``repro.`` (a span of its telemetry,
``repro.<span name>``), all of them kept.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
PROGRAM = "repro."          # the prefix of the program's span annotations


@dataclass
class Reduced:
    busy_per_device: List[np.ndarray]    # per device, merged intervals
    kernels: List[Tuple[str, float, float]]   # custom calls (name, a, b)
    programs: Dict[str, float]           # program name -> device ns
    spans: Dict[str, List[Tuple[float, float]]] = field(default_factory=dict)

    @property
    def n_devices(self) -> int:
        return len(self.busy_per_device)

    @property
    def busy(self) -> np.ndarray:
        """Intervals in which any device ran an operation."""
        return merge(np.concatenate(self.busy_per_device)
                     if self.busy_per_device else np.zeros((0, 2)))

    def busy_in(self, a: float, b: float) -> float:
        """Device-busy time inside [a, b], averaged over the devices."""
        if not self.busy_per_device:
            return 0.0
        return float(np.mean([covered(m, a, b)
                              for m in self.busy_per_device]))

    def window(self, name: str) -> Tuple[float, float]:
        (a, b), = self.spans[name]
        return a, b


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of [start, end] intervals as sorted disjoint intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > reach[:-1]]
    starts = iv[new, 0]
    ends = reach[np.r_[np.flatnonzero(new)[1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], axis=1).astype(np.float64)


def covered(merged: np.ndarray, a: float, b: float) -> float:
    """Length of [a, b] that the merged intervals cover."""
    if len(merged) == 0 or b <= a:
        return 0.0
    lo = np.clip(merged[:, 0], a, b)
    hi = np.clip(merged[:, 1], a, b)
    return float((hi - lo).sum())


def gaps(merged: np.ndarray, a: float, b: float) -> np.ndarray:
    """[G, 2] idle gaps of [a, b] between the merged busy intervals."""
    inside = merged[(merged[:, 1] > a) & (merged[:, 0] < b)] \
        if len(merged) else merged
    edges = np.concatenate([[a], np.clip(inside.reshape(-1), a, b), [b]])
    g = edges.reshape(-1, 2)
    return g[g[:, 1] > g[:, 0]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(xplane: str, span_names) -> Reduced:
    """Read one trace (``.xplane.pb``, or gzipped as ``.xplane.pb.gz``);
    ``span_names`` are the host annotations to keep, besides every
    program span."""
    import gzip
    from jax.profiler import ProfileData
    want = set(span_names)
    if xplane.endswith(".gz"):
        with gzip.open(xplane, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(xplane)
    per_device, kernels = [], []
    programs: Dict[str, float] = {}
    spans: Dict[str, List[Tuple[float, float]]] = {n: [] for n in want}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            intervals = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        a = float(ev.start_ns)
                        b = a + float(ev.duration_ns)
                        name = ev.name
                        intervals.append((a, b))
                        if "custom-call" in name:
                            kernels.append((name, a, b))
                elif line.name == PROGRAMS_LINE:
                    for ev in line.events:
                        programs[ev.name] = (programs.get(ev.name, 0.0)
                                             + float(ev.duration_ns))
            per_device.append(
                merge(np.asarray(intervals, np.float64).reshape(-1, 2)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in want or ev.name.startswith(PROGRAM):
                        a = float(ev.start_ns)
                        spans.setdefault(ev.name, []).append(
                            (a, a + float(ev.duration_ns)))
    for v in spans.values():
        v.sort()
    return Reduced(busy_per_device=per_device, kernels=kernels,
                   programs=programs, spans=spans)
