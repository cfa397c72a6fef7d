"""The fused anneal kernel's share of its roofline: the least time the chip
could take for the launches in the trace (``roofline.fused_anneal_cost``
at each launch's own shapes, read from its event by
``roofline.launch_shape``, over the peaks of ``peaks.json``), divided by
the summed device time of the kernel's events.  A launch is one event of
the kernel's operation.  Nothing is read where the trace holds no kernel
event."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import roofline  # noqa: E402

# the kernel's operation: ``%fused_anneal.<n> = (...) custom-call(...)``
KERNEL = "%fused_anneal"


def read(run):
    red = run.trace
    if red is None:
        return None
    launches = [(name, b - a) for name, a, b in red.kernels
                if name.startswith(KERNEL)]
    if not launches:
        return None
    least = sum(roofline.least_seconds(
        *roofline.fused_anneal_cost(**roofline.launch_shape(name)),
        run.device_kind)[0] for name, _ in launches)
    return 100.0 * least / (sum(ns for _, ns in launches) * 1e-9)
