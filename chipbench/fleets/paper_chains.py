"""The paper's VSR generator (arXiv:2105.03221 section 3): VM 0 is the
input VM at the service's source with U(0.1, 1) GFLOPS, the other VMs
U(3, 10) GFLOPS, chained by U(5, 50) Mbps virtual links ``v -> v+1``."""
from typing import Sequence

import numpy as np

import gen


def services(rng: np.random.Generator, n: int, sources: Sequence[int],
             n_vms: int = 3, vm_gflops=(3.0, 10.0), input_gflops=(0.1, 1.0),
             link_mbps=(5.0, 50.0)) -> gen.Services:
    F = rng.uniform(*vm_gflops, size=(n, n_vms)).astype(np.float32)
    F[:, 0] = rng.uniform(*input_gflops, size=n)
    H = np.zeros((n, n_vms, n_vms), np.float32)
    for v in range(n_vms - 1):
        H[:, v, v + 1] = rng.uniform(*link_mbps, size=n)
    src = np.asarray(rng.choice(np.asarray(sources), size=n), np.int32)
    return gen.Services(F=F, H=H, src=src)
