"""End-to-end driver (the paper is an inference paper): serve real models
with batched requests, then place the serving fleet on the cloud-fog
substrate with the paper's optimizer and report energy per deployment.

  PYTHONPATH=src python examples/placement_aware_serving.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import topology
from repro.models import model as M
from repro.serve import cache as C
from repro.serve import engine
from repro.serve.scheduler import EnergyAwareScheduler, Service
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

# --- 1. serve a batch of requests through a real (reduced) model ----------
cfg = configs.get_smoke("qwen3-4b")
params, _ = M.init_model(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
B, S, GEN = 4, 24, 12
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                               jnp.int32)}
cache = C.zeros(C.cache_spec(cfg, B, S + GEN + 8))
t0 = time.time()
seq, _ = engine.greedy_generate(params, cfg, batch, cache, GEN)
dt = time.time() - t0
tok_rate = B * GEN / dt
print(f"served {B} requests x {GEN} tokens in {dt:.2f}s "
      f"({tok_rate:.1f} tok/s)")

# --- 2. place the serving fleet on the datacenter-scale CFN ---------------
# Each production service (full-size arch + its measured token rate) becomes
# a VSR; the paper's optimizer decides edge / fog / cloud per stage.
sched = EnergyAwareScheduler(topology.datacenter_topology())
sched.add_service(Service("qwen3-chat", configs.get("qwen3-4b"),
                          tokens_per_s=2000.0))
sched.add_service(Service("olmoe-embed", configs.get("olmoe-1b-7b"),
                          tokens_per_s=8000.0))
sched.add_service(Service("deepseek-api", configs.get("deepseek-v2-236b"),
                          tokens_per_s=500.0, n_stages=8))
for p in sched.solve():
    print(f"{p.service:14s} -> {'/'.join(p.layers)}")
s = sched.savings_vs_cloud()
print(f"fleet power: {sched.total_power_w():.0f} W  "
      f"(vs all-cloud: saves {s['saving_frac']:.1%})")
