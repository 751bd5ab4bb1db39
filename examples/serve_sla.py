"""Deadline-SLA serving with Chronos hedging.

Serves batched requests on a real (reduced-config) model engine while the
HedgedScheduler plans speculative replica dispatch per request deadline.
Compares SLA attainment (PoCD) and machine-time cost against the no-hedging
baseline — the serving analogue of the paper's Fig. 2.

Run:  PYTHONPATH=src python examples/serve_sla.py
"""
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.models.inputs import make_batch
from repro.serve import (Engine, HedgedScheduler, ReplicaPool, Request,
                         baseline_no_hedge)

compile_cache.enable()

# 1) a real engine decoding real tokens (reduced gemma2 for CPU speed)
cfg = get_config("gemma2-2b").reduced()
eng = Engine.build(cfg, max_seq=32)
batch = make_batch(cfg, 2, 8, "prefill")
tokens = eng.generate(batch, n_tokens=8)
print(f"engine ok: decoded {tokens.shape[1]} tokens/seq "
      f"on {cfg.name} (live KV-cache decode)\n")

# 2) SLA study over a heavy-tailed replica pool (draws are keyed
#    per-request inside the compiled window core — no shared generator)
import jax

pool = ReplicaPool(n_replicas=8, base_tok_s=200.0, beta=1.3)
requests = [Request(deadline=d, rid=i, n_tokens=64)
            for i, d in enumerate(np.random.default_rng(1).uniform(
                0.4, 0.9, size=600))]

sched = HedgedScheduler(pool, theta=1e-2, key=jax.random.PRNGKey(0))
hedged = sched.run_workload(requests)
base = baseline_no_hedge(pool, requests, key=jax.random.PRNGKey(0))

print(f"{'policy':16s} {'SLA attainment':>15s} {'mean machine-time':>18s} "
      f"{'p99 latency':>12s}")
print(f"{'no hedging':16s} {base['pocd']:15.3f} "
      f"{base['mean_machine_time']:18.3f} {base['latency']['p99']:12.3f}")
print(f"{'chronos hedged':16s} {hedged['pocd']:15.3f} "
      f"{hedged['mean_machine_time']:18.3f} "
      f"{hedged['latency']['p99']:12.3f}")
print(f"\nhedged mean r* = {hedged['mean_r']:.2f} "
      f"(adaptive per-request argmax over the Chronos trio)")
print("for the online-governor serving loop at traffic scale, see "
      "examples/serve_requests.py")
