"""Online hedged serving at traffic scale — Chronos as the live policy.

Streams a request workload (any `repro.workloads` scenario, collapsed to
1-task requests) through the online serving loop: every probe_every-th
request is served unhedged and its completion feeds the tail governor,
which refits the Pareto tail and re-solves Algorithm 1 each epoch of
refit_every requests; the remaining traffic is hedged at the freshly
fitted (strategy, r*). Prints PoCD / mean machine-time / p99 latency per
strategy against the no-hedge baseline, plus the governor's fit
trajectory.

Run:  PYTHONPATH=src python examples/serve_requests.py
      PYTHONPATH=src python examples/serve_requests.py \
          --scenario flash-crowd --requests 5000 --strategies \
          hadoop_ns,sresume,auto --refit-every 500 --probe-every 10
      PYTHONPATH=src python examples/serve_requests.py \
          --requests 20000 --devices 8 --window 1024
"""
import argparse
import os

ap = argparse.ArgumentParser()
ap.add_argument("--scenario", default="request-storm",
                help="workload-registry scenario serving as the request "
                     "stream (default: request-storm)")
ap.add_argument("--requests", type=int, default=4000)
ap.add_argument("--seed", type=int, default=0)
ap.add_argument("--theta", type=float, default=1e-3)
ap.add_argument("--strategies", default=None,
                help="comma-separated subset of repro.strategies.names() "
                     "plus 'auto' (governor-chosen per epoch); default: "
                     "all registered strategies")
ap.add_argument("--refit-every", type=int, default=500,
                help="epoch length in requests; 0 = known-tail mode "
                     "(solve once at the true per-request tail)")
ap.add_argument("--probe-every", type=int, default=10,
                help="serve every k-th request unhedged as governor "
                     "exploration traffic (must divide --refit-every)")
ap.add_argument("--window", type=int, default=512,
                help="compiled serving window width (requests per "
                     "dispatch)")
ap.add_argument("--devices", type=int, default=0,
                help="> 0 shards serving windows over N devices via the "
                     "fleet mesh (forcing N XLA host devices on CPU); "
                     "bit-identical to single-device serving")
ap.add_argument("--fixed-r", type=int, default=0,
                help="> 0 adds a fixed-r clone baseline at this "
                     "replication level")
args = ap.parse_args()

_flags = os.environ.get("XLA_FLAGS", "")
if args.devices > 0 and "xla_force_host_platform_device_count" not in _flags:
    # must happen before jax is imported anywhere in this process
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count="
                               f"{args.devices}")

import jax
import numpy as np

from repro import RunConfig, compile_cache, simulate
from repro.serve import make_requests, serve_trace
from repro.strategies import names
from repro.workloads import list_scenarios

compile_cache.enable()

if args.scenario not in list_scenarios():
    ap.error(f"unknown scenario {args.scenario!r}; registered: "
             + ", ".join(sorted(list_scenarios())))
if args.strategies:
    ORDER = tuple(s.strip() for s in args.strategies.split(",") if s.strip())
    unknown = sorted(set(ORDER) - set(names()) - {"auto"})
    if unknown:
        ap.error(f"unknown strategies {', '.join(unknown)}; registered: "
                 f"{', '.join(names())} (+ auto)")
else:
    ORDER = names()

reqs = make_requests(args.scenario, n_requests=args.requests,
                     seed=args.seed)
refit = args.refit_every if args.refit_every > 0 else None
key = jax.random.PRNGKey(args.seed)

mode = (f"online (epochs of {refit}, probe every {args.probe_every})"
        if refit else "known-tail")
print(f"{args.scenario}: {reqs.n_requests} requests, beta in "
      f"[{reqs.beta.min():.2f}, {reqs.beta.max():.2f}], {mode}"
      + (f", {args.devices} devices" if args.devices > 0 else ""))

cfg = RunConfig(serve=True, theta=args.theta, strategies=ORDER,
                window=args.window, refit_every=refit,
                probe_every=args.probe_every,
                devices=args.devices if args.devices > 0 else None)
outs, r_min = simulate(key, reqs, cfg=cfg)

if args.fixed_r > 0:
    outs[f"clone r={args.fixed_r}"] = serve_trace(
        jax.random.fold_in(key, 10_007), reqs, strategy="clone",
        theta=args.theta, r_min=r_min, window=args.window,
        refit_every=refit, probe_every=args.probe_every,
        r_override=args.fixed_r)

print(f"\n{'strategy':14s} {'PoCD':>7s} {'machine-t':>10s} {'p99 lat':>8s} "
      f"{'utility':>8s} {'mean r*':>8s} {'refits':>7s}")
for name, o in outs.items():
    print(f"{name:14s} {float(o.result.pocd):7.4f} "
          f"{float(o.result.mean_cost):10.3f} {o.latency['p99']:8.3f} "
          f"{o.utility:8.3f} {o.mean_r:8.2f} {o.n_refits:7d}")

base = outs.get("hadoop_ns")
hedged = {n: o for n, o in outs.items()
          if n != "hadoop_ns" and o.mean_r > 0}
if base is not None and hedged:
    best = max(hedged, key=lambda n: float(hedged[n].result.pocd))
    o = hedged[best]
    dp = (float(o.result.pocd) - float(base.result.pocd)) * 100
    dc = (float(o.result.mean_cost) / float(base.result.mean_cost)
          - 1) * 100
    print(f"\nbest hedge ({best}) vs no-hedge: PoCD {dp:+.1f} pts, "
          f"machine-time {dc:+.1f}%")

probe = next((o for o in outs.values() if o.fits), None)
if probe is not None:
    trail = ", ".join(f"(t_min {f.t_min:.2f}, beta {f.beta:.2f})"
                      for f in probe.fits[-3:])
    true_b = float(np.mean(reqs.beta))
    print(f"governor fit trajectory (last 3 of {len(probe.fits)}): {trail}"
          f"  [stream mean beta {true_b:.2f}]")
