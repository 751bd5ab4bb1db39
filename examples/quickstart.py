"""Quickstart: the Chronos optimization framework in 60 seconds.

Given a job (N tasks, Pareto task times, deadline D), compute the closed-form
PoCD and expected machine cost of Clone / Speculative-Restart /
Speculative-Resume, solve for the optimal number of speculative attempts r*
(Algorithm 1), and cross-check against the Monte-Carlo kernel.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core import (JobSpec, solve_grid, solve_algorithm1, pocd_of,
                        cost_of, utility, gamma, theory)
from repro.kernels import ops

compile_cache.enable()

# A deadline-critical job: 10 tasks, task times ~ Pareto(t_min=10s, beta=2),
# deadline 50s, straggler check at 3s, kill slow attempts at 8s.
job = JobSpec.make(t_min=10.0, beta=2.0, D=50.0, N=10,
                   tau_est=3.0, tau_kill=8.0, phi_est=0.25,
                   C=1.0, theta=1e-3, R_min=0.0)

print("=== closed-form PoCD / cost (Theorems 1-6) ===")
for strategy in ("clone", "srestart", "sresume"):
    for r in (0, 1, 2, 3):
        R = float(pocd_of(strategy, r, job))
        E = float(cost_of(strategy, r, job))
        U = float(utility(strategy, jnp.float32(r), job))
        print(f"{strategy:9s} r={r}  PoCD={R:.4f}  E[T]={E:7.1f}  U={U:+.4f}")
    print()

print("=== Algorithm 1: optimal r* per strategy ===")
for strategy in ("clone", "srestart", "sresume"):
    sol_fast = solve_grid(strategy, job)          # production exact solver
    sol_paper = solve_algorithm1(strategy, job)   # paper-faithful hybrid
    g = float(gamma(strategy, job))
    print(f"{strategy:9s} r*={sol_fast.r_opt} U={sol_fast.utility:+.4f} "
          f"(Algorithm 1 agrees: r*={sol_paper.r_opt})  Gamma={g:+.2f}")

print("\n=== Theorem 7 orderings ===")
print("Clone beats S-Restart:   ", bool(theory.clone_beats_srestart(job, 2)))
print("S-Resume beats S-Restart:", bool(theory.sresume_beats_srestart(job, 2)))

print("\n=== Monte-Carlo cross-check (Pallas pocd_mc kernel) ===")
J, N, R = 4096, 10, 4
u = jax.random.uniform(jax.random.PRNGKey(0), (J, N, R), minval=1e-7,
                       maxval=1.0)
ones = jnp.ones((J,))
for strategy in ("clone", "sresume"):
    sol = solve_grid(strategy, job)
    met, cost = ops.pocd_mc(u, 10.0 * ones, 2.0 * ones, 50.0 * ones,
                            jnp.full((J,), sol.r_opt, jnp.int32),
                            mode=strategy, tau_est_frac=0.3,
                            tau_kill_gap_frac=0.5, phi=0.25)
    print(f"{strategy:9s} r*={sol.r_opt}  theory PoCD={sol.pocd:.4f}  "
          f"kernel MC PoCD={float(met.mean()):.4f}")
