"""The one traffic generator: a trace of jobs or requests from `--seed`.

A configuration (`configs/<name>.json`) fixes the deployment's job
classes and its trace scale (`n_jobs`, over `hours` where jobs arrive
at a rate); a traffic mix (`traffic/<name>.json`) fixes the arrival
process and the population seed. Every seed of a cell gets the same set
of jobs and the same set of arrival times, and the seed decides which
job lands on which arrival. A closed stream (`"arrival": "closed"`) has
every request queued at time 0.
So the shapes the program compiles for, and the work it does, are the
same for every seed; the draws and the order differ.

The samplers are copies of the program's own (`workloads/generators.py`,
calibrated to the paper's trace statistics), kept here so that no change
to the program can move the yardstick.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

COLUMNS = ("n_tasks", "t_min", "beta", "D", "arrival", "C", "theta_scale",
           "job_class")


def _class_table(classes) -> dict:
    """Stack the config's job classes into (K,) float32 columns."""
    f = lambda k: jnp.asarray([c[k] for c in classes], jnp.float32)
    lo = lambda k: jnp.asarray([c[k][0] for c in classes], jnp.float32)
    hi = lambda k: jnp.asarray([c[k][1] for c in classes], jnp.float32)
    return dict(weight=f("weight"), mean_tasks=f("mean_tasks"),
                sigma_tasks=f("sigma_tasks"), min_tasks=f("min_tasks"),
                max_tasks=f("max_tasks"), t_lo=lo("t_min_range"),
                t_hi=hi("t_min_range"), b_lo=lo("beta_range"),
                b_hi=hi("beta_range"), ratio=f("deadline_ratio"),
                theta_scale=f("theta_scale"), price=f("price"))


def poisson_arrivals(key, n, rate):
    return jnp.cumsum(jax.random.exponential(key, (n,)) / rate)


def closed_arrivals(key, n, rate):
    return jnp.zeros((n,), jnp.float32)


ARRIVALS = {"poisson": poisson_arrivals, "closed": closed_arrivals}


@functools.partial(jax.jit, static_argnames=("n", "arrival"))
def _population(key, table, rate, *, n, arrival):
    """Class, size, tail and deadline of n jobs, and n arrival times."""
    k_mix, k_cnt, k_par, k_arr = jax.random.split(key, 4)
    cls = jax.random.categorical(k_mix, jnp.log(table["weight"]),
                                 shape=(n,)).astype(jnp.int32)
    sigma = table["sigma_tasks"][cls]
    mu = jnp.log(table["mean_tasks"])[cls] - 0.5 * sigma ** 2
    raw = jnp.exp(mu + sigma * jax.random.normal(k_cnt, (n,)))
    n_tasks = jnp.clip(raw, table["min_tasks"][cls],
                       table["max_tasks"][cls]).astype(jnp.int32)
    k1, k2 = jax.random.split(k_par)
    t_min = table["t_lo"][cls] + (table["t_hi"] - table["t_lo"])[cls] * \
        jax.random.uniform(k1, (n,))
    beta = table["b_lo"][cls] + (table["b_hi"] - table["b_lo"])[cls] * \
        jax.random.uniform(k2, (n,))
    D = table["ratio"][cls] * (t_min * beta / (beta - 1.0))
    arrivals = jnp.sort(ARRIVALS[arrival](k_arr, n, rate))
    return dict(n_tasks=n_tasks, t_min=t_min, beta=beta, D=D,
                C=table["price"][cls], theta_scale=table["theta_scale"][cls],
                job_class=cls), arrivals


@jax.jit
def _assign(key, jobs, arrivals):
    """Lay the jobs onto the sorted arrival times in a seeded order."""
    order = jax.random.permutation(key, arrivals.shape[0])
    out = {k: v[order] for k, v in jobs.items()}
    out["arrival"] = arrivals
    return out


def make_trace(config: dict, traffic: dict, seed: int) -> dict:
    """Arrival-sorted per-job numpy columns (`COLUMNS`) for one run."""
    n = int(config["n_jobs"])
    rate = n / (float(config["hours"]) * 3600.0) if "hours" in config else 0.0
    jobs, arrivals = _population(
        jax.random.PRNGKey(int(traffic["population_seed"])),
        _class_table(config["classes"]), jnp.float32(rate), n=n,
        arrival=traffic["arrival"])
    out = jax.device_get(_assign(jax.random.PRNGKey(seed), jobs, arrivals))
    dtypes = dict(n_tasks=np.int32, job_class=np.int32)
    return {k: np.asarray(out[k], dtypes.get(k, np.float32))
            for k in COLUMNS}
