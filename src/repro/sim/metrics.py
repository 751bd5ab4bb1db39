"""Job-level metrics from per-task simulator outputs (segment reductions)."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs import trace as obs_trace
from .trace import JobSet


class SimResult(NamedTuple):
    pocd: jnp.ndarray          # scalar — fraction of jobs meeting D
    job_met: jnp.ndarray       # (J,) bool
    job_completion: jnp.ndarray  # (J,)
    job_cost: jnp.ndarray      # (J,) machine-time * C
    mean_cost: jnp.ndarray     # scalar


def aggregate(jobs: JobSet, completion, machine) -> SimResult:
    job_completion = jax.ops.segment_max(completion, jobs.job_id, jobs.n_jobs)
    job_machine = jax.ops.segment_sum(machine, jobs.job_id, jobs.n_jobs)
    met = job_completion <= jobs.D
    cost = job_machine * jobs.C
    return SimResult(pocd=jnp.mean(met.astype(jnp.float32)),
                     job_met=met, job_completion=job_completion,
                     job_cost=cost, mean_cost=jnp.mean(cost))


def class_summary(jobs: JobSet, result: SimResult) -> dict:
    """Per-workload-class breakdown of a SimResult (host-side numpy).

    Returns {class_id: {"n_jobs", "pocd", "mean_cost", "mean_completion"}}.
    With reps>1 `job_met` is already a met frequency, so `pocd` stays the
    per-class deadline-met probability.
    """
    import numpy as np
    cls = np.asarray(jobs.job_class)
    met = np.asarray(result.job_met, np.float64)
    cost = np.asarray(result.job_cost, np.float64)
    comp = np.asarray(result.job_completion, np.float64)
    out = {}
    for c in np.unique(cls):
        m = cls == c
        out[int(c)] = {
            "n_jobs": int(m.sum()),
            "pocd": float(met[m].mean()),
            "mean_cost": float(cost[m].mean()),
            "mean_completion": float(comp[m].mean()),
        }
    return out


def request_result(reqs, completion, machine) -> SimResult:
    """SimResult from per-request serving columns (repro.serve).

    A request is a 1-task job, so no segment reduction is needed: the
    per-request completion IS the job completion and the per-request
    machine time, priced by C, IS the job cost. Producing the same
    schema as `aggregate` lets StreamCombiner accumulate serving epochs
    exactly as it accumulates batch chunks.
    """
    completion = jnp.asarray(completion)
    met = completion <= jnp.asarray(reqs.D)
    cost = jnp.asarray(machine) * jnp.asarray(reqs.C)
    return SimResult(pocd=jnp.mean(met.astype(jnp.float32)),
                     job_met=met, job_completion=completion,
                     job_cost=cost, mean_cost=jnp.mean(cost))


def latency_summary(result: SimResult) -> dict:
    """Host-side latency percentiles of a result's completion column."""
    import numpy as np
    with obs_trace.span("d2h.wait"):
        lat = np.asarray(result.job_completion, np.float64)
    return {"p50": float(np.percentile(lat, 50)),
            "p95": float(np.percentile(lat, 95)),
            "p99": float(np.percentile(lat, 99)),
            "mean": float(lat.mean())}


def net_utility(pocd, mean_cost, r_min, theta):
    """Paper's evaluation utility on empirical quantities (Fig 2c/3c)."""
    gap = jnp.maximum(pocd - r_min, 1e-9)
    return jnp.where(pocd > r_min, jnp.log10(gap) - theta * mean_cost,
                     -jnp.inf)


class StreamCombiner:
    """Streaming reducer over job-contiguous chunks of a trace.

    The fleet layer (`repro.fleet`) splits million-job traces into
    bounded-memory chunks and runs the compiled per-strategy pipeline per
    chunk; this combiner accumulates each chunk's per-job metric columns
    on the host (a few bytes per job — the memory that chunking bounds is
    the per-task draw buffers, not these) and `finalize` recomputes the
    scalar reductions over the full concatenated columns in one device
    call. Because the scalars are reduced once over the same (J,) arrays
    a monolithic run would produce, a chunked run is bit-identical to an
    unchunked one — the equality the chunk tests pin.

    Queue metrics (finite-capacity chunks) combine as weighted means
    (weights = chunk job counts; `max_wait` takes the max, `preempted`
    the sum). Each chunk replays on its own slot pool, so combined queue
    metrics describe per-window contention — see DESIGN.md §14.
    """

    def __init__(self):
        self._met, self._completion, self._cost = [], [], []
        self._weights, self._queues = [], []
        self._capacity = []

    def add(self, result: SimResult, n_jobs: int, queue=None,
            capacity=None) -> None:
        import numpy as np
        with obs_trace.span("d2h.wait"):
            self._met.append(np.asarray(result.job_met))
        with obs_trace.span("d2h.wait"):
            self._completion.append(np.asarray(result.job_completion))
        with obs_trace.span("d2h.wait"):
            self._cost.append(np.asarray(result.job_cost))
        self._weights.append(float(n_jobs))
        if queue is not None:
            # paired with this chunk's weight explicitly, so a caller
            # mixing queue-less and queue-bearing chunks can never
            # mis-weight a queue with another chunk's job count
            self._queues.append((float(n_jobs), queue))
        if capacity is not None:
            # device-side CapacityMetrics pytree for this chunk's window
            # (repro.obs.metrics), combined in chunk order at finalize
            self._capacity.append(capacity)

    @property
    def n_chunks(self) -> int:
        return len(self._weights)

    def finalize(self) -> SimResult:
        import numpy as np
        if not self._met:
            raise ValueError("StreamCombiner.finalize before any add()")
        with obs_trace.span("combiner.finalize"):
            met = jnp.asarray(np.concatenate(self._met))
            completion = jnp.asarray(np.concatenate(self._completion))
            cost = jnp.asarray(np.concatenate(self._cost))
            return SimResult(
                pocd=jnp.mean(met.astype(jnp.float32)), job_met=met,
                job_completion=completion, job_cost=cost,
                mean_cost=jnp.mean(cost))

    def finalize_queue(self):
        """Weighted-combined queue metrics (None when no chunk had any)."""
        import numpy as np
        if not self._queues:
            return None
        w = np.asarray([wi for wi, _ in self._queues], np.float64)
        w = w / w.sum()
        queues = [q for _, q in self._queues]
        f = lambda xs: jnp.float32(float(np.sum(w * np.asarray(xs))))
        q0 = queues[0]
        return type(q0)(
            mean_wait=f([float(q.mean_wait) for q in queues]),
            max_wait=jnp.float32(max(float(q.max_wait) for q in queues)),
            utilization=f([float(q.utilization) for q in queues]),
            preempted=jnp.float32(
                sum(float(q.preempted) for q in queues)),
            admitted_frac=f([float(q.admitted_frac) for q in queues]),
            slots=q0.slots)

    def finalize_capacity(self):
        """Chunk-order combination of the per-window CapacityMetrics
        pytrees (None when no chunk carried any). Counters, histograms,
        and integrals sum — one fixed order, host-side — so the combined
        pytree is invariant to mesh shape; see repro.obs.metrics."""
        if not self._capacity:
            return None
        from ..obs.metrics import combine_windows
        return combine_windows(self._capacity)

    # -- chunk-boundary checkpointing (repro.chaos) ------------------------
    #
    # The combiner IS the resume state of a chunked run: everything already
    # reduced lives in these host lists, everything not yet reduced is
    # recomputable from (key, chunk index). state_dict snapshots the lists
    # as a flat {name: numpy array} dict; from_state rebuilds a combiner
    # whose finalize() output is BITWISE identical to the original's —
    # per-chunk list boundaries are restored exactly (from the weights),
    # so the final np.concatenate sees the same parts in the same order.

    def state_dict(self) -> dict:
        import numpy as np
        if not self._met:
            raise ValueError("state_dict of an empty StreamCombiner")
        out = {
            "met": np.concatenate(self._met),
            "completion": np.concatenate(self._completion),
            "cost": np.concatenate(self._cost),
            "weights": np.asarray(self._weights, np.float64),
        }
        if self._queues:
            out["queue_w"] = np.asarray([w for w, _ in self._queues],
                                        np.float64)
            out["queue_vals"] = np.asarray(
                [[float(q.mean_wait), float(q.max_wait),
                  float(q.utilization), float(q.preempted),
                  float(q.admitted_frac)] for _, q in self._queues],
                np.float32)
            out["queue_slots"] = np.asarray(
                [-1 if q.slots is None else int(q.slots)
                 for _, q in self._queues], np.int64)
        if self._capacity:
            for f in self._capacity[0]._fields:
                out[f"cap_{f}"] = np.stack(
                    [np.asarray(getattr(m, f)) for m in self._capacity])
        return out

    @classmethod
    def from_state(cls, state: dict) -> "StreamCombiner":
        import numpy as np
        acc = cls()
        w = np.asarray(state["weights"], np.float64)
        splits = np.cumsum(w.astype(np.int64))[:-1]
        acc._met = list(np.split(np.asarray(state["met"]), splits))
        acc._completion = list(np.split(np.asarray(state["completion"]),
                                        splits))
        acc._cost = list(np.split(np.asarray(state["cost"]), splits))
        acc._weights = [float(x) for x in w]
        if "queue_vals" in state:
            from ..cluster.engine import QueueMetrics
            vals = np.asarray(state["queue_vals"])
            slots = np.asarray(state["queue_slots"])
            acc._queues = [
                (float(wi), QueueMetrics(
                    mean_wait=jnp.float32(v[0]), max_wait=jnp.float32(v[1]),
                    utilization=jnp.float32(v[2]),
                    preempted=jnp.float32(v[3]),
                    admitted_frac=jnp.float32(v[4]),
                    slots=None if int(s) < 0 else int(s)))
                for wi, v, s in zip(state["queue_w"], vals, slots)]
        cap_keys = [k for k in state if k.startswith("cap_")]
        if cap_keys:
            from ..obs.metrics import CapacityMetrics
            n = int(np.asarray(state[cap_keys[0]]).shape[0])
            acc._capacity = [
                CapacityMetrics(**{f: np.asarray(state[f"cap_{f}"])[i]
                                   for f in CapacityMetrics._fields})
                for i in range(n)]
        return acc
