"""Chaos layer: deterministic fault injection, checkpoint/resume
bit-identity, mesh shrink under device loss, governor re-solve.

The central contract (DESIGN.md §16): a faulted run is a pure function of
(FaultPlan, run key), and recovery — retry after an injected failure,
re-execution after detected corruption, resume after a simulated crash,
re-sharding after device loss — is BITWISE invisible in the metrics,
because every (rep, block) cell is keyed by its global coordinates and
all float reductions happen host-side in fixed order.

Mesh-shrink cases need 8 forced host devices and run under the CI
multi-device lane (XLA_FLAGS=--xla_force_host_platform_device_count=8);
they skip on a 1-device host.
"""
import json

import jax
import numpy as np
import pytest

from repro import ckpt
from repro.chaos import (EMPTY_PLAN, ChaosContext, ChaosExhausted,
                         CheckpointConfig, ElasticGovernor, FaultEvent,
                         FaultPlan, SimulatedCrash, from_faults, generate,
                         resume_cluster_fleet, resume_fleet)
from repro.chaos.recovery import (check_fingerprint, pack_state,
                                  run_fingerprint, unpack_state)
from repro.fleet import run_fleet_strategy
from repro.fleet.cluster import run_cluster_fleet_strategy
from repro.sim import SimParams, generate as gen_jobs
from repro.strategies import names

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

P = SimParams()
KEY = jax.random.PRNGKey(0)


def outputs_equal(a, b) -> bool:
    """Bitwise equality of two RunOutput/ClusterOutput payloads."""
    for g in a.result._fields:
        if not np.array_equal(np.asarray(getattr(a.result, g)),
                              np.asarray(getattr(b.result, g))):
            return False
    for f in ("r_opt", "utility", "theory_pocd", "theory_cost"):
        if not np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))):
            return False
    qa, qb = getattr(a, "queue", None), getattr(b, "queue", None)
    if (qa is None) != (qb is None):
        return False
    if qa is not None:
        for f in qa._fields:
            x, y = getattr(qa, f), getattr(qb, f)
            if x is None and y is None:
                continue
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                return False
    return True


# ---------------------------------------------------------------------------
# FaultPlan: validation, lowering, generation, determinism
# ---------------------------------------------------------------------------


def test_plan_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan(events=(FaultEvent("meteor", 0),))
    with pytest.raises(ValueError, match="chunk must be >= 0"):
        FaultPlan(events=(FaultEvent("crash", -1),))
    with pytest.raises(ValueError, match="duplicate crash"):
        FaultPlan(events=(FaultEvent("crash", 2), FaultEvent("crash", 2)))
    with pytest.raises(ValueError, match="chunk_fail count"):
        FaultPlan(events=(FaultEvent("chunk_fail", 0, 0),))


def test_plan_at_and_fingerprint():
    plan = FaultPlan(events=(FaultEvent("device_loss", 2, 2),
                             FaultEvent("chunk_fail", 2, 1),
                             FaultEvent("crash", 3)), seed=5)
    assert len(plan.at(2)) == 2
    assert plan.at(2, "device_loss")[0].count == 2
    assert plan.at(3, "crash") and not plan.at(0)
    assert plan.kinds() == ("chunk_fail", "crash", "device_loss")
    # fingerprint is stable and distinguishes seeds and events
    assert plan.fingerprint() == plan.fingerprint()
    assert plan.fingerprint() != FaultPlan(events=plan.events,
                                           seed=6).fingerprint()


def test_from_faults_lowers_scenario_dicts():
    plan = from_faults(({"kind": "device_loss", "chunk": 2, "count": 2},
                        {"kind": "chunk_fail", "chunk": 3}), seed=1)
    assert plan.events[0] == FaultEvent("device_loss", 2, 2, ())
    assert plan.events[1].count == 1
    assert plan.seed == 1


def test_generate_is_deterministic_in_seed():
    a = generate(seed=11, n_chunks=50, p_device_loss=0.3,
                 p_chunk_fail=0.3, p_corrupt=0.3, max_lost=3)
    b = generate(seed=11, n_chunks=50, p_device_loss=0.3,
                 p_chunk_fail=0.3, p_corrupt=0.3, max_lost=3)
    c = generate(seed=12, n_chunks=50, p_device_loss=0.3,
                 p_chunk_fail=0.3, p_corrupt=0.3, max_lost=3)
    assert a == b and a.n_events > 0
    assert a.events != c.events or a.seed != c.seed


def test_scenario_carries_fault_schedule():
    from repro.workloads.registry import get_scenario
    s = get_scenario("pod-loss-flash-crowd")
    plan = from_faults(s.faults)
    assert plan.at(2, "device_loss") and plan.at(3, "chunk_fail")


# ---------------------------------------------------------------------------
# ckpt hardening: latest_step / load_leaves on hostile directories
# ---------------------------------------------------------------------------


def test_latest_step_empty_and_missing(tmp_path):
    assert ckpt.latest_step(tmp_path / "nope") is None
    assert ckpt.latest_step(tmp_path) is None


def test_latest_step_skips_garbage_and_torn_writes(tmp_path):
    ckpt.save(tmp_path, 1, [np.arange(3)])
    ckpt.save(tmp_path, 2, [np.arange(3)])
    # torn write: a .tmp dir from a killed process
    (tmp_path / "step_00000003.tmp").mkdir()
    # garbage entries: stray file, malformed and non-canonical names
    (tmp_path / "step_junk").mkdir()
    (tmp_path / "step_5").mkdir()
    (tmp_path / "notes.txt").write_text("x")
    assert ckpt.latest_step(tmp_path) == 2


def test_latest_step_skips_truncated_manifest_and_missing_leaves(tmp_path):
    ckpt.save(tmp_path, 1, [np.arange(3)])
    # newest step has a truncated manifest -> must fall back to step 1
    bad = tmp_path / "step_00000002"
    bad.mkdir()
    (bad / "manifest.json").write_text('{"n_leaves": 1')
    assert ckpt.latest_step(tmp_path) == 1
    # newest step names a leaf file that is missing -> still step 1
    bad2 = tmp_path / "step_00000003"
    bad2.mkdir()
    (bad2 / "manifest.json").write_text(json.dumps(
        {"step": 3, "n_leaves": 2, "leaves": []}))
    np.save(bad2 / "0.npy", np.arange(2))
    assert ckpt.latest_step(tmp_path) == 1


def test_load_leaves_round_trip(tmp_path):
    leaves = [np.arange(4, dtype=np.int32), np.ones((2, 3), np.float64)]
    ckpt.save(tmp_path, 7, leaves)
    out = ckpt.load_leaves(tmp_path, 7)
    assert all(np.array_equal(x, y) and x.dtype == y.dtype
               for x, y in zip(leaves, out))


def test_pack_unpack_state_header_round_trip():
    arrays = {"a": np.arange(5), "b": np.ones(3, np.float32)}
    fp = run_fingerprint(strategy="sresume", n_jobs=5,
                         key=np.asarray(KEY), theta=1e-4, slots=None)
    leaves = pack_state(arrays, next_chunk=3, fingerprint=fp)
    header, back = unpack_state(leaves)
    assert header["next_chunk"] == 3
    check_fingerprint(header["fingerprint"], fp)
    assert all(np.array_equal(arrays[k], back[k]) for k in arrays)
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        check_fingerprint(header["fingerprint"],
                          dict(fp, strategy="hedge"))


# ---------------------------------------------------------------------------
# Kill/resume bit-identity (single-device flat + cluster paths)
# ---------------------------------------------------------------------------

N_JOBS, CHUNK = 48, 12                      # -> 4 chunks
JOBS = gen_jobs(n_jobs=N_JOBS, seed=3)


def _flat(key=KEY, strategy="sresume", **kw):
    return run_fleet_strategy(key, JOBS, strategy, P, chunk_jobs=CHUNK,
                              reps=2, **kw)


@pytest.mark.parametrize("k", [0, 1, 2, 3])
def test_crash_resume_bit_identity_every_chunk(tmp_path, k):
    """Crash after chunk k's checkpoint commits, resume in a fresh
    checkpointer: metrics bitwise equal to the uninterrupted run — for
    every possible crash boundary, including the final chunk."""
    base = _flat()
    plan = FaultPlan(events=(FaultEvent("crash", k),))
    cfg = CheckpointConfig(directory=tmp_path)
    with pytest.raises(SimulatedCrash) as ei:
        _flat(chaos=ChaosContext(plan), checkpoint=cfg)
    assert ei.value.chunk == k
    out = resume_fleet(KEY, JOBS, "sresume", P, chunk_jobs=CHUNK, reps=2,
                       chaos=ChaosContext(plan), checkpoint=cfg)
    assert outputs_equal(base, out)


@pytest.mark.parametrize("strategy", names())
def test_crash_resume_every_strategy(tmp_path, strategy):
    """The recovery contract holds for every registered strategy."""
    base = _flat(strategy=strategy)
    plan = FaultPlan(events=(FaultEvent("crash", 1),))
    cfg = CheckpointConfig(directory=tmp_path, use_async=False)
    with pytest.raises(SimulatedCrash):
        _flat(strategy=strategy, chaos=ChaosContext(plan), checkpoint=cfg)
    out = resume_fleet(KEY, JOBS, strategy, P, chunk_jobs=CHUNK, reps=2,
                       chaos=ChaosContext(plan), checkpoint=cfg)
    assert outputs_equal(base, out)


def test_retry_and_corruption_are_invisible_and_deterministic():
    """Injected launch failures and NaN corruption retry to a clean,
    bit-identical result; two executions of the same plan produce the
    same audit log."""
    base = _flat()
    plan = FaultPlan(events=(FaultEvent("chunk_fail", 1, 2),
                             FaultEvent("corrupt", 2, 1)), seed=9)
    ctx1 = ChaosContext(plan, backoff_base=0.0)
    out1 = _flat(chaos=ctx1)
    ctx2 = ChaosContext(plan, backoff_base=0.0)
    out2 = _flat(chaos=ctx2)
    assert outputs_equal(base, out1) and outputs_equal(base, out2)
    assert ctx1.records == ctx2.records
    kinds = [k for _, k, _ in ctx1.records]
    assert kinds.count("retry") == 3 and kinds.count("corrupt") == 1


@pytest.mark.parametrize("path", ["fused", "staged", "cluster"])
def test_retry_reads_no_donated_buffer(path):
    """A retried chunk re-runs its thunk on the same input arrays, so a
    chunk program must not donate them. XLA:CPU honours donation (checked
    first), so a donating chunk program fails this test here with
    "Array has been deleted"; on the chip, chip_smoke.py phase (e) runs
    the same retry."""
    import jax.numpy as jnp
    x = jnp.ones((8,))
    jax.jit(lambda v: v + 1, donate_argnums=0)(x)
    assert x.is_deleted()

    plan = FaultPlan(events=(FaultEvent("chunk_fail", 0),
                             FaultEvent("corrupt", 1)), seed=5)
    if path == "cluster":
        def run(**kw):
            return run_cluster_fleet_strategy(KEY, JOBS, "sresume", P,
                                              slots=300, chunk_jobs=CHUNK,
                                              **kw)
    else:
        # reps=1: the job-level block columns then match an output's
        # shape, so a donating program would really reuse (delete) them
        def run(**kw):
            return run_fleet_strategy(KEY, JOBS, "sresume", P,
                                      chunk_jobs=CHUNK, reps=1,
                                      fused=path == "fused", **kw)
    ctx = ChaosContext(plan, backoff_base=0.0)
    out = run(chaos=ctx)
    assert [k for _, k, _ in ctx.records].count("retry") == 2
    assert outputs_equal(run(), out)


def test_empty_plan_matches_chaos_off():
    base = _flat()
    out = _flat(chaos=ChaosContext(EMPTY_PLAN))
    assert outputs_equal(base, out)


def test_exhausted_retries_surface():
    plan = FaultPlan(events=(FaultEvent("chunk_fail", 0, 5),))
    with pytest.raises(ChaosExhausted):
        _flat(chaos=ChaosContext(plan, max_attempts=3, backoff_base=0.0))


def test_backoff_schedule_is_exponential():
    sleeps = []
    plan = FaultPlan(events=(FaultEvent("chunk_fail", 0, 3),))
    ctx = ChaosContext(plan, max_attempts=5, backoff_base=0.1,
                       sleep=sleeps.append)
    _flat(chaos=ctx)
    assert sleeps == pytest.approx([0.1, 0.2, 0.4])


def test_checkpoint_cadence_and_retention(tmp_path):
    """every=2 halves the saves; keep=2 bounds retention via gc_old; the
    final chunk always checkpoints."""
    cfg = CheckpointConfig(directory=tmp_path, every=2, keep=2,
                           use_async=False)
    _flat(checkpoint=cfg)
    steps = sorted(p.name for p in tmp_path.iterdir())
    assert steps == ["step_00000002", "step_00000004"]
    assert ckpt.latest_step(tmp_path) == 4


def test_resume_refuses_fingerprint_mismatch(tmp_path):
    cfg = CheckpointConfig(directory=tmp_path)
    plan = FaultPlan(events=(FaultEvent("crash", 1),))
    with pytest.raises(SimulatedCrash):
        _flat(chaos=ChaosContext(plan), checkpoint=cfg)
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        resume_fleet(KEY, JOBS, "hedge", P, chunk_jobs=CHUNK, reps=2,
                     chaos=ChaosContext(plan), checkpoint=cfg)


def test_resume_without_checkpoint_rejected():
    with pytest.raises(ValueError, match="requires a checkpoint"):
        _flat(resume=True)


def test_cluster_crash_resume_with_slot_change(tmp_path):
    """Finite-capacity path: the slot pool shrinks at window 1, the run
    crashes after window 2, and the resume — including queue metrics and
    per-window slots — is bitwise equal to the uninterrupted faulted
    run."""
    kw = dict(slots=40, chunk_jobs=CHUNK, reps=2)
    events = (FaultEvent("slot_change", 1, -10),
              FaultEvent("chunk_fail", 2, 1))
    ref = run_cluster_fleet_strategy(
        KEY, JOBS, "sresume", P,
        chaos=ChaosContext(FaultPlan(events=events), backoff_base=0.0),
        **kw)
    plan = FaultPlan(events=events + (FaultEvent("crash", 2),))
    cfg = CheckpointConfig(directory=tmp_path)
    with pytest.raises(SimulatedCrash):
        run_cluster_fleet_strategy(
            KEY, JOBS, "sresume", P, chaos=ChaosContext(plan,
                                                        backoff_base=0.0),
            checkpoint=cfg, **kw)
    out = resume_cluster_fleet(
        KEY, JOBS, "sresume", P, checkpoint=cfg,
        chaos=ChaosContext(plan, backoff_base=0.0), **kw)
    # slot_change moves windows 1+ to the smaller pool
    ctx = ChaosContext(plan)
    ctx.bind(4, None, 2, slots=40)
    assert [ctx.slots_at(ci, 40) for ci in range(4)] == [40, 30, 30, 30]
    assert outputs_equal(ref, out)


def test_run_all_fleet_scenario_plan_smoke():
    """run_all_fleet picks up a scenario's declared fault schedule and
    completes on a single-device host (device_loss degrades to a no-op
    there; chunk_fail still retries)."""
    from repro.fleet import run_all_fleet
    from repro.workloads.registry import get_scenario, register
    register(get_scenario("pod-loss-flash-crowd")._replace(
        name="pod-loss-mini", n_jobs=48), replace=True)
    outs, r_min = run_all_fleet(
        KEY, "pod-loss-mini", P,
        strategies=("hadoop_ns", "sresume"), chunk_jobs=12, block_jobs=12)
    assert set(outs) == {"hadoop_ns", "sresume"}
    assert np.isfinite(float(outs["sresume"].result.pocd))


# ---------------------------------------------------------------------------
# ElasticGovernor: pure schedule + tail re-solve composition
# ---------------------------------------------------------------------------


def test_governor_schedule_pure_and_compounding():
    plan = FaultPlan(events=(FaultEvent("device_loss", 1, 2),
                             FaultEvent("device_loss", 3, 2)))
    gov = ElasticGovernor(alpha=1.0)
    sc = gov.schedule(plan, 5, 8)
    assert np.allclose(sc, [1.0, 8 / 6, 8 / 6, 8 / 4, 8 / 4])
    # pure: same inputs, same schedule, no state consumed
    assert np.array_equal(sc, gov.schedule(plan, 5, 8))
    sqrt = ElasticGovernor(alpha=0.5)
    assert np.allclose(sqrt.schedule(plan, 5, 8), np.sqrt(sc))


def test_governor_resolves_tail_at_new_price():
    from repro.obs.tail import TailGovernor
    tail = TailGovernor(deadline=60.0, n_tasks=200, price=1.0,
                        min_samples=8)
    rng = np.random.default_rng(0)
    for x in 10.0 * rng.pareto(1.5, size=64) + 10.0:
        tail.observe(float(x))
    gov = ElasticGovernor(alpha=1.0, tail=tail)
    gov.on_capacity(2, alive=4, base_devices=8, scale=2.0)
    assert tail.price == pytest.approx(2.0)
    assert gov.decision is not None and gov.decision.r_opt >= 0
    assert gov.history == [(2, 4, 2.0)]


def test_cost_scale_re_solves_not_yet_dispatched_chunks():
    """With a governor, chunks after the loss solve r* at the scaled
    cost: the solved r* for later chunks must not exceed the unfaulted
    one (speculation gets more expensive), and chunks before the loss
    are untouched."""
    base = _flat(strategy="hedge")
    plan = FaultPlan(events=(FaultEvent("device_loss", 2, 4),))
    # base_devices=8 models the logical cluster capacity (the 1-device
    # test host cannot express the loss physically, the price can)
    ctx = ChaosContext(plan,
                       governor=ElasticGovernor(alpha=1.0, base_devices=8))
    out = _flat(strategy="hedge", chaos=ctx)
    r_base = np.asarray(base.r_opt).reshape(4, -1)
    r_out = np.asarray(out.r_opt).reshape(4, -1)
    assert np.array_equal(r_base[:2], r_out[:2])
    assert np.all(r_out[2:] <= r_base[2:])
    assert ctx.cost_scale(1) == 1.0 and ctx.cost_scale(2) == 2.0


# ---------------------------------------------------------------------------
# Mesh shrink (multi-device lane)
# ---------------------------------------------------------------------------


@multi_device
def test_shrink_mesh_non_contiguous_failed_ids():
    """runtime.elastic.shrink_mesh with explicit failed ids drops whole
    data-rows containing them — model groups stay intact even for
    non-contiguous loss."""
    from repro.runtime.elastic import shrink_mesh
    devs = jax.devices()[:8]
    # (data=4, model=2) grid; lose devices 1 and 6 -> rows 0 and 3 die
    m = shrink_mesh(devs, data=4, model=2, failed=[1, 6])
    assert m.devices.shape == (2, 2)
    ids = [d.id for d in m.devices.reshape(-1)]
    assert ids == [2, 3, 4, 5]
    # legacy trailing-loss path unchanged
    m2 = shrink_mesh(devs, data=4, model=2, lost=2)
    assert m2.devices.shape == (3, 2)
    with pytest.raises(ValueError, match="not in the mesh"):
        shrink_mesh(devs, data=4, model=2, failed=[99])
    with pytest.raises(RuntimeError, match="not enough devices"):
        shrink_mesh(devs, data=4, model=2, failed=[0, 2, 4, 6])


@multi_device
def test_shrink_fleet_mesh_non_contiguous():
    from repro.fleet import fleet_mesh
    from repro.fleet.mesh import shrink_fleet_mesh
    mesh = fleet_mesh(devices=8, reps=2)
    out = shrink_fleet_mesh(mesh, failed=[2, 5], reps=2)
    assert out.devices.size == 6
    assert [d.id for d in out.devices.reshape(-1)] == [0, 1, 3, 4, 6, 7]
    assert shrink_fleet_mesh(mesh, failed=[], reps=2) is mesh
    with pytest.raises(RuntimeError, match="no devices survive"):
        shrink_fleet_mesh(fleet_mesh(devices=1), failed=[0])


@multi_device
def test_device_loss_shrink_is_bitwise_invisible(tmp_path):
    """8 -> 6 -> 4 devices across chunk boundaries (non-contiguous ids),
    plus a crash + resume on the shrunken mesh: metrics bitwise equal to
    the run that never lost a device."""
    from repro.fleet import fleet_mesh
    mesh = fleet_mesh(devices=8, reps=2)
    base = run_fleet_strategy(KEY, JOBS, "sresume", P, mesh=mesh,
                              chunk_jobs=CHUNK, reps=2)
    plan = FaultPlan(events=(
        FaultEvent("device_loss", 1, device_ids=(3, 6)),
        FaultEvent("device_loss", 2, 2),
        FaultEvent("crash", 2),
    ))
    cfg = CheckpointConfig(directory=tmp_path)
    ctx = ChaosContext(plan)
    with pytest.raises(SimulatedCrash):
        run_fleet_strategy(KEY, JOBS, "sresume", P, mesh=mesh,
                           chunk_jobs=CHUNK, reps=2, chaos=ctx,
                           checkpoint=cfg)
    shrink_logs = [d for c, k, d in ctx.records if k == "device_loss"]
    assert any("alive=6" in d for d in shrink_logs)
    assert any("alive=4" in d for d in shrink_logs)
    out = resume_fleet(KEY, JOBS, "sresume", P, mesh=mesh,
                       chunk_jobs=CHUNK, reps=2,
                       chaos=ChaosContext(plan), checkpoint=cfg)
    assert outputs_equal(base, out)
