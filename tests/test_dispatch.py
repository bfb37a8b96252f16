"""Multi-tenant dispatch plane (DESIGN.md §19).

Covers the PR 9 surface: capped-launch retention and the done-job
enqueue guard (the two dispatcher bugfixes), DRR fair-share properties,
bulk ≡ scalar ≡ legacy placement equivalence, the cluster-wide
speculation budget with the ``budgeted``/``clone`` policies, and the
``pacman_workload`` / ``fleet_workload`` / ``trace_workload``
generators.
"""
import os

import numpy as np
import pytest

from conftest import assert_runs_equivalent, run_traced
from repro.core.speculator import (
    BudgetedSpeculator,
    CloneSmallJobs,
    SpeculationBudget,
)
from repro.obs.trace import K_BUDGET, TraceRecorder
from repro.sim.dispatch import LaunchRequest
from repro.sim.faults import apply_script, lose_mof_at_map_progress
from repro.sim.job import JobSpec
from repro.sim.mapreduce import Simulation
from repro.sim.runner import run_workload
from repro.sim.workload import (
    FLEET_SIZES,
    PACMAN_PROBS,
    PACMAN_SIZES,
    fleet_workload,
    pacman_workload,
    trace_workload,
)

_FUZZ_EXAMPLES = int(os.environ.get("REPRO_FUZZ_EXAMPLES", "8"))


# ---------------------------------------------------------------------------
# Bugfix 1: capped requests are retained, metadata intact
# ---------------------------------------------------------------------------
def _run_until_maps_running(sim, job, until=20.0):
    sim.engine.run(until=until, stop=lambda: False)
    task = next(t for t in job.maps if t.running_attempts())
    return task


def test_capped_launch_request_retained_with_metadata():
    """A LaunchRequest against a task at max_running_attempts stays
    queued (the old pass silently dropped it) and launches with its
    rollback metadata once the cap clears."""
    sim = Simulation(policy="yarn", seed=0)
    job = sim.submit(JobSpec("j0", "terasort", 1.0))
    task = _run_until_maps_running(sim, job)
    sim._enqueue(LaunchRequest(task, speculative=True, reason="spec"))
    sim._dispatch()
    assert len(task.running_attempts()) == sim.params.max_running_attempts

    req = LaunchRequest(task, speculative=True, rollback=True,
                        rollback_node="n03", reason="rollback")
    sim._enqueue(req)
    sim._dispatch()
    assert req in sim.sched.pending, "capped request was dropped"
    assert sim.sched.has_queued(task)

    sim._kill_attempt(task.running_attempts()[0], "test")
    launched = []
    orig = sim._start_attempt
    sim._start_attempt = lambda r, nid: (launched.append(r), orig(r, nid))
    sim._dispatch()
    assert launched and launched[0] is req
    assert launched[0].rollback and launched[0].rollback_node == "n03"
    assert launched[0].reason == "rollback"
    assert not sim.sched.has_queued(task)


def test_capped_request_dropped_when_task_completes():
    """Retention is not a leak: a request held behind the cap is dropped
    once its task completes."""
    sim = Simulation(policy="yarn", seed=0)
    job = sim.submit(JobSpec("j0", "terasort", 1.0))
    task = _run_until_maps_running(sim, job)
    sim._enqueue(LaunchRequest(task, speculative=True))
    sim._dispatch()
    req = LaunchRequest(task, speculative=True, reason="stuck")
    sim._enqueue(req)
    sim._dispatch()
    assert sim.sched.has_queued(task)
    sim.run()
    assert not sim.sched.has_queued(task)
    assert sim.sched.pending == []


# ---------------------------------------------------------------------------
# Bugfix 2: enqueue against a done job is a no-op (MOF loss racing
# job completion must not mutate frozen state)
# ---------------------------------------------------------------------------
def test_enqueue_after_job_done_is_noop():
    sim = Simulation(policy="bino", seed=1)
    job = sim.submit(JobSpec("j0", "terasort", 1.0))
    sim.run()
    assert job.done
    task = job.maps[0]
    state_before = task.state
    done_before = job.n_maps_done
    assert done_before == len(job.maps)
    # a straggling re-execution request (completed-producer branch)
    sim.sched.enqueue(LaunchRequest(task, reason="late-mof"))
    assert sim.sched.pending == []
    assert not sim.sched.has_queued(task)
    assert task.state is state_before
    assert job.n_maps_done == done_before


def test_n_maps_done_never_negative_under_mof_loss_near_completion():
    """MOF loss injected at ~full map progress races job completion; the
    re-execution path must never push n_maps_done below zero."""
    for seed in range(4):
        sim = Simulation(policy="bino", seed=seed)
        job = sim.submit(JobSpec("j0", "terasort", 1.0))
        lose_mof_at_map_progress(sim, job, 0.99)
        sim.run()
        assert 0 <= job.n_maps_done <= len(job.maps), \
            (seed, job.n_maps_done)
        assert job.done


try:
    from hypothesis import given, settings, strategies as st

    @given(frac=st.floats(0.05, 0.999),
           seed=st.integers(0, 7),
           policy=st.sampled_from(["yarn", "bino"]))
    @settings(max_examples=_FUZZ_EXAMPLES, deadline=None)
    def test_hyp_n_maps_done_invariant(frac, seed, policy):
        sim = Simulation(policy=policy, seed=seed)
        job = sim.submit(JobSpec("j0", "terasort", 1.0))
        lose_mof_at_map_progress(sim, job, frac)
        sim.run()
        assert 0 <= job.n_maps_done <= len(job.maps)
except ImportError:  # pragma: no cover
    pass


# ---------------------------------------------------------------------------
# Queue plumbing
# ---------------------------------------------------------------------------
def test_pending_view_and_queued_index():
    sim = Simulation(policy="yarn", seed=0)
    j0 = sim.submit(JobSpec("j0", "terasort", 1.0))
    j1 = sim.submit(JobSpec("j1", "grep", 1.0))
    sim.sched.dispatch = lambda: None  # hold everything queued
    sim.engine.run(until=15.0, stop=lambda: False)
    pend = sim.sched.pending
    assert len(pend) == len(j0.maps) + len(j1.maps)
    # per-tenant FIFO, tenant rotation in arrival order
    assert [r.task.job.spec.job_id for r in pend] == \
        ["j0"] * len(j0.maps) + ["j1"] * len(j1.maps)
    for t in j0.maps:
        assert sim.sched.has_queued(t)
    del sim.sched.dispatch
    sim.run()
    assert sim.sched.pending == []
    assert sim.sched._queued == {}
    assert sim.sched._total == 0


def test_watchdog_does_not_double_enqueue():
    sim = Simulation(policy="yarn", seed=0)
    job = sim.submit(JobSpec("j0", "terasort", 1.0))
    task = _run_until_maps_running(sim, job)
    sim._kill_attempt(task.running_attempts()[0], "test")
    sim.sched.dispatch = lambda: None
    sim.sched.watchdog()
    sim.sched.watchdog()
    assert sum(1 for r in sim.sched.pending
               if r.task is task) == 1


# ---------------------------------------------------------------------------
# Fair-share (DRR) properties
# ---------------------------------------------------------------------------
def _grants_per_job(sim):
    counts = {}
    orig = sim._start_attempt

    def logged(req, node_id):
        jid = req.task.job.spec.job_id
        counts[jid] = counts.get(jid, 0) + 1
        return orig(req, node_id)

    sim._start_attempt = logged
    return counts


def _queued_multi_job(n_jobs, *, n_workers, n_containers, gb=1.0,
                      dispatch_opts=None, benches=("terasort",) * 8):
    """Simulation with every job's maps enqueued and dispatch held."""
    sim = Simulation(policy="yarn", seed=0, n_workers=n_workers,
                     n_containers=n_containers,
                     dispatch_opts=dispatch_opts)
    jobs = [sim.submit(JobSpec(f"j{i}", benches[i], gb))
            for i in range(n_jobs)]
    sim.sched.dispatch = lambda: None
    sim.engine.run(until=15.0, stop=lambda: False)
    del sim.sched.dispatch
    return sim, jobs


def test_drr_even_split_under_contention():
    """3 tenants × 8 queued maps, 6 free containers → 2 grants each: no
    tenant starves while holding demand with containers free."""
    sim, _ = _queued_multi_job(3, n_workers=2, n_containers=3)
    counts = _grants_per_job(sim)
    sim.sched.dispatch()
    assert counts == {"j0": 2, "j1": 2, "j2": 2}


def test_drr_uneven_demand_work_conserving():
    """A tenant with less demand than its share leaves the residual to
    the others (DRR is work-conserving): demand (1, 8, 8) over 6 slots
    → j0 gets its 1, the rest split 5 near-evenly."""
    sim, jobs = _queued_multi_job(3, n_workers=2, n_containers=3)
    keep = sim.sched._queues["j0"].popleft()
    while sim.sched._queues["j0"]:
        sim.sched._unindex(sim.sched._queues["j0"].popleft().task)
    sim.sched._queues["j0"].append(keep)
    counts = _grants_per_job(sim)
    sim.sched.dispatch()
    assert counts["j0"] == 1
    assert counts["j1"] + counts["j2"] == 5
    assert abs(counts["j1"] - counts["j2"]) <= 1


def test_drr_weights_bias_share():
    """weights={'j0': 2} gives j0 twice the per-cycle credit: 8 slots
    over tenants weighted (2, 1, 1) → (4, 2, 2)."""
    sim, _ = _queued_multi_job(
        4, n_workers=2, n_containers=4,
        dispatch_opts={"weights": {"j0": 2.0}})
    # drop j3 entirely: three tenants, 8 slots
    while sim.sched._queues["j3"]:
        sim.sched._unindex(sim.sched._queues["j3"].popleft().task)
    counts = _grants_per_job(sim)
    sim.sched.dispatch()
    assert counts == {"j0": 4, "j1": 2, "j2": 2}


def test_weights_validated():
    with pytest.raises(ValueError):
        Simulation(policy="yarn", seed=0,
                   dispatch_opts={"weights": {"j0": 0.0}})


def test_pass_stops_at_pool_exhaustion():
    """The placement pass stops once the free pool is provably spent:
    with 6 slots and 24 queued maps a pass grants exactly 6, the
    untried tail stays queued per-tenant FIFO (deficit credit is
    pass-local, so the early stop matches the full visit), and a pass
    against an exactly-full cluster is the O(nodes) skip."""
    sim, _ = _queued_multi_job(3, n_workers=2, n_containers=3)
    before = [r.task.task_id for r in sim.sched.pending]
    counts = _grants_per_job(sim)
    sim.sched.dispatch()
    assert sum(counts.values()) == 6
    left = [r.task.task_id for r in sim.sched.pending]
    assert len(left) == len(before) - 6
    for jid in ("j0", "j1", "j2"):
        kept = [t for t in left if t.startswith(f"{jid}_")]
        orig = [t for t in before if t.startswith(f"{jid}_")]
        assert kept == [t for t in orig if t in set(kept)]
    skipped = sim.sched.n_skipped_passes
    sim.sched.dispatch()
    assert sum(counts.values()) == 6  # no grant slipped through
    assert sim.sched.n_skipped_passes == skipped + 1


def test_completion_purges_queued_requests():
    """task_done/job_done purge eagerly: a queued launch for a task
    that completes (or a job that finishes) leaves the queues and the
    O(1) index immediately, not at the next placement pass."""
    sim = Simulation(policy="yarn", seed=0, n_workers=4, n_containers=2)
    job = sim.submit(JobSpec("j0", "terasort", 1.0))
    sim.engine.run(until=5.0, stop=lambda: False)
    t = job.maps[0]
    sim.sched.enqueue(LaunchRequest(t, speculative=True, reason="x"))
    assert sim.sched.has_queued(t)
    sim.sched.task_done(t)
    assert not sim.sched.has_queued(t)
    assert all(r.task is not t for r in sim.sched.pending)
    # job teardown drops the whole tenant queue
    for m in job.maps[1:3]:
        sim.sched.enqueue(LaunchRequest(m, speculative=True, reason="x"))
    sim.sched.job_done("j0")
    assert not any(r.task.job is job for r in sim.sched.pending)
    assert not sim.sched.has_queued(job.maps[1])


# ---------------------------------------------------------------------------
# Placement-pass equivalence: bulk ≡ scalar ≡ legacy
# ---------------------------------------------------------------------------
DISPATCH_VARIANTS = (
    ("default", None),
    ("bulk", {"bulk": True, "bulk_min": 1}),
    ("scalar", {"bulk": False}),
    ("legacy-fifo", {"fair": False, "bulk": False}),
)


def test_single_job_byte_identical_across_dispatch_variants():
    """The single-job default path is byte-identical whatever the
    dispatcher configuration — the §19 equivalence gate."""
    script = [("crash", 7, 0.45, 0.0)]
    fault = lambda sim, job: apply_script(sim, job, script)
    for policy in ("yarn", "bino"):
        runs, labels = [], []
        for label, opts in DISPATCH_VARIANTS:
            runs.append(run_traced("batch", policy, fault, seed=3,
                                   dispatch_opts=opts))
            labels.append(label)
        assert_runs_equivalent(runs, labels)


def test_multi_job_bulk_matches_scalar():
    """With several tenants the bulk pass must still make exactly the
    scalar pass's decisions (fair order fixed, placement vectorized)."""
    extra = (JobSpec("j1", "wordcount", 1.0, submit_time=4.0),
             JobSpec("j2", "grep", 2.0, submit_time=7.0),
             JobSpec("j3", "terasort", 1.0, submit_time=7.5))
    script = [("crash", 5, 0.5, 0.0)]
    fault = lambda sim, job: apply_script(sim, job, script)
    runs, labels = [], []
    for label, opts in (("bulk", {"bulk": True, "bulk_min": 1}),
                        ("scalar", {"bulk": False})):
        runs.append(run_traced("batch", "bino", fault, seed=2,
                               extra_jobs=extra, dispatch_opts=opts))
        labels.append(label)
    assert_runs_equivalent(runs, labels)
    assert runs[0].sim.sched.n_bulk_passes > 0
    assert runs[1].sim.sched.n_bulk_passes == 0


def test_profile_counters():
    run = run_traced("batch", "yarn", None, seed=1)
    sched = run.sim.sched
    assert sched.n_grants == len(run.launches)
    assert sched.n_decisions >= sched.n_grants
    # The first wave queues at least ``bulk_min`` launches (one bulk
    # pass); the shallower later ones take the scalar pass. The cluster
    # never fills up here, so no pass is skipped.
    assert sched.n_bulk_passes >= 1 and sched.n_scalar_passes >= 1
    assert sched.n_skipped_passes == 0


# ---------------------------------------------------------------------------
# Cluster-wide speculation budget + the budgeted/clone policies
# ---------------------------------------------------------------------------
def test_speculation_budget_meter():
    b = SpeculationBudget(2)
    assert b.capacity == 2 and b.available == 2
    assert b.admit() and b.admit() and not b.admit()
    assert (b.admitted, b.denied) == (2, 1)
    b.begin_tick(1)  # re-based on running copies, not past admissions
    assert b.available == 1
    assert b.admit() and not b.admit()
    assert SpeculationBudget(-3).capacity == 0


def test_budgeted_policy_zero_budget_never_speculates():
    specs = pacman_workload(5, seed=2, mean_interarrival=15.0)
    results = run_workload(
        "budgeted", specs, seed=4, n_workers=10, n_containers=4,
        policy_factory=lambda nodes: BudgetedSpeculator(
            budget=SpeculationBudget(0)))
    assert all(r.n_spec_attempts == 0 for r in results)


def test_clone_small_jobs_clones_upfront():
    """Small jobs get one clone per task with no straggler signal at
    all; a zero budget suppresses every clone."""
    spec = [JobSpec("j0", "terasort", 0.5)]  # 4 maps + 1 reduce ≤ 12
    cloned = run_workload("clone", spec, seed=1, n_workers=10,
                          n_containers=4)
    assert cloned[0].n_spec_attempts > 0
    starved = run_workload(
        "clone", spec, seed=1, n_workers=10, n_containers=4,
        policy_factory=lambda nodes: CloneSmallJobs(
            budget=SpeculationBudget(0)))
    assert starved[0].n_spec_attempts == 0


def test_clone_skips_large_jobs():
    """A job above the small-job threshold gets no upfront clones (LATE
    detection still applies, so pin the clone set, not spec counts)."""
    sim = Simulation(policy="clone", seed=1, n_workers=10,
                     n_containers=8)
    sim.submit(JobSpec("j0", "terasort", 4.0))  # 32 maps > 12-task cutoff
    sim.run()
    assert sim.speculator._cloned == set()


def test_budget_bounds_running_speculation():
    """At every assessment tick the number of RUNNING speculative
    copies never exceeds the budget capacity (ample containers, so
    admitted copies launch immediately)."""
    specs = [JobSpec(f"j{i}", "terasort", 0.5, submit_time=2.0 * i)
             for i in range(6)]
    sim = Simulation(policy="clone", seed=3, n_workers=20,
                     n_containers=8)
    cap = sim.speculator.budget.capacity
    assert cap > 0
    seen = []
    inner_tick = sim._speculator_tick

    def tick():
        seen.append(sim.arrays.n_running_spec())
        inner_tick()

    sim._speculator_tick = tick
    for s in specs:
        sim.submit(s)
    sim.run()
    assert seen and max(seen) <= cap
    assert sim.speculator.budget.admitted > 0


def test_budgeted_emits_budget_records():
    rec = TraceRecorder()
    script = [("slow", 2, 0.1, 0.5)]
    fault = lambda sim, job: apply_script(sim, job, script)
    run = run_traced("batch", "budgeted", fault, seed=5, obs=rec)
    ticks = rec.by_kind(K_BUDGET)
    assert len(ticks) > 0
    assert (ticks["b"] > 0).all()            # capacity recorded
    assert (ticks["f1"] <= ticks["f0"]).all()  # admitted ≤ proposed
    assert run.results[0].n_spec_attempts > 0


def test_budgeted_and_clone_obs_off_equivalence():
    """The budget policies obey the §18.2 emit-site contract: wiring
    the recorder does not perturb the trace."""
    script = [("slow", 2, 0.1, 0.5)]
    fault = lambda sim, job: apply_script(sim, job, script)
    for policy in ("budgeted", "clone"):
        a = run_traced("batch", policy, fault, seed=5)
        b = run_traced("batch", policy, fault, seed=5,
                       obs=TraceRecorder())
        assert_runs_equivalent([a, b], ["obs-off", "obs-on"])


# ---------------------------------------------------------------------------
# Workload generators (ISSUE 9 satellite: arrival-process tests)
# ---------------------------------------------------------------------------
def test_pacman_workload_deterministic_and_offsettable():
    a = pacman_workload(50, seed=3)
    assert a == pacman_workload(50, seed=3)
    assert a != pacman_workload(50, seed=4)
    shifted = pacman_workload(50, seed=3, start=100.0)
    assert all(abs((s.submit_time - t.submit_time) - 100.0) < 1e-9
               for s, t in zip(shifted, a))


def test_pacman_workload_size_mix():
    jobs = pacman_workload(4000, seed=0)
    sizes = np.array([j.input_gb for j in jobs])
    for size, p in zip(PACMAN_SIZES, PACMAN_PROBS):
        got = float(np.mean(sizes == size))
        assert abs(got - p) < 0.03, (size, got, p)
    assert all(j.submit_time > 0 for j in jobs)


def test_fleet_workload_heavy_tail_and_bursts():
    jobs = fleet_workload(2000, seed=1)
    assert jobs == fleet_workload(2000, seed=1)
    times = np.array([j.submit_time for j in jobs])
    assert (np.diff(times) >= 0).all()
    sizes = np.array([j.input_gb for j in jobs])
    assert set(np.unique(sizes)) <= set(FLEET_SIZES)
    # rank^-alpha frequencies: monotone non-increasing by rank, with
    # the smallest size clearly dominant and the tail present
    freqs = [float(np.mean(sizes == s)) for s in FLEET_SIZES]
    assert freqs[0] > 0.4
    assert freqs[-1] > 0.0
    assert all(freqs[i] >= freqs[i + 1] - 0.02
               for i in range(len(freqs) - 1))
    # MMPP over-dispersion: gap CV well above the Poisson CV of 1
    gaps = np.diff(times)
    cv = float(gaps.std() / gaps.mean())
    assert cv > 1.2, cv
    pois = np.diff([j.submit_time
                    for j in pacman_workload(2000, seed=1)])
    assert cv > float(pois.std() / pois.mean())


def test_trace_workload_sorts_and_defaults():
    jobs = trace_workload([(30.0, 2.0), (5.0, 1.0, "grep")],
                          n_reduces=3)
    assert [j.job_id for j in jobs] == ["t00000", "t00001"]
    assert jobs[0].submit_time == 5.0 and jobs[0].bench == "grep"
    assert jobs[1].bench == "terasort" and jobs[1].n_reduces == 3


def test_fleet_workload_runs_multi_tenant():
    """End-to-end: a burst of fleet jobs through every policy finishes
    with sane JCTs on all four policies."""
    specs = fleet_workload(12, seed=2, mean_interarrival=5.0,
                           burst_len=60.0, idle_len=60.0)
    for policy in ("yarn", "bino", "budgeted", "clone"):
        results = run_workload(policy, specs, seed=1, n_workers=20,
                               n_containers=4)
        assert len(results) == len(specs)
        assert all(r.jct > 0 for r in results)
