"""Dispatch-plane benchmark (ISSUE 9; ROADMAP open item 1).

Two parts:

**Plane cost** — µs per placement decision at 1 000 and 10 000 nodes
(kernel shuffle engine, a burst of concurrent jobs so the pending
queues stay deep), comparing the multi-tenant plane's bulk placement
pass against the pre-§19 *linear* pass — the single flat pending list
rescanned per dispatch with a per-request heap query and an O(pending)
``has_queued`` — embedded here verbatim as the measurement baseline.
Acceptance gate (full mode): 10 000-node cost per decision at least
``GATE_DECISION_SPEEDUP_10K``× down vs that linear pass.

**Fleet figure** — ``fleet_workload`` bursts (heavy-tailed sizes, MMPP
arrivals; ≥ 100 concurrent jobs in full mode) through all four
policies (yarn / bino / budgeted / clone), reporting p50/p99 job
slowdown vs the per-size fault-free baseline and time-weighted fleet
utilization.

Writes the ``perf_dispatch`` payload into ``BENCH_scale.json``.

Usage:
    PYTHONPATH=src python -m benchmarks.perf_dispatch [--quick] [--full]
    PYTHONPATH=src python -m benchmarks.run --only perf_dispatch --quick
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.common import Row, bench_json_update, bench_quick
from repro.core.types import TaskKind, TaskState
from repro.sim.dispatch import Dispatcher, LaunchRequest
from repro.sim.job import JobSpec
from repro.sim.mapreduce import SimParams, Simulation
from repro.sim.runner import baseline_jct, run_workload
from repro.sim.workload import fleet_workload

# Acceptance gate (ISSUE 9): 10 000-node dispatch cost per decision at
# least this much lower on the multi-tenant plane than on the pre-§19
# linear pass. Asserted in full mode, printed in quick mode.
GATE_DECISION_SPEEDUP_10K = 2.0

FLEET_POLICIES = ("yarn", "bino", "budgeted", "clone")


# ---------------------------------------------------------------------------
# The pre-§19 pass, kept as the measurement baseline: one flat pending
# list, full rescan per dispatch, per-request heap query, O(pending)
# has_queued / watchdog set. Subclasses the plane only to inherit the
# Simulation-facing surface; every hot method is the old code plus the
# pass counters the new plane exposes.
# ---------------------------------------------------------------------------
class LegacyLinearDispatcher(Dispatcher):
    def __init__(self, sim):
        super().__init__(sim)
        self._pending: List[LaunchRequest] = []

    @property
    def pending(self) -> List[LaunchRequest]:
        return self._pending

    def enqueue(self, req: LaunchRequest) -> None:
        task = req.task
        if task.job.done:
            return  # keep the PR 9 enqueue bugfix out of the comparison
        if task.state == TaskState.COMPLETED and not req.speculative:
            if task.kind == TaskKind.MAP:
                task.job.n_maps_done -= 1
            task.state = TaskState.RUNNING
            task.output_available = bool(task.output_nodes)
            self.sim._arr_task_state(task)
        self._pending.append(req)

    def has_queued(self, task) -> bool:
        return any(r.task is task for r in self._pending)

    def task_done(self, task) -> None:
        pass  # the old plane had no eager purge — stale requests
        # lingered until the next full rescan dropped them

    def job_done(self, job_id: str) -> None:
        pass

    def dispatch(self) -> None:
        sim = self.sim
        still: List[LaunchRequest] = []
        for req in self._pending:
            task = req.task
            if task.job.done or task.state == TaskState.COMPLETED:
                continue
            if len(task.running_attempts()) >= \
                    sim.params.max_running_attempts:
                continue  # the old pass dropped capped requests
            exclude = {a.node_id for a in task.running_attempts()}
            exclude |= sim._marked_failed
            self.n_decisions += 1
            node_id = sim.cluster.pick_container(list(req.placement),
                                                 exclude=exclude)
            if node_id is None:
                still.append(req)
                continue
            self.n_grants += 1
            sim._start_attempt(req, node_id)
        self._pending = still
        self.n_scalar_passes += 1

    def watchdog(self) -> None:
        sim = self.sim
        arr = sim.arrays
        candidates = []
        if arr is not None:
            for r in arr.idle_task_rows():
                candidates.append(arr.owner(r).task)
        else:
            for job in sim.active_jobs.values():
                for t in job.tasks:
                    if t.state == TaskState.RUNNING \
                            and not t.running_attempts():
                        candidates.append(t)
        if candidates:
            queued = {r.task.task_id for r in self._pending}
            for t in candidates:
                if t.kind == TaskKind.REDUCE \
                        and not t.job.reduces_scheduled:
                    continue
                if t.task_id not in queued:
                    self.enqueue(LaunchRequest(t, reason="am-watchdog"))
        self.dispatch()


# ---------------------------------------------------------------------------
# Part A: plane cost per decision
# ---------------------------------------------------------------------------
def _timed(obj, name: str) -> Dict[str, float]:
    """Accumulate the wall seconds of every call of ``obj.<name>`` into
    the returned ``{"s": ...}``."""
    wall = {"s": 0.0}
    inner = getattr(obj, name)

    def timed(*args):
        t0 = time.perf_counter()
        try:
            return inner(*args)
        finally:
            wall["s"] += time.perf_counter() - t0

    setattr(obj, name, timed)
    return wall


def _burst_specs(n_workers: int) -> List[JobSpec]:
    """A same-instant burst of concurrent jobs sized to ~4 map splits
    per worker in total (PR 7's proportional shape, split across
    tenants so the multi-tenant plane actually rotates)."""
    n_jobs = max(8, n_workers // 50)
    maps_per_job = max(1, 4 * n_workers // n_jobs)
    gb = maps_per_job / 8.0            # 8 × 128 MiB splits per GB
    return [JobSpec(f"b{i:04d}", "terasort", gb, n_reduces=2)
            for i in range(n_jobs)]


def measure_plane(n_workers: int, plane: str, *, sim_seconds: float,
                  seed: int = 0) -> Dict:
    """Kernel-mode burst with 2 containers/worker — demand is 2× the
    slot count, so pending queues stay deep and the cluster sits full
    (the PR 7 profile's regime). The dispatch wall brackets every
    placement pass; attempt *construction* (``_start_attempt``) is
    identical under both planes and timed out of the metric."""
    params = dataclasses.replace(SimParams(), sim_time_cap=sim_seconds)
    sim = Simulation(policy="yarn", seed=seed, n_workers=n_workers,
                     n_containers=2, params=params, shuffle="kernel")
    if plane == "legacy":
        sim.sched = LegacyLinearDispatcher(sim)
    # Both wrappers sit on the instances, so every caller (the sim and
    # the dispatcher's own watchdog) goes through them.
    dispatch = _timed(sim.sched, "dispatch")
    construct = _timed(sim, "_start_attempt")
    for spec in _burst_specs(n_workers):
        sim.submit(spec)
    t0 = time.perf_counter()
    sim.run()
    wall = time.perf_counter() - t0
    sched = sim.sched
    plane_wall = max(dispatch["s"] - construct["s"], 1e-9)
    # The comparable unit is the granted launch — both planes issue the
    # same ~N grants for this workload. Normalizing by placement
    # *attempts* would flatter the legacy pass, which burns millions of
    # keep-churn rescans per grant (reported as `attempts` below); the
    # new plane's early-stop visits only what it can place.
    us = 1e6 * plane_wall / max(sched.n_grants, 1)
    return {
        "n_workers": n_workers,
        "plane": plane,
        "n_jobs": len(_burst_specs(n_workers)),
        "sim_seconds": sim_seconds,
        "attempts": sched.n_decisions,
        "grants": sched.n_grants,
        "bulk_passes": sched.n_bulk_passes,
        "scalar_passes": sched.n_scalar_passes,
        "skipped_passes": sched.n_skipped_passes,
        "dispatch_wall_s": round(plane_wall, 4),
        "construct_wall_s": round(construct["s"], 4),
        "us_per_decision": round(us, 3),
        "wall_s": round(wall, 3),
    }


# ---------------------------------------------------------------------------
# Part B: fleet figure
# ---------------------------------------------------------------------------
def _fleet_metrics(sim: Simulation, total_slots: int):
    """Wrap the assessment tick to sample fleet utilization and job
    concurrency (the tick re-schedules itself through the instance
    attribute, so the wrapper stays in the loop)."""
    samples = {"t": [], "busy": [], "jobs": []}
    inner = sim._speculator_tick

    def tick():
        free = int(sim.arrays.node_free.sum()) if sim.arrays is not None \
            else sum(n.free_containers for n in sim.cluster.nodes.values())
        samples["t"].append(sim.engine.now)
        samples["busy"].append(total_slots - free)
        samples["jobs"].append(len(sim.active_jobs))
        inner()

    sim._speculator_tick = tick
    return samples


def measure_fleet(policy: str, specs: List[JobSpec], *, n_workers: int,
                  n_containers: int, seed: int = 0) -> Dict:
    total_slots = n_workers * n_containers
    sim = Simulation(policy=policy, seed=seed, n_workers=n_workers,
                     n_containers=n_containers)
    samples = _fleet_metrics(sim, total_slots)
    for spec in specs:
        sim.submit(spec)
    t0 = time.perf_counter()
    results = sim.run()
    wall = time.perf_counter() - t0
    by_id = {s.job_id: s for s in specs}
    slowdowns = sorted(
        r.jct / baseline_jct(by_id[r.job_id].bench,
                             by_id[r.job_id].input_gb, seed=seed,
                             n_workers=n_workers,
                             n_containers=n_containers)
        for r in results)
    t = np.asarray(samples["t"])
    busy = np.asarray(samples["busy"], dtype=np.float64)
    if len(t) > 1:
        dt = np.diff(t)
        util = float((busy[:-1] * dt).sum() / (total_slots * dt.sum()))
    else:
        util = 0.0
    return {
        "policy": policy,
        "n_jobs": len(specs),
        "n_workers": n_workers,
        "n_containers": n_containers,
        "finished": len(results),
        "max_concurrent_jobs": int(max(samples["jobs"], default=0)),
        "utilization": round(util, 4),
        "p50_slowdown": round(float(np.percentile(slowdowns, 50)), 3),
        "p99_slowdown": round(float(np.percentile(slowdowns, 99)), 3),
        "mean_slowdown": round(float(np.mean(slowdowns)), 3),
        "spec_attempts": int(sum(r.n_spec_attempts for r in results)),
        "wall_s": round(wall, 3),
    }


def run() -> List[Row]:
    quick = bench_quick()
    rows: List[Row] = []
    # -- Part A: µs/decision, bulk plane vs the linear pass ------------
    plane_sizes = (1000,) if quick else (1000, 10_000)
    sim_seconds = 60.0 if quick else 120.0
    plane_results: List[Dict] = []
    speedup_10k: Optional[float] = None
    for n in plane_sizes:
        bulk = measure_plane(n, "bulk", sim_seconds=sim_seconds)
        legacy = measure_plane(n, "legacy", sim_seconds=sim_seconds)
        plane_results.extend([bulk, legacy])
        speedup = legacy["us_per_decision"] / \
            max(bulk["us_per_decision"], 1e-9)
        rows.append((
            f"perf_dispatch/{n}n_us_per_decision",
            bulk["us_per_decision"],
            f"linear={legacy['us_per_decision']:.3g}us "
            f"speedup={speedup:.2f}x "
            f"(dispatch wall {bulk['dispatch_wall_s']:.3g}s vs "
            f"{legacy['dispatch_wall_s']:.3g}s)"))
        if n == 10_000:
            speedup_10k = speedup
            rows.append((
                "perf_dispatch/10000n_decision_speedup", speedup,
                f"gate: >={GATE_DECISION_SPEEDUP_10K:g}x over the "
                f"linear pass"))
    if speedup_10k is not None \
            and speedup_10k < GATE_DECISION_SPEEDUP_10K:
        raise AssertionError(
            f"dispatch-plane 10k gate failed: {speedup_10k:.2f}x < "
            f"{GATE_DECISION_SPEEDUP_10K}x per decision vs linear pass")
    # -- Part B: fleet slowdown + utilization --------------------------
    n_fleet = 40 if quick else 150
    fleet_workers, fleet_containers = 100, 8
    specs = fleet_workload(n_fleet, seed=11, mean_interarrival=1.0,
                           burst_factor=8.0, burst_len=120.0,
                           idle_len=120.0)
    fleet_results: List[Dict] = []
    for policy in FLEET_POLICIES:
        r = measure_fleet(policy, specs, n_workers=fleet_workers,
                          n_containers=fleet_containers)
        fleet_results.append(r)
        rows.append((
            f"perf_dispatch/fleet_{policy}_p99_slowdown",
            r["p99_slowdown"],
            f"p50={r['p50_slowdown']} util={r['utilization']} "
            f"max_concurrent={r['max_concurrent_jobs']} "
            f"spec={r['spec_attempts']}"))
        if r["finished"] != len(specs):
            raise AssertionError(
                f"fleet run incomplete: {policy} finished "
                f"{r['finished']}/{len(specs)}")
    if not quick:
        max_conc = max(r["max_concurrent_jobs"] for r in fleet_results)
        if max_conc < 100:
            raise AssertionError(
                f"fleet figure must reach >=100 concurrent jobs, "
                f"got {max_conc}")
    payload = {
        "plane": plane_results,
        "decision_speedup_10k": None if speedup_10k is None
        else round(speedup_10k, 2),
        "fleet": fleet_results,
        "fleet_n_jobs": n_fleet,
    }
    path = bench_json_update("perf_dispatch", payload,
                             mode="quick" if quick else "full")
    rows.append(("perf_dispatch/json", 1.0, str(path)))
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="1 000-node tier + a 40-job fleet")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.quick and not args.full:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    for name, value, derived in run():
        print(f"{name},{value:.4g},{derived}")


if __name__ == "__main__":
    main()
