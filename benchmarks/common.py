"""Shared helpers for the paper-figure benchmarks.

Every ``fig*.py`` module exposes ``run() -> List[Row]``; a Row is
``(name, value, derived)`` where ``name`` identifies the measurement,
``value`` is the primary number, and ``derived`` carries the comparison
against the paper's claim (or context). ``benchmarks.run`` aggregates all
figures into one CSV.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from repro.sim import JobSpec, faults
from repro.sim.runner import slowdown

Row = Tuple[str, float, str]

_ROOT = Path(__file__).resolve().parent.parent
BENCH_JSON = _ROOT / "BENCH_scale.json"

# Shared shape of the perf_scale / perf_shuffle sweeps: both run the same
# proportionally-sized job (so their BENCH_scale.json payloads compare),
# differing only in what they measure.
SCALE_SIZES_QUICK = (20, 100, 500)
SCALE_SIZES_FULL = (20, 100, 500, 1000)
# The kernelized-drain tier (ISSUE 7): batch vs kernel only — the rescan
# and event substrates are structurally unusable at this size, and the
# job cannot finish inside any tractable sim window (reduces cap at 32),
# so the tier runs the same capped observation window as the main sweep.
SCALE_SIZE_XL = 10_000
SCALE_N_CONTAINERS = 8
SCALE_SPLITS_PER_WORKER = 4    # job size scales with the cluster
SCALE_SIM_SECONDS_QUICK = 120.0
SCALE_SIM_SECONDS_FULL = 240.0


def drain_seconds(reg) -> float:
    """Drain wall accumulated by ``repro.obs.instrument_drain`` (which
    retired PR 7's local ``attach_drain_timer``); 0.0 when the substrate
    has no calendar lane."""
    return float(reg.snapshot().get("drain_s", 0.0))


def bench_quick() -> bool:
    return os.environ.get("REPRO_BENCH_QUICK", "") not in ("", "0")


def bench_json_update(name: str, payload: Dict, *, mode: str) -> Path:
    """Merge one benchmark's latest payload into ``BENCH_scale.json``.

    Schema 2 is a per-benchmark map with a shared bounded history:
    ``{"schema": 2, "benchmarks": {name: payload}, "history": [...]}``.
    The previous payload for ``name`` is pushed into history; a schema-1
    file (PR 1's single perf_scale payload) is migrated in place."""
    payload = dict(payload)
    payload.update({"benchmark": name, "generated_unix": int(time.time()),
                    "cpu_count": os.cpu_count(), "mode": mode})
    doc = {"schema": 2, "benchmarks": {}, "history": []}
    if BENCH_JSON.exists():
        try:
            prev = json.loads(BENCH_JSON.read_text())
            if prev.get("schema") == 2:
                doc["benchmarks"] = prev.get("benchmarks", {})
                doc["history"] = prev.get("history", [])
            else:  # schema 1: one perf_scale payload with embedded history
                hist = prev.pop("history", [])
                prev.setdefault("benchmark", "perf_scale")
                doc["history"] = hist + [prev]
        except (json.JSONDecodeError, OSError):
            pass
    old = doc["benchmarks"].get(name)
    if old is not None:
        doc["history"].append(old)
    doc["history"] = doc["history"][-20:]
    doc["benchmarks"][name] = payload
    BENCH_JSON.write_text(json.dumps(doc, indent=2) + "\n")
    return BENCH_JSON


# Process fan-out for the sweep grids (benches × fracs × seeds). Each cell
# is an independent deterministic simulation, so they parallelize
# perfectly; per-process LRU caches keep the fault-free baselines shared
# within a worker. REPRO_BENCH_PROCS=1 forces the serial path.
_BENCH_PROCS = int(os.environ.get("REPRO_BENCH_PROCS",
                                  str(os.cpu_count() or 1)))

# Small representative subset of the suite for the heavier sweeps; the
# overall figures use more benches. Chosen to span the MOF-ratio axis.
FAST_BENCHES = ("terasort", "wordcount", "grep", "aggregation")
SUITE = ("terasort", "wordcount", "secondarysort", "grep", "aggregation",
         "join", "kmeans", "pagerank", "scan", "sort")

CRASH_FRACS = (0.1, 0.4, 0.7, 1.0)   # paper: 10 %..100 % of map progress
SEEDS = (1, 2)


def crash_fault(frac: float) -> Callable:
    def f(sim, job):
        faults.crash_busiest_node_at_map_progress(sim, job, frac)
    return f


def mof_fault(frac: float) -> Callable:
    def f(sim, job):
        faults.lose_mof_at_map_progress(sim, job, frac)
    return f


def delay_fault(at: float, factor: float = 0.05,
                duration: float = 180.0) -> Callable:
    # factor strictly below GlanceConfig.threshold_slowdown (0.1): Eq. 3
    # is a strict inequality, so a slowdown exactly AT the threshold is
    # by definition not a straggler.
    def f(sim, job):
        # slow the node hosting the most of the job's work
        def fire():
            counts = {}
            for t in job.maps:
                for a in t.running_attempts():
                    counts[a.node_id] = counts.get(a.node_id, 0) + 1
            victim = max(sorted(counts), key=lambda n: counts[n]) \
                if counts else sim.cluster.node_ids[0]
            sim.set_node_speed(victim, factor)
            sim.engine.after(duration, sim.set_node_speed, victim, 1.0)
        sim.engine.at(at, fire)
    return f


def _slowdown_cell(cell) -> float:
    """One grid cell, executed in a worker process. ``fault_for`` must be a
    module-level factory (crash_fault/mof_fault/...) so it pickles by
    reference; the fault closure itself is built inside the worker."""
    policy, bench, input_gb, frac, seed, fault_for, policy_kwargs = cell
    sd, _ = slowdown(policy, JobSpec("j0", bench, input_gb),
                     fault_for(frac), seed=seed, **policy_kwargs)
    return sd


def avg_slowdown(policy: str, input_gb: float, fault_for,
                 benches: Sequence[str] = FAST_BENCHES,
                 fracs: Sequence[float] = CRASH_FRACS,
                 seeds: Sequence[int] = SEEDS,
                 **policy_kwargs) -> Tuple[float, List[float]]:
    """Average slowdown over benches × fault-points × seeds.

    The grid fans out over a process pool (bench-major result order is
    preserved); anything unpicklable in the request — a closure fault
    factory, a ``policy_factory`` — falls back to the serial path.
    """
    grid = [(policy, bench, input_gb, frac, seed, fault_for, policy_kwargs)
            for bench in benches for frac in fracs for seed in seeds]
    sds = _run_grid(grid)
    return float(np.mean(sds)), sds


def _run_grid(grid) -> List[float]:
    workers = min(_BENCH_PROCS, len(grid))
    if workers > 1 and _grid_picklable(grid):
        import concurrent.futures as cf
        import multiprocessing as mp
        # Spawned, never forked: the caller may already hold JAX (and the
        # chip). A broken pool raises.
        with cf.ProcessPoolExecutor(
                max_workers=workers,
                mp_context=mp.get_context("spawn")) as ex:
            return list(ex.map(_slowdown_cell, grid))
    return [_slowdown_cell(cell) for cell in grid]


def _grid_picklable(grid) -> bool:
    import pickle
    try:
        pickle.dumps(grid[0])
        return True
    except Exception:
        return False


def vs_paper(measured: float, paper: float) -> str:
    return f"paper={paper:g} measured={measured:.2f}"
