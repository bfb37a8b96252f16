"""Benchmark aggregator: one module per paper figure and perf harness.
Prints ``name,value,derived`` CSV rows.

``--jobs N`` fans the figure modules out over N worker processes. Rows
are still printed in the canonical ``FIGS`` order (results are collected
per module and emitted in submission order), so the CSV is deterministic
regardless of completion order. Workers are spawned, never forked: the
serial modules initialise JAX in this process, and a forked child of a
process that holds the chip fails or hangs. A broken pool raises.

JAX's persistent compilation cache is on for every run
(:mod:`repro.compile_cache`).

Usage:
    PYTHONPATH=src python -m benchmarks.run             # all figures
    PYTHONPATH=src python -m benchmarks.run --only fig4a,fig9
    PYTHONPATH=src python -m benchmarks.run --jobs 4
    PYTHONPATH=src python -m benchmarks.run --only perf_scale --quick
    # shuffle-substrate rows incl. the batch fetch-plane gate (>=2x over
    # event at 1000 nodes, full sweep) merged into BENCH_scale.json:
    PYTHONPATH=src python -m benchmarks.run --only perf_shuffle
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time
import traceback
from typing import List, Tuple

FIGS = [
    "fig1_slowdown",
    "fig4a_overall",
    "fig4b_dependency",
    "fig4c_scope",
    "fig5_variance",
    "fig6_stress",
    "fig7_glance",
    "fig8_collective",
    "fig9_rollback",
    "fig_scorecard",
    "fig_predictor",
    "perf_scale",
    "perf_shuffle",
    "perf_accel",
    "perf_net",
    "perf_runtime",
    "perf_dispatch",
]

# (rows, wall seconds, error string or "")
_ModResult = Tuple[List[Tuple[str, float, str]], float, str]


def _run_module(mod_name: str, quick: bool, inner_procs: int) -> _ModResult:
    """Execute one figure module; runs in a worker process under --jobs.
    ``inner_procs`` caps the module's own sweep fan-out so nested pools
    don't oversubscribe the machine."""
    if quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    os.environ["REPRO_BENCH_PROCS"] = str(inner_procs)
    t0 = time.time()
    try:
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        rows = mod.run()
    except Exception as e:
        traceback.print_exc(file=sys.stderr)
        return [], time.time() - t0, f"{type(e).__name__}: {e}"
    return list(rows), time.time() - t0, ""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated figure prefixes (e.g. fig4a,fig9)")
    ap.add_argument("--quick", action="store_true",
                    help="bounded wall-time budget for modules that "
                         "support it (perf_scale/perf_shuffle: smaller "
                         "size sweep, shorter sim cap)")
    ap.add_argument("--jobs", type=int, default=1,
                    help="run figure modules across N processes "
                         "(CSV row order stays deterministic)")
    args = ap.parse_args()
    from repro.compile_cache import enable
    enable()
    if args.quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    selected = FIGS
    if args.only:
        keys = [k.strip() for k in args.only.split(",")]
        selected = [f for f in FIGS if any(f.startswith(k) for k in keys)]

    print("name,value,derived")
    failures = []
    jobs = max(1, args.jobs)
    # Modules that merge into BENCH_scale.json must not race each other's
    # read-modify-write; they run serially after the parallel batch.
    writers = {"fig_scorecard", "fig_predictor", "perf_scale",
               "perf_shuffle", "perf_accel", "perf_net", "perf_runtime",
               "perf_dispatch"}
    parallel = [m for m in selected if m not in writers]
    by_mod = {}
    if jobs > 1 and len(parallel) > 1:
        import concurrent.futures as cf
        import multiprocessing as mp
        inner = max(1, (os.cpu_count() or 1) // jobs)
        with cf.ProcessPoolExecutor(
                max_workers=jobs, mp_context=mp.get_context("spawn")) as ex:
            futs = {m: ex.submit(_run_module, m, args.quick, inner)
                    for m in parallel}
            by_mod = {m: f.result() for m, f in futs.items()}

    def emit(mod_name, outcome):
        rows, wall, err = outcome
        if err:
            failures.append(mod_name)
            print(f"{mod_name}/ERROR,nan,{err}", flush=True)
            return
        for name, value, derived in rows:
            print(f"{name},{value:.4g},{derived}")
        print(f"{mod_name}/wall_s,{wall:.1f},", flush=True)

    # Emit in canonical FIGS order; modules not covered by the parallel
    # batch run (and stream their rows) as this loop reaches them.
    inner = int(os.environ.get("REPRO_BENCH_PROCS",
                               str(os.cpu_count() or 1)))
    for m in selected:
        if m not in by_mod:
            by_mod[m] = _run_module(m, args.quick, inner)
        emit(m, by_mod[m])
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
