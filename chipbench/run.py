#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

From the root of a checkout. The cell (``BENCHMARK.json``) names a
configuration and a traffic mix; the mix names its driver. The driver's
set-up runs first (counted in ``setup_s``), then its window for
``--seconds``, then the check of what the window produced against the
plain reference, outside the window. With ``--trace 1`` the window runs
under the profiler and the run reports the cell's per-layer metrics,
else its end-to-end metrics.

Earlier lines of standard output carry notes (warm-up, window counts,
compiles in the window, peak device memory); the last line is one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (with ``--trace 1`` also ``breakdown``) and, last, ``checks``:
each number compared beside its limit, which also closes standard error.

Without a TPU, with fewer chips than the cell asks for, or outside a
checkout of the repository, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "chipbench_out" / "trace"

from chipbench import harness  # noqa: E402


class NoResult(Exception):
    """The run cannot produce a result; exit non-zero, print none."""


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _device(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoResult(f"no TPU: JAX found {devs[0].platform}")
    if require_chip and len(devs) < chips:
        raise NoResult(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes() -> int:
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks) if peaks else 0


def _program_path() -> None:
    """The program lives in the checkout's ``src``, the benchmark beside
    it. Without the program there is no run."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise NoResult(f"no program at {src}: run from a checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def main(argv=None, *, require_chip: bool = True, hooks=None,
         tweak=None, cache: bool = True) -> int:
    """``require_chip``, ``hooks``, ``tweak`` and ``cache`` are seams for
    the CPU tests (a tiny size, a broken timed path, no persistent cache
    in a shared test process); the command line always requires the
    chip."""
    args = _args(argv)
    try:
        _program_path()
        manifest = harness.load_manifest(ROOT)
        cell, config, traffic = harness.find_cell(manifest, args.workload,
                                                  ROOT)
        limits = harness.load_limits(args.workload)
        if tweak is not None:
            config, traffic = tweak(config, traffic)
        if cache:
            # The benchmark's own compile cache, inside the checkout: the
            # program takes the directory it is given.
            os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        # The TPU runtime would log to a fixed path under /tmp.
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        device = _device(int(cell["chips"]), require_chip)
    except (NoResult, harness.ManifestError, FileNotFoundError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax
    from chipbench import trace as tr
    from repro.compile_cache import CompileCounter, enable
    if cache:
        CACHE_DIR.mkdir(exist_ok=True)
        enable()
    CompileCounter.install()

    run = harness.Run(args.workload, args.seed, args.seconds,
                      bool(args.trace), hooks)
    run.device_kind = device["kind"]
    run.device_count = int(cell["chips"])
    run.config = config
    driver = harness.load_driver(traffic["driver"])
    state = driver.setup(run, config, traffic)
    setup_s = time.perf_counter() - T_START
    run.e2e["setup_s"] = setup_s

    log_dir = None
    if run.trace:
        log_dir = TRACE_DIR / f"{args.workload}-{args.seed}"
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    c0 = CompileCounter.snapshot()
    with harness.span("window"):
        driver.window(run, state, args.seconds, traffic)
    compiled = CompileCounter.since(c0)
    if run.trace:
        jax.profiler.stop_trace()
        run.trace_summary = tr.reduce_dir(str(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    peak = _peak_bytes()
    run.note(f"window compiles: executables={compiled['executables']} "
             f"compiled={compiled['compiled']} "
             f"compile_s={compiled['compile_s']:.3f}")
    run.note(f"setup_s={setup_s:.3f} memory_peak_bytes={peak}")
    driver.release(state)
    gc.collect()
    t_check = time.perf_counter()
    driver.check(run, state, limits)
    run.note(f"check_s={time.perf_counter() - t_check:.3f}")

    metrics = {}
    for m in harness.cell_metrics(manifest, args.workload, run.trace):
        if run.trace:
            value = harness.load_metric(m["name"]).read(run)
        else:
            value = next((run.e2e[b] for b in harness.base_names(m["name"])
                          if b in run.e2e), None)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": sum(1 for *_, ok in run.checks if not ok),
           "metrics": metrics, "device": device}
    if run.trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        out["breakdown"] = run.trace_summary["breakdown"]
    out["checks"] = {name: {"value": v, "limit": lim, "ok": ok}
                     for name, v, lim, ok in run.checks}
    for name, v, lim, ok in run.checks:
        print(f"check {name}: {v!r} limit {lim!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
