#!/usr/bin/env python3
"""Readings the limits of a cell's correctness check are set from.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 \\
        --seconds <window>

In one process, for each seed: the cell's set-up, a window of
``--seconds``, and then the numbers its check compares for the program
(the lower readings), for the control (the plain reference computed in
the next precision below the configuration's, in the program's place)
and, for a training cell, for each fault planted in the reference put in
the program's place (the upper readings). One JSON line per seed; the
benchmark's own runs never run this. Needs the chip, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402


def readings(workload: str, seed: int, seconds: float, *,
             tweak=None, hooks=None) -> dict:
    manifest = harness.load_manifest(ROOT)
    _cell, config, traffic = harness.find_cell(manifest, workload, ROOT)
    if tweak is not None:
        config, traffic = tweak(config, traffic)
    limits = harness.load_limits(workload)
    driver = harness.load_driver(traffic["driver"])
    run = harness.Run(workload, seed, seconds, False, hooks)
    state = driver.setup(run, config, traffic)
    driver.window(run, state, seconds, traffic)
    driver.release(state)
    gc.collect()
    driver.check(run, state, limits)
    out = {"seed": seed, "correct": run.correct,
           "program": {n: v for n, v, _lim, _ok in run.checks}}
    out.update(driver.upper_readings(state))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"readings: no program at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import enable
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    enable()
    for s in args.seeds.split(","):
        t0 = time.perf_counter()
        out = readings(args.workload, int(s), args.seconds)
        out["wall_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
