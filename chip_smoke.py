#!/usr/bin/env python3
"""Smoke test of the speculation engine's device path and the live
runtime on one TPU chip, through the entry points a user calls.

    python3 chip_smoke.py [--seed N]

Phases, in one process on one chip:

(a) paper testbed — 20 workers × 8 containers, a 10 GB terasort, the
    busiest node crashed at 40 % map progress: on the ``jax`` backend
    and on ``pallas`` (compiled), each under ``policy="bino"`` (glance,
    Eq. 4 failure masks, collective, reaping) and ``policy="yarn"``
    (LATE, reaping). Every run must finish the job.
(b) fleet — 10,000 workers × 8 containers, the proportional terasort of
    ``benchmarks/perf_accel.measure_live`` (40,000 maps; the attempt
    mirror pads to 65,536 rows) on the ``pallas`` backend, under bino
    and under yarn, each capped at ``FLEET_SIM_SECONDS`` of simulated
    time.
(c) sweep — a ``BatchedSweep`` of 32 fault scenarios on the final,
    mid-run snapshot of (b)'s bino run, against the serial numpy scoring
    of the same clones.
(d) runtime — ``TrainerRuntime`` on the full ``qwen1.5-0.5b`` (4 host
    threads sharing the chip), 3 steps fault-free, then the same steps
    under ``benchmarks.perf_runtime.CRASH_SCRIPT`` with bino recovery.
    Losses must be finite and the final parameters bit-identical.

Every assessment call of (a) and (b) is replayed on the numpy reference
over a clone of the same snapshot (:class:`repro.accel.shadow.
ShadowBackend`); a difference outside the DESIGN.md §13.3 tolerance
fails the run, and so does a method the policy calls that was never
compared. Data and weights are generated from ``--seed``.

Each phase prints one line with its result and wall time, compile time
apart. The last line is ``{"ok": true, "device": {...}}``. Without a TPU,
or outside a checkout of the repository, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

TESTBED_WORKERS = 20
TESTBED_GB = 10.0             # the paper's larger job size (§IV)
FLEET_WORKERS = 10_000
FLEET_SIM_SECONDS = 300.0
# Backend methods each policy's ticks must reach (and the shadow compare).
POLICY_METHODS = {
    "bino": ("spatial_hits", "temporal_zeta", "temporal_marks",
             "temporal_marks_zeta", "responsive", "failed", "winning",
             "reap_rows"),
    "yarn": ("late_victims", "reap_rows"),
}
SWEEP_SCENARIOS = 32
RUNTIME_ARCH = "qwen1.5-0.5b"
RUNTIME_HOSTS = 4
# Device memory of phase (d), 16 GB chip: bf16 params 0.93 GB + f32 AdamW
# moments 3.7 GB + one bf16 grad tree per buffered microbatch (0.93 GB
# each, 4 hosts × 1 here) + the f32 reduced tree 1.86 GB, then the new
# state 4.6 GB once the trees are released; the rematerialized step adds
# about 1 GB of temporaries per microbatch in flight.
RUNTIME_MICROBATCHES = 1      # per shard
RUNTIME_SEQ_LEN = 512
RUNTIME_BATCH = 2             # sequences per microbatch
RUNTIME_REMAT = "full"
RUNTIME_STEPS = 3


def log(msg: str) -> None:
    print(msg, flush=True)


def _tally_line(tally) -> str:
    parts = [f"{m}={c['equal']}/{c['tolerated']}/{c['outside']}"
             for m, c in sorted(tally.counts.items())]
    return " ".join(parts)


class Phase:
    """Wall time of one phase, with the compile time inside it apart."""

    def __enter__(self):
        from repro.compile_cache import CompileCounter
        self.t0 = time.perf_counter()
        self.c0 = CompileCounter.snapshot()
        return self

    def __exit__(self, *exc):
        from repro.compile_cache import CompileCounter
        self.wall = time.perf_counter() - self.t0
        self.compiles = CompileCounter.since(self.c0)
        return False

    def timing(self) -> str:
        c = self.compiles
        return (f"wall_s={self.wall:.2f} compile_s={c['compile_s']:.2f} "
                f"executables={c['executables']} "
                f"cache_hits={c['cache_hits']}")


def _check_tally(name, tally, methods) -> None:
    if tally.outside():
        raise AssertionError(
            f"{name}: {tally.outside()} device-vs-numpy differences outside "
            f"the §13.3 tolerance: {tally.examples}")
    missing = [m for m in methods if not sum(tally.counts.get(m, {}).values())]
    if missing:
        raise AssertionError(f"{name}: never compared: {missing}")


# ---------------------------------------------------------------------------
# Phases (sizes are arguments so a CPU test can run them small)
# ---------------------------------------------------------------------------
def phase_testbed(backend: str, policy: str, *, seed: int = 0,
                  n_workers: int = TESTBED_WORKERS,
                  input_gb: float = TESTBED_GB) -> dict:
    from benchmarks.common import crash_fault
    from repro.accel.shadow import ShadowBackend
    from repro.sim import JobSpec, Simulation
    name = f"testbed/{backend}/{policy}"
    shadow = ShadowBackend(backend)
    sim = Simulation(policy=policy, seed=seed, n_workers=n_workers,
                     n_containers=8, assess_backend=shadow)
    job = sim.submit(JobSpec("j0", "terasort", input_gb))
    crash_fault(0.4)(sim, job)
    results = sim.run()
    if not results or results[0].job_id != "j0":
        raise AssertionError(f"{name}: the job did not finish")
    _check_tally(name, shadow.tally, POLICY_METHODS[policy])
    return {"backend": backend, "policy": policy,
            "jct_s": results[0].finish_time,
            "ticks": sim.assess_ticks, "tally": shadow.tally,
            "n_attempts": results[0].n_attempts,
            "n_spec": results[0].n_spec_attempts}


def phase_fleet(backend: str, policy: str, *, seed: int = 0,
                n_workers: int = FLEET_WORKERS,
                sim_seconds: float = FLEET_SIM_SECONDS):
    from benchmarks.common import SCALE_N_CONTAINERS, SCALE_SPLITS_PER_WORKER
    from repro.accel.shadow import ShadowBackend
    from repro.sim import JobSpec
    from repro.sim.mapreduce import BINO_PARAMS, SimParams, Simulation
    n_maps = SCALE_SPLITS_PER_WORKER * n_workers
    shadow = ShadowBackend(backend)
    base = BINO_PARAMS if policy == "bino" else SimParams()
    params = dataclasses.replace(base, sim_time_cap=sim_seconds)
    sim = Simulation(policy=policy, seed=seed, n_workers=n_workers,
                     n_containers=SCALE_N_CONTAINERS, params=params,
                     assess_backend=shadow)
    sim.submit(JobSpec("fleet", "terasort", n_maps / 8.0))
    sim.run()
    if not sim.assess_ticks:
        raise AssertionError(f"fleet/{policy}: no assessment tick ran")
    _check_tally(f"fleet/{policy}", shadow.tally, POLICY_METHODS[policy])
    dev = shadow.device
    return {"backend": backend, "policy": policy, "n_maps": n_maps,
            "rows": sim.arrays.n,
            "capacity": dev._dc.cap, "upload_bytes": dev.upload_bytes,
            "ticks": sim.assess_ticks, "assess_wall_s": sim.assess_wall,
            "sim_now": sim.engine.now, "tally": shadow.tally}, sim


def phase_sweep(sim, *, seed: int = 0,
                n_scenarios: int = SWEEP_SCENARIOS) -> dict:
    from repro.accel.shadow import compare_sweep
    from repro.accel.sweep import BatchedSweep, scenario_grid
    arr = sim.arrays
    scenarios = scenario_grid(n_scenarios, len(arr.node_ids), seed=seed)
    sweep = BatchedSweep(arr, sim.engine.now).prepare(scenarios)
    batched = sweep.run_batched()
    t0 = time.perf_counter()
    batched_again = sweep.run_batched()
    t_batched = time.perf_counter() - t0
    t0 = time.perf_counter()
    serial = sweep.run_serial()
    t_serial = time.perf_counter() - t0
    tally = compare_sweep(sweep, batched, serial)
    again = compare_sweep(sweep, batched_again, batched)
    if again.outside():
        raise AssertionError("sweep: two batched runs disagree")
    _check_tally("sweep", tally, ("spatial_hits", "failed", "late_victims",
                                  "winning", "n_reap"))
    kinds = sorted({s.kind for s in scenarios})
    return {"scenarios": n_scenarios, "kinds": kinds, "rows": arr.n,
            "batched_s": t_batched, "serial_numpy_s": t_serial,
            "tally": tally}


def phase_runtime(*, seed: int = 0, arch: str = RUNTIME_ARCH,
                  reduced: bool = False, n_hosts: int = RUNTIME_HOSTS,
                  microbatches: int = RUNTIME_MICROBATCHES,
                  seq_len: int = RUNTIME_SEQ_LEN,
                  batch: int = RUNTIME_BATCH,
                  steps: int = RUNTIME_STEPS) -> dict:
    import jax
    import numpy as np

    from benchmarks.perf_runtime import (
        CHAOS_HORIZON,
        CRASH_SCRIPT,
        REPAIR_TIMEOUT,
        RESTART_TIMEOUT,
    )
    from repro.configs import get_config, reduced_config
    from repro.runtime import ChaosController, RuntimeConfig, TrainerRuntime
    from repro.train.loop import TrainConfig

    cfg = get_config(arch)
    if reduced:
        cfg = reduced_config(cfg)
    rt = RuntimeConfig(
        n_hosts=n_hosts, microbatches_per_shard=microbatches,
        recovery="bino", compute_delay=0.0,
        restart_timeout=RESTART_TIMEOUT, repair_timeout=REPAIR_TIMEOUT)

    def train(chaos):
        t = TrainerRuntime(cfg, TrainConfig(remat=RUNTIME_REMAT), rt,
                           seq_len=seq_len,
                           per_shard_batch=batch, seed=seed, chaos=chaos)
        try:
            out = {"n_params": sum(int(x.size) for x in
                                   jax.tree.leaves(t.state["params"]))}
            batch0 = t.coord.batch_fn(t.coord.datastates[0])
            if chaos is None:
                # AOT compile of the microbatch step: its own footprint.
                mem = t.grad_step.lower(t.state["params"], batch0) \
                    .compile().memory_analysis()
                out["grad_step_bytes"] = {
                    "argument": mem.argument_size_in_bytes,
                    "output": mem.output_size_in_bytes,
                    "temp": mem.temp_size_in_bytes}
            # Compile the step once here, before four host threads race
            # to the same cold jit.
            jax.block_until_ready(t.grad_step(t.state["params"], batch0))

            def on_step(i, _trainer):
                if chaos is not None and i == 1:
                    chaos.release()        # the crash lands in step 1
            t0 = time.perf_counter()
            reports = t.run(steps, on_step=on_step)
            out["wall_s"] = time.perf_counter() - t0
            out["losses"] = [float(r.metrics.get("loss", np.nan))
                             for r in reports]
            out["mb_executed"] = sum(r.mb_executed for r in reports)
            out["recoveries"] = sum(len(r.recoveries) for r in reports)
            out["params"] = [np.asarray(x) for x in
                             jax.tree.leaves(t.state["params"])]
            return out
        finally:
            t.shutdown()
            del t
            gc.collect()

    base = train(None)
    # The fault-free run's model state must be gone from the device.
    between = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    chaos = ChaosController(CRASH_SCRIPT, horizon=CHAOS_HORIZON, seed=seed,
                            defer_arm=True)
    crash = train(chaos)
    losses = base["losses"] + crash["losses"]
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"runtime: non-finite loss {losses}")
    identical = all(a.dtype == b.dtype and a.tobytes() == b.tobytes()
                    for a, b in zip(base["params"], crash["params"]))
    if not identical or len(base["params"]) != len(crash["params"]):
        raise AssertionError("runtime: parameters after the crash script "
                             "differ from the fault-free run")
    if not crash["recoveries"]:
        raise AssertionError("runtime: the crash script caused no recovery")
    stats = jax.devices()[0].memory_stats() or {}
    return {"arch": cfg.arch_id, "n_params": base["n_params"],
            "d_model": cfg.d_model, "vocab": cfg.vocab_size,
            "grad_step_bytes": base.get("grad_step_bytes"),
            "losses_fault_free": base["losses"],
            "losses_crash": crash["losses"],
            "wall_fault_free_s": base["wall_s"],
            "wall_crash_s": crash["wall_s"],
            "mb_executed_crash": crash["mb_executed"],
            "recoveries": crash["recoveries"],
            "bit_identical": identical,
            "bytes_in_use_between_runs": between,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit")}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.compile_cache import CompileCounter, enable
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repository ({e})",
              file=sys.stderr)
        return 2
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this smoke "
              "runs only on the chip", file=sys.stderr)
        return 3
    cache = enable()
    CompileCounter.install()
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} cache={cache}")

    for policy in POLICY_METHODS:
        for backend in ("jax", "pallas"):
            with Phase() as ph:
                r = phase_testbed(backend, policy, seed=args.seed)
            log(f"(a) testbed backend={backend} policy={policy} "
                f"jct_s={r['jct_s']:.3f} ticks={r['ticks']} "
                f"attempts={r['n_attempts']} spec={r['n_spec']} "
                f"{ph.timing()}\n"
                f"    shadow equal/tolerated/outside: "
                f"{_tally_line(r['tally'])}")

    fleets = {}
    for policy in POLICY_METHODS:
        with Phase() as ph:
            r, fleets[policy] = phase_fleet("pallas", policy, seed=args.seed)
        log(f"(b) fleet backend=pallas policy={policy} "
            f"workers={FLEET_WORKERS} maps={r['n_maps']} rows={r['rows']} "
            f"capacity={r['capacity']} "
            f"upload_bytes_per_tick={r['upload_bytes']} ticks={r['ticks']} "
            f"sim_s={r['sim_now']:.1f} "
            f"assess_wall_s={r['assess_wall_s']:.2f} {ph.timing()}\n"
            f"    shadow equal/tolerated/outside: {_tally_line(r['tally'])}")
    sim = fleets.pop("bino")
    del fleets
    gc.collect()

    with Phase() as ph:
        r = phase_sweep(sim, seed=args.seed)
    del sim
    gc.collect()
    log(f"(c) sweep scenarios={r['scenarios']} kinds={','.join(r['kinds'])} "
        f"rows={r['rows']} batched_s={r['batched_s']:.4f} "
        f"serial_numpy_s={r['serial_numpy_s']:.3f} {ph.timing()}\n"
        f"    verdicts equal/tolerated/outside: {_tally_line(r['tally'])}")

    with Phase() as ph:
        r = phase_runtime(seed=args.seed)
    log(f"(d) runtime arch={r['arch']} params={r['n_params']} "
        f"d_model={r['d_model']} vocab={r['vocab']} hosts={RUNTIME_HOSTS} "
        f"mb/shard={RUNTIME_MICROBATCHES} seq={RUNTIME_SEQ_LEN} "
        f"batch={RUNTIME_BATCH} steps={RUNTIME_STEPS} "
        f"grad_step_bytes={r['grad_step_bytes']} "
        f"loss_fault_free={r['losses_fault_free']} "
        f"loss_crash={r['losses_crash']} "
        f"recoveries={r['recoveries']} mb_executed={r['mb_executed_crash']} "
        f"bit_identical={r['bit_identical']} "
        f"bytes_in_use_between_runs={r['bytes_in_use_between_runs']} "
        f"peak_bytes_in_use={r['peak_bytes_in_use']} "
        f"bytes_limit={r['bytes_limit']} {ph.timing()}")

    total = CompileCounter.snapshot()
    log(f"compiles executables={total['executables']} "
        f"cache_hits={total['cache_hits']} compiled={total['compiled']} "
        f"compile_s={total['compile_s']:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
