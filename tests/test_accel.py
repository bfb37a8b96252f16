"""Equivalence gate for the assessment-compute backends (DESIGN.md §13.3).

Four layers:

1. **Backend trace parity** — seeded simulations under crash / delay /
   MOF-loss / fetch-quorum faults must emit byte-identical action traces
   and job results whether the vectorized policies compute on the
   ``numpy`` reference backend, the jit ``jax`` backend, or the
   ``pallas`` backend in interpret mode.
2. **DeviceColumns invariants** (hypothesis) — after arbitrary
   grow/sync/deactivate/compact sequences, the padded device mirror
   equals the live columns on ``[:n]`` and holds exact pad fills beyond,
   with power-of-two monotone capacities.
3. **Batched sweep parity** — one vmapped device step across a fault
   scenario grid equals the same clones scored serially on the numpy
   backend, bit for bit.
4. Unit behaviours: the percentile mirror vs ``np.percentile``, backend
   registry resolution, LATE eligibility gating.
"""
import dataclasses

import numpy as np
import pytest

from repro.accel import BACKENDS, get_backend
from repro.accel.base import AssessmentBackend
from repro.core.arrays import ArraySnapshot, DeviceColumns
from repro.core.types import AttemptState, TaskKind, TaskState
from repro.sim import JobSpec, Simulation, faults
from repro.sim.mapreduce import SimParams

from conftest import HAVE_HYPOTHESIS

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st


# ---------------------------------------------------------------------------
# Harness (mirrors tests/test_columnar.py)
# ---------------------------------------------------------------------------
def _crash(sim, job):
    faults.crash_busiest_node_at_map_progress(sim, job, 0.4)


def _delay(sim, job):
    def fire():
        counts = {}
        for t in job.maps:
            for a in t.running_attempts():
                counts[a.node_id] = counts.get(a.node_id, 0) + 1
        victim = max(sorted(counts), key=lambda n: counts[n]) \
            if counts else sim.cluster.node_ids[0]
        sim.set_node_speed(victim, 0.05)
        sim.engine.after(150.0, sim.set_node_speed, victim, 1.0)
    sim.engine.at(30.0, fire)


def _mof(sim, job):
    faults.lose_mof_at_map_progress(sim, job, 1.0)


def _quorum(sim, job):
    # Wide MOF loss: many reducers report, the AM's too-many-fetch-
    # failures quorum trips and re-runs the producer.
    faults.lose_mof_at_map_progress(sim, job, 1.0, max_stragglers=16)


def _run(policy, backend, fault, seed=1, gb=2.0):
    sim = Simulation(policy=policy, seed=seed, assess_backend=backend,
                     record_actions=True)
    job = sim.submit(JobSpec("j0", "terasort", gb))
    fault(sim, job)
    results = sim.run()
    return sim, results


def _result_key(results):
    return [(r.job_id, r.finish_time, r.n_attempts, r.n_spec_attempts,
             r.n_fetch_failures) for r in results]


_REF_CACHE = {}


def _reference(policy, fault, seed=1):
    key = (policy, fault.__name__, seed)
    if key not in _REF_CACHE:
        _REF_CACHE[key] = _run(policy, None, fault, seed)
    return _REF_CACHE[key]


# ---------------------------------------------------------------------------
# 1. Backend trace parity on the fault grid
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("policy,fault", [
    ("yarn", _crash), ("yarn", _quorum),
    ("bino", _delay), ("bino", _mof),
])
def test_backend_traces_identical(policy, fault, backend):
    ref, rres = _reference(policy, fault)
    dev, dres = _run(policy, backend, fault)
    assert ref.action_trace == dev.action_trace
    assert _result_key(rres) == _result_key(dres)
    assert dev.action_trace, "scenario produced no actions — not probing"


def test_backend_traces_identical_bino_crash_jax():
    # Crash drives Eq. 4 (failure masks) + straggler extraction + the
    # collective ramp's winning test through the device path.
    ref, rres = _reference("bino", _crash)
    dev, dres = _run("bino", "jax", _crash)
    assert ref.action_trace == dev.action_trace
    assert _result_key(rres) == _result_key(dres)


# ---------------------------------------------------------------------------
# 2. DeviceColumns padding/compaction invariants
# ---------------------------------------------------------------------------
def _check_mirror(arr: ArraySnapshot, dc: DeviceColumns):
    host = dc.refresh(arr.active_jobs())
    n = arr.n
    assert dc.cap >= max(n, 1)
    assert dc.cap & (dc.cap - 1) == 0, "capacity must stay a power of two"
    for name, fill in DeviceColumns._FILLS.items():
        buf = host[name]
        assert len(buf) == dc.cap
        assert np.array_equal(buf[:n], getattr(arr, name)[:n])
        pad = buf[n:]
        expect = np.full(dc.cap - n, fill, dtype=pad.dtype)
        assert np.array_equal(pad, expect), name
    assert np.array_equal(host["order"][:n], arr.order())
    assert not host["order"][n:].any()
    assert host["n_rows"] == n


def _snapshot_ops(arr: ArraySnapshot, ops, rng):
    """Replay an op script against a raw snapshot (no simulator)."""
    jidx = arr.job_started("j0")
    owners = []
    for op in ops:
        if op == 0 or not owners:   # add a row
            o = type("O", (), {"row": -1})()
            t_order = len(owners) // 2
            if t_order * 2 == len(owners):   # first attempt of a task
                arr.task_created(jidx)
            o.row = arr.add_attempt(
                o, f"a{len(owners)}", f"t{t_order}", t_order,
                len(owners) % 2, jidx, int(rng.integers(0, 4)),
                TaskKind.MAP if t_order % 2 else TaskKind.REDUCE,
                bool(rng.integers(0, 2)), float(rng.random()),
                0.0, 1.0 + float(rng.random()), 3, TaskState.RUNNING)
            owners.append(o)
        elif op == 1:               # progress sync
            o = owners[int(rng.integers(0, len(owners)))]
            arr.sync_row(o.row, float(rng.random()), float(rng.random()))
        elif op == 2:               # end an attempt
            o = owners[int(rng.integers(0, len(owners)))]
            arr.set_attempt_state(o.row, AttemptState.COMPLETED)
        elif op == 3:               # deactivate everything (job done)...
            arr.job_finished("j0")
            arr.job_started("j0")   # ...and reopen for later adds
        else:                       # force physical compaction
            arr._compact()
    return arr


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_device_columns_mirror_hypothesis():
    @settings(max_examples=40, deadline=None)
    @given(ops=st.lists(st.integers(min_value=0, max_value=4),
                        min_size=1, max_size=120),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def inner(ops, seed):
        rng = np.random.default_rng(seed)
        arr = ArraySnapshot([f"n{i:02d}" for i in range(4)])
        dc = DeviceColumns(arr)
        caps = []
        for cut in range(0, len(ops), 17):
            _snapshot_ops(arr, ops[cut:cut + 17], rng)
            _check_mirror(arr, dc)
            caps.append(dc.cap)
        assert caps == sorted(caps), "capacity must never shrink"
    inner()


def test_device_columns_mirror_seeded():
    # Bare-interpreter fallback for the same invariants.
    rng = np.random.default_rng(7)
    arr = ArraySnapshot([f"n{i:02d}" for i in range(4)])
    dc = DeviceColumns(arr)
    ops = list(rng.integers(0, 5, size=400))
    for cut in range(0, len(ops), 23):
        _snapshot_ops(arr, ops[cut:cut + 23], rng)
        _check_mirror(arr, dc)


def test_device_columns_repad_after_compaction():
    # Rows vacated by compaction must return to exact pad fills.
    arr = ArraySnapshot(["n00", "n01"])
    rng = np.random.default_rng(0)
    _snapshot_ops(arr, [0] * 60, rng)       # 60 live rows
    dc = DeviceColumns(arr)
    _check_mirror(arr, dc)
    arr.job_finished("j0")                  # all rows dead
    arr._compact()
    arr.job_started("j0")
    _check_mirror(arr, dc)
    assert arr.n == 0


# ---------------------------------------------------------------------------
# 3. Batched sweep parity (device vmap vs serial numpy)
# ---------------------------------------------------------------------------
def _mid_run_snapshot(n_workers=20, n_jobs=3, cap_s=80.0, seed=5):
    params = dataclasses.replace(SimParams(), sim_time_cap=cap_s)
    sim = Simulation(policy="yarn", seed=seed, n_workers=n_workers,
                     params=params)
    for j in range(n_jobs):
        sim.submit(JobSpec(f"j{j}", "terasort", 2.0,
                           submit_time=float(3 * j)))
    sim.run()
    return sim


def test_batched_sweep_matches_serial_numpy():
    from repro.accel.sweep import BatchedSweep, scenario_grid
    sim = _mid_run_snapshot()
    assert sim.arrays.n > 0 and sim.active_jobs
    scenarios = scenario_grid(8, n_nodes=20, seed=1)
    assert {s.kind for s in scenarios} == {
        "crash", "delay", "mof_loss", "fetch_quorum"}
    sweep = BatchedSweep(sim.arrays, sim.engine.now).prepare(scenarios)
    serial = sweep.run_serial()
    batched = sweep.run_batched()
    assert len(serial) == len(batched) == 8
    for a, b in zip(serial, batched):
        assert set(a) == set(b)
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
    # the grid must actually diversify assessment outcomes
    sigs = {repr(s) for s in serial}
    assert len(sigs) > 1, "scenario grid produced identical verdicts"


def test_scenario_grid_deterministic():
    from repro.accel.sweep import scenario_grid
    assert scenario_grid(12, 50, seed=3) == scenario_grid(12, 50, seed=3)
    assert scenario_grid(12, 50, seed=3) != scenario_grid(12, 50, seed=4)


# ---------------------------------------------------------------------------
# 4. Unit behaviours
# ---------------------------------------------------------------------------
def test_percentile_mirror_matches_numpy():
    # LATE's per-job percentile: exact order statistics + numpy's lerp.
    import jax
    import jax.numpy as jnp
    from repro.accel.jax_backend import member_percentile
    rng = np.random.default_rng(0)
    with jax.enable_x64(True):
        for m in list(range(1, 24)) + [101]:
            vals = rng.random(m) * rng.choice([1e-6, 1.0, 1e6])
            padded = np.concatenate([vals, rng.random(7)])
            member = np.zeros((2, m + 7), dtype=bool)
            member[0, :m] = True                # job 0: the m values
            for q in (25.0, 50.0, 75.0, 90.0):
                got, cnt = member_percentile(
                    jnp.asarray(member), jnp.asarray(padded),
                    jnp.float64(q), jnp.float64(1.0))
                want = float(np.percentile(vals, q))
                assert float(got[0]) == want, (m, q, float(got[0]), want)
                assert int(cnt[0]) == m and int(cnt[1]) == 0


@pytest.mark.parametrize("dtype", ["float64", "float32"])
def test_order_stat_matches_sorted(dtype):
    # The sort-free order statistics behind LATE's percentile: exact
    # k-th smallest per job, in both precisions the platforms use.
    import jax
    import jax.numpy as jnp
    from repro.accel.jax_backend import order_stat
    rng = np.random.default_rng(3)
    cap, jcap = 64, 3
    with jax.enable_x64(dtype == "float64"):
        vals = (rng.random(cap) * rng.choice([1e-6, 1.0, 1e6], cap)
                ).astype(dtype)
        vals[::7] = vals[1]                     # ties
        vals[5] = 0.0
        keys = rng.integers(0, jcap + 1, cap)   # jcap = not a member
        member = keys[None, :] == np.arange(jcap)[:, None]
        ks = np.array([[0, 1, 2], [3, 0, 5], [60, 2, 63]])
        got = np.asarray(order_stat(jnp.asarray(member), jnp.asarray(vals),
                                    jnp.asarray(ks)))
    for i in range(len(ks)):
        for j in range(jcap):
            srt = np.sort(vals[keys == j])
            if ks[i, j] < len(srt):
                assert got[i, j] == srt[ks[i, j]], (i, j)
            else:
                assert got[i, j] == np.inf, (i, j)


def test_shadow_tolerates_only_near_threshold_flips():
    # §13.3 tolerance: a flipped Eq. 4 verdict is tolerated only where
    # the reference's silence sits within TOL of the threshold.
    from repro.accel.shadow import Tally, check_failure
    now, window = 100.0, 1.5
    hb = np.array([90.0, 90.0, 99.0])
    thresholds = np.array([10.0 * (1 + 1e-9), 5.0, 10.0])
    ref = np.array([False, True, False])
    dev = np.array([True, False, False])     # node 0 near, node 1 firm
    tally = Tally()
    check_failure(tally, dev, ref, now, hb, thresholds, window, "failed")
    assert tally.counts["failed"] == {"equal": 1, "tolerated": 1,
                                      "outside": 1}
    assert tally.outside() == 1 and tally.examples


def test_backend_registry():
    for name in BACKENDS:
        b = get_backend(name)
        assert isinstance(b, AssessmentBackend)
        assert b.name == name
        assert get_backend(b) is b
    assert get_backend(None).name == "numpy"
    with pytest.raises(ValueError):
        get_backend("cuda")


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_shared_backend_instance_across_snapshots(backend):
    # get_backend passes instances through, so one backend may serve two
    # interleaved simulations whose tick clocks coincide — per-tick memos
    # must key on the snapshot, not just on `now`.
    sim1 = _mid_run_snapshot(seed=5)
    sim2 = _mid_run_snapshot(seed=9)
    t = max(sim1.engine.now, sim2.engine.now) + 1.0
    shared = get_backend(backend)
    out1 = shared.late_victims(
        sim1.arrays, t, sim1.arrays.active_jobs(),
        np.ones(len(sim1.arrays.active_jobs()), dtype=bool), 10.0, 25.0)
    out2 = shared.late_victims(
        sim2.arrays, t, sim2.arrays.active_jobs(),
        np.ones(len(sim2.arrays.active_jobs()), dtype=bool), 10.0, 25.0)
    fresh = get_backend(backend)
    want2 = fresh.late_victims(
        sim2.arrays, t, sim2.arrays.active_jobs(),
        np.ones(len(sim2.arrays.active_jobs()), dtype=bool), 10.0, 25.0)
    assert np.array_equal(out2, want2)
    r1 = shared.reap_rows(sim1.arrays, t)
    r2 = shared.reap_rows(sim2.arrays, t)
    assert np.array_equal(r2, fresh.reap_rows(sim2.arrays, t))
    assert np.array_equal(r1, fresh.reap_rows(sim1.arrays, t))
    del out1


def test_late_victims_respects_eligibility():
    sim = _mid_run_snapshot(n_jobs=2)
    arr = sim.arrays
    now = sim.engine.now
    active = arr.active_jobs()
    assert active
    b = get_backend("numpy")
    none_eligible = np.zeros(len(active), dtype=bool)
    victims = b.late_victims(arr, now, active, none_eligible, 10.0, 25.0)
    assert (victims == -1).all()
