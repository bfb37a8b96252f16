"""Flight-recorder gates (repro.obs; DESIGN.md §18).

Four planes pinned here:

1. **Schema** — record round-trip through the structured-numpy rail and
   the parallel object rail, plus bounded memory (drop-oldest segments).
2. **Byte identity** — attaching a recorder must not change a single
   byte of simulator behaviour: obs-on vs obs-off runs are compared on
   action traces, launch sequences and job results across every shuffle
   engine (the recorder keeps its own seq counter and every emit site
   is a pure read — §18.2).
3. **Scorecard math** — precision / recall / time-to-detect / wasted
   backup work on a hand-built trace with known ground truth.
4. **Cross-world identity** — the sim and the FakeClock live runtime,
   fed the same declarative fault script, must produce scorecards with
   an identical comparable core (victims / tp / fp / fn / precision /
   recall; time-to-detect is clock-relative and waived — §18.5).
"""
import json

import numpy as np
import pytest

from conftest import assert_runs_equivalent, run_traced
from repro.obs import (
    END_COMPLETED,
    END_FAILED,
    FAULT_CODES,
    K_ACTION,
    K_ATT_END,
    K_ATT_START,
    K_DETECT,
    K_DRAIN,
    K_FAULT,
    TRACE_DTYPE,
    MetricsRegistry,
    TraceRecorder,
    comparable_core,
    instrument_drain,
    scorecard,
    span,
    to_chrome_trace,
    trace_diff,
    write_chrome_trace,
)
from repro.sim import JobSpec, faults
from repro.sim.mapreduce import Simulation

SHUFFLES = ("rescan", "event", "batch", "kernel")


# ---------------------------------------------------------------------------
# 1. Schema round-trip + bounded memory
# ---------------------------------------------------------------------------
def test_record_schema_roundtrip():
    t = [0.0]
    rec = TraceRecorder(lambda: t[0])
    t[0] = 1.5
    rec.emit(K_ATT_START, a=3, b=1, obj="t1_a0")
    t[0] = 2.25
    rec.emit(K_ATT_END, a=3, b=END_COMPLETED, f0=1.5, f1=0.75, f2=1.0,
             obj="t1_a0")
    rec.emit(K_DRAIN, b=17, f0=2.0)

    recs = rec.records()
    assert recs.dtype == TRACE_DTYPE
    assert len(rec) == 3
    assert recs["kind"].tolist() == [K_ATT_START, K_ATT_END, K_DRAIN]
    assert recs["seq"].tolist() == [0, 1, 2]
    assert recs["time"].tolist() == [1.5, 2.25, 2.25]
    end = recs[1]
    assert (int(end["a"]), int(end["b"])) == (3, END_COMPLETED)
    assert (end["f0"], end["f1"], end["f2"]) == (1.5, 0.75, 1.0)
    # object rail pairs back up in emission order; K_DRAIN carries none
    objs = [(int(r["kind"]), o) for r, o in rec.iter_with_objs()]
    assert objs == [(K_ATT_START, "t1_a0"), (K_ATT_END, "t1_a0"),
                    (K_DRAIN, None)]
    assert rec.counts() == {"attempt_start": 1, "attempt_end": 1,
                            "drain": 1}
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_ring_buffer_drops_oldest_segment():
    rec = TraceRecorder(capacity=32, segment_size=8)
    for i in range(100):
        rec.emit(K_ACTION, a=i, obj=f"act{i}")
    # bounded: at most capacity records retained, the rest counted
    assert len(rec) <= 32
    assert rec.dropped == 100 - len(rec)
    recs = rec.records()
    # newest survive, in order, seq still globally monotonic
    assert int(recs["seq"][-1]) == 99
    assert np.all(np.diff(recs["seq"]) == 1)
    assert int(recs["a"][0]) == 100 - len(rec)
    # object rail dropped with its segment: survivors still pair up
    objs = [o for _, o in rec.iter_with_objs(K_ACTION)]
    assert objs[-1] == "act99" and len(objs) == len(rec)


# ---------------------------------------------------------------------------
# 2. obs-on ≡ obs-off byte identity, per engine
# ---------------------------------------------------------------------------
OBS_SCENARIOS = [
    ("crash_during_shuffle", "bino", 3, [("crash", 7, 0.45, 0.0)]),
    ("mof_plus_slowdown", "bino", 2,
     [("mof", 0, 0.85, 1.0), ("slow", 4, 0.3, 0.2)]),
    ("yarn_crash_mid_map", "yarn", 1, [("crash", 3, 0.15, 0.0)]),
]


def _script_fault(script):
    def fault(sim, job):
        faults.apply_script(sim, job, script)
    return fault


@pytest.mark.parametrize("name,policy,seed,script",
                         OBS_SCENARIOS, ids=[s[0] for s in OBS_SCENARIOS])
def test_obs_on_off_byte_identity(name, policy, seed, script):
    """Wiring a recorder through every emit site must not move a single
    event: same action trace, same launches, same results — per engine
    (the §18.2 determinism contract)."""
    for mode in SHUFFLES:
        off = run_traced(mode, policy, _script_fault(script), seed=seed,
                         gb=1.0)
        rec = TraceRecorder()
        on = run_traced(mode, policy, _script_fault(script), seed=seed,
                        gb=1.0, obs=rec)
        assert_runs_equivalent([off, on], [f"{mode}/obs-off",
                                           f"{mode}/obs-on"])
        assert len(rec) > 0, f"{mode}: recorder saw nothing"
        assert len(rec.by_kind(K_ATT_START)) == \
            len(rec.by_kind(K_ATT_END)), mode


def test_obs_trace_is_deterministic_across_reruns():
    a, b = TraceRecorder(), TraceRecorder()
    for rec in (a, b):
        run_traced("batch", "bino",
                   _script_fault([("crash", 7, 0.45, 0.0)]),
                   seed=3, gb=1.0, obs=rec)
    d = trace_diff(a, b)
    assert d["equal"], d


def test_action_trace_lazy_and_identical():
    """Satellite 1: the unbounded repr-string list is retired — the
    ``action_trace`` property materializes lazily from the recorder's
    action rail and matches the record_actions-only private rail."""
    script = [("crash", 7, 0.45, 0.0)]
    off = run_traced("batch", "bino", _script_fault(script), seed=3, gb=1.0)
    rec = TraceRecorder()
    on = run_traced("batch", "bino", _script_fault(script), seed=3, gb=1.0,
                    obs=rec)
    assert off.sim.action_trace == on.sim.action_trace
    assert len(on.sim.action_trace) == len(rec.by_kind(K_ACTION))
    assert on.sim._act_rec is rec  # no second recorder when obs is wired


# ---------------------------------------------------------------------------
# 3. Scorecard math on hand-built ground truth
# ---------------------------------------------------------------------------
def test_scorecard_math():
    t = [0.0]
    rec = TraceRecorder(lambda: t[0])
    t[0] = 5.0
    rec.emit(K_FAULT, a=1, b=FAULT_CODES["crash"])          # victim 1
    rec.emit(K_FAULT, a=-1, b=FAULT_CODES["mof"])           # not a node
    t[0] = 6.5
    rec.emit(K_DETECT, a=1, b=1)                            # tp, ttd 1.5
    t[0] = 7.0
    rec.emit(K_DETECT, a=3, b=0)                            # fp
    t[0] = 8.0
    rec.emit(K_FAULT, a=2, b=FAULT_CODES["hang"])           # fn (missed)
    rec.emit(K_ATT_END, a=1, b=END_FAILED, f1=3.5, f2=1.0)  # wasted backup
    rec.emit(K_ATT_END, a=0, b=END_COMPLETED, f1=2.0, f2=1.0)
    rec.emit(K_ATT_END, a=0, b=END_FAILED, f1=9.0, f2=0.0)  # not a backup

    card = scorecard(rec, policy="hand")
    assert card["victims"] == [1, 2]
    assert card["tp"] == [1] and card["fp"] == [3] and card["fn"] == [2]
    assert card["precision"] == 0.5 and card["recall"] == 0.5
    assert card["ttd"] == {1: 1.5} and card["mean_ttd"] == 1.5
    assert card["n_backups"] == 2
    assert card["wasted_backup_work"] == 3.5
    assert comparable_core(card) == {
        "victims": [1, 2], "tp": [1], "fp": [3], "fn": [2],
        "precision": 0.5, "recall": 0.5}


def test_scorecard_vacuous_cases():
    rec = TraceRecorder()
    card = scorecard(rec)
    assert card["precision"] == 1.0 and card["recall"] == 1.0
    assert card["victims"] == [] and card["mean_ttd"] is None
    with pytest.raises(ValueError):
        scorecard(rec, mode="nope")


# ---------------------------------------------------------------------------
# 4. Metrics registry + instrument_drain (satellite 2)
# ---------------------------------------------------------------------------
def test_metrics_registry_snapshot():
    reg = MetricsRegistry()
    reg.counter("hits").inc()
    reg.counter("hits").inc(2)
    reg.gauge("depth").set(7.5)
    reg.histogram("lat").observe(1.0)
    reg.histogram("lat").observe(3.0)
    with reg.timer("work"):
        pass
    snap = reg.snapshot()
    assert snap["hits"] == 3 and snap["depth"] == 7.5
    assert snap["lat_n"] == 2 and snap["lat_mean"] == 2.0
    assert snap["lat_min"] == 1.0 and snap["lat_max"] == 3.0
    assert snap["work_n"] == 1 and snap["work_s"] >= 0.0


def test_instrument_drain_times_batch_lane():
    sim = Simulation(policy="bino", seed=0, n_workers=8, shuffle="batch")
    reg = instrument_drain(sim)
    sim.submit(JobSpec("j0", "terasort", 1.0))
    sim.run()
    snap = reg.snapshot()
    assert snap["drain_n"] > 0 and snap["drain_s"] > 0.0
    # rescan has no calendar lane: the timer exists but stays at zero
    sim2 = Simulation(policy="bino", seed=0, n_workers=8, shuffle="rescan")
    assert instrument_drain(sim2).snapshot() == {}


# ---------------------------------------------------------------------------
# 5. Chrome-trace export + trace diff
# ---------------------------------------------------------------------------
def test_chrome_export_roundtrip(tmp_path):
    rec = TraceRecorder()
    run_traced("batch", "bino", _script_fault([("crash", 7, 0.45, 0.0)]),
               seed=3, gb=1.0, obs=rec)
    doc = to_chrome_trace(rec)
    events = doc["traceEvents"]
    assert events, "export produced nothing"
    assert all({"name", "ph", "pid", "tid"} <= set(e) for e in events)
    # attempt lifecycle pairs become complete ("X") slices
    slices = [e for e in events if e["ph"] == "X"]
    assert slices and all(e["dur"] >= 0 for e in slices)
    assert doc["otherData"]["dropped_records"] == 0
    out = tmp_path / "trace.json"
    write_chrome_trace(rec, str(out))
    loaded = json.loads(out.read_text())
    assert len(loaded["traceEvents"]) == len(events)


def test_trace_diff_reports_divergence():
    t = [1.0]
    a, b = TraceRecorder(lambda: t[0]), TraceRecorder(lambda: t[0])
    a.emit(K_DETECT, a=1, b=1)
    b.emit(K_DETECT, a=2, b=1)
    d = trace_diff(a, b)
    assert not d["equal"] and d["first_diff"] == 0 and "a=" in d["detail"]
    assert trace_diff(a, a)["equal"]


# ---------------------------------------------------------------------------
# 6. Cross-world scorecard identity: sim vs FakeClock live runtime
# ---------------------------------------------------------------------------
CROSS_SCRIPTS = [
    [("crash", 1, 0.2, 0.0)],
    [("crash", 1, 0.2, 0.0), ("crash", 2, 0.3, 0.0)],
]


@pytest.mark.parametrize("script", CROSS_SCRIPTS,
                         ids=["one_crash", "two_crashes"])
def test_scorecard_identical_across_worlds(script):
    """The same declarative fault script, interpreted by the simulator
    and by the ChaosController against live host threads on a FakeClock,
    must yield the same detection verdict sets (§18.5). Time-to-detect
    is clock-relative and only sanity-checked per world."""
    from repro.configs import get_config, reduced_config
    from repro.runtime import (
        ChaosController,
        FakeClock,
        RuntimeConfig,
        TrainerRuntime,
    )
    from repro.train.loop import TrainConfig

    # -- sim world ----------------------------------------------------
    rec_sim = TraceRecorder()
    sim = Simulation(policy="bino", seed=1, n_workers=4, obs=rec_sim)
    job = sim.submit(JobSpec("j0", "terasort", 2.0))
    faults.apply_script(sim, job, script)
    sim.run()
    card_sim = scorecard(rec_sim, policy="bino")

    # -- live runtime world -------------------------------------------
    rec_rt = TraceRecorder(thread_safe=True)
    rt = RuntimeConfig(n_hosts=4, microbatches_per_shard=4,
                       recovery="bino", compute_delay=0.02)
    t = TrainerRuntime(
        reduced_config(get_config("qwen1.5-0.5b")), TrainConfig(), rt,
        seq_len=32, per_shard_batch=2, seed=0,
        clock=FakeClock(auto_advance=True),
        chaos=ChaosController(script, horizon=6.0, seed=7), obs=rec_rt)
    try:
        t.run(3)
        snap = t.coord.metrics.snapshot()
    finally:
        t.shutdown()
    card_rt = scorecard(rec_rt, policy="bino")

    assert comparable_core(card_sim) == comparable_core(card_rt)
    assert card_sim["recall"] == 1.0
    for card in (card_sim, card_rt):
        assert all(v > 0 for v in card["ttd"].values())
    # the coordinator's metrics plane agrees with the trace plane
    assert snap["detections"] == len(rec_rt.by_kind(K_DETECT)[
        rec_rt.by_kind(K_DETECT)["b"] == 1])
    assert snap["recoveries"] > 0


# ---------------------------------------------------------------------------
# 7. Host spans on the profiler's clock (DESIGN.md §18.7)
# ---------------------------------------------------------------------------
def _span_names(log_dir):
    import glob

    import jax
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    return [ev.name for plane in data.planes for line in plane.lines
            for ev in line.events if ev.name.startswith("repro.")]


def test_span_names_the_program_under_a_trace(tmp_path):
    import jax
    with span("sim.tick", tick=7):
        pass                          # no trace: nothing recorded
    with jax.profiler.trace(str(tmp_path)):
        with span("sim.tick", tick=7):
            with span("accel.wait"):
                pass
    assert _span_names(tmp_path) == ["repro.sim.tick", "repro.accel.wait"]


def test_spans_move_no_simulated_event(tmp_path):
    """A profiler trace around a run records the tick's spans, the
    device path's among them, and changes nothing the simulation
    decides: same action trace, same launches, same results."""
    import jax
    fault = _script_fault([("crash", 7, 0.45, 0.0)])
    off = run_traced("batch", "bino", fault, seed=3, gb=1.0,
                     assess_backend="jax")
    with jax.profiler.trace(str(tmp_path)):
        on = run_traced("batch", "bino", fault, seed=3, gb=1.0,
                        assess_backend="jax")
    assert_runs_equivalent([off, on], ["profiler-off", "profiler-on"])
    assert off.sim.action_trace == on.sim.action_trace
    names = set(_span_names(tmp_path))
    assert {"repro.sim.tick", "repro.sim.snapshot", "repro.core.glance",
            "repro.core.plan", "repro.accel.spatial_hits",
            "repro.accel.upload", "repro.accel.launch", "repro.accel.wait",
            "repro.accel.fetch"} <= names
    backend = on.sim.speculator.backend
    assert backend.fetch_bytes > 0
