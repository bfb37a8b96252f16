"""The program's spans as the benchmark reads them: the reduction to
count, total and self time on synthetic events, the idle-gap labels of
both span families, the benchmark's own metrics left as they were, and
the tiny cells traced on the CPU with every span where it is documented
to sit."""
import contextlib
import io
import json

import pytest

import chipbench_tiny
from chipbench import harness
from chipbench import spans as sp
from chipbench import trace as tr

HOST = "/host:CPU"
DEV = "/device:TPU:0"


def _ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur))


# Two host lines: the simulator's tick on one, a training step on the
# other, with a benchmark span between program spans on the first.
EVENTS = [
    _ev(HOST, "python#0", "chipbench.window", 0, 1000),
    _ev(HOST, "python#0", "chipbench.engine.chunk", 80, 540),
    _ev(HOST, "python#0", "repro.sim.tick", 100, 500),
    _ev(HOST, "python#0", "repro.sim.snapshot", 110, 40),
    _ev(HOST, "python#0", "repro.core.glance", 160, 340),
    _ev(HOST, "python#0", "chipbench.backend.spatial_hits", 190, 220),
    _ev(HOST, "python#0", "repro.accel.spatial_hits", 200, 200),
    _ev(HOST, "python#0", "repro.accel.launch", 210, 30),
    _ev(HOST, "python#0", "repro.accel.wait", 250, 100),
    _ev(HOST, "python#0", "repro.accel.fetch", 360, 20),
    _ev(HOST, "python#0", "repro.core.plan", 520, 60),
    _ev(HOST, "python#1", "repro.runtime.step", 700, 500),
    _ev(HOST, "python#1", "repro.runtime.gather", 720, 200),
    _ev(HOST, "python#1", "repro.runtime.bino_tick", 750, 50),
    _ev(HOST, "python#1", "repro.runtime.reduce", 930, 150),
    _ev(HOST, "python#0", "repro.sim.tick", 1100, 50),   # outside
]


def test_program_spans_count_total_and_self_time():
    s = sp.program_spans(EVENTS, (0, 1000))
    ns = 1e-9
    assert s["sim.tick"] == {"count": 1,
                             "total_s": pytest.approx(500 * ns),
                             "self_s": pytest.approx((500 - 40 - 340 - 60)
                                                     * ns),
                             "parents": [""]}
    # the benchmark's span between glance and the method is no child
    assert s["core.glance"]["self_s"] == pytest.approx(140 * ns)
    assert s["accel.spatial_hits"]["self_s"] == pytest.approx(50 * ns)
    assert s["accel.spatial_hits"]["parents"] == ["core.glance"]
    assert s["accel.wait"]["parents"] == ["accel.spatial_hits"]
    assert s["runtime.bino_tick"]["parents"] == ["runtime.gather"]
    # clipped to the window: the step and the reduce end at 1000
    assert s["runtime.step"]["total_s"] == pytest.approx(300 * ns)
    assert s["runtime.step"]["self_s"] == pytest.approx(30 * ns)
    assert s["runtime.reduce"]["total_s"] == pytest.approx(70 * ns)
    assert s["runtime.gather"]["self_s"] == pytest.approx(150 * ns)
    # the second tick lies outside the window
    assert s["sim.tick"]["count"] == 1
    assert not any(k.startswith("chipbench") or k == "window" for k in s)


def test_program_spans_keep_host_lines_apart():
    """Two threads whose spans overlap in time: neither is the other's
    child."""
    ev = [_ev(HOST, "python#0", "repro.runtime.step", 0, 100),
          _ev(HOST, "python#1", "repro.sim.tick", 10, 20)]
    s = sp.program_spans(ev, (0, 100))
    assert s["runtime.step"]["self_s"] == pytest.approx(100e-9)
    assert s["sim.tick"]["parents"] == [""]


def test_line_keys_part_host_threads_and_keep_device_lines():
    assert sp.line_key(HOST, "python", 0) == "python#0"
    assert sp.line_key(HOST, "python", 3) == "python#3"
    assert sp.line_key(DEV, tr.OPS_LINE, 2) == tr.OPS_LINE
    assert sp.line_key(DEV, tr.MODULES_LINE, 1) == tr.MODULES_LINE


def test_tick_split_adds_up_to_the_tick():
    s = sp.program_spans(EVENTS, (0, 1000))
    split = sp.tick_split(s, ticks=1)
    parts = ("policy_ms", "prep_ms", "wait_ms", "fetch_ms")
    assert sum(split[k] for k in parts) == pytest.approx(split["tick_ms"])
    assert split["tick_ms"] == pytest.approx(500e-6)
    assert split["wait_ms"] == pytest.approx(100e-6)
    assert split["fetch_ms"] == pytest.approx(20e-6)
    assert split["prep_ms"] == pytest.approx((50 + 30) * 1e-6)
    step = sp.step_split(s, steps=1)
    assert step["bino_tick_ms"] == pytest.approx(50e-6)
    assert step["gather_ms"] == pytest.approx(150e-6)
    assert sp.tick_split({}, 3) is None and sp.step_split({}, 3) is None


def _device_ops(intervals):
    return [_ev(DEV, "XLA Ops", "fusion", s, e - s) for s, e in intervals]


def test_idle_gaps_take_the_innermost_span_of_either_family():
    events = EVENTS + _device_ops([(245, 255), (340, 350), (640, 660)])
    gaps = sp.idle_gaps(events, (0, 1000))
    # longest first; middles 830, 495, 122.5 and 297.5
    assert [g[0] for g in gaps] == [
        "host:repro.runtime.gather",   # the step's gather, other line
        "host:repro.core.glance",      # the benchmark's span has ended
        "host:repro.sim.snapshot",     # inside the benchmark's chunk
        "host:repro.accel.wait",       # inside the benchmark's span
    ]
    assert [g[1] for g in gaps] == pytest.approx(
        [340e-9, 290e-9, 245e-9, 85e-9])
    # a benchmark span innermost keeps today's label
    ev2 = [_ev(HOST, "python#0", "chipbench.window", 0, 100),
           _ev(HOST, "python#0", "repro.core.glance", 0, 100),
           _ev(HOST, "python#0", "chipbench.backend.winning", 20, 60)]
    ev2 += _device_ops([(0, 10)])
    assert sp.idle_gaps(ev2, (0, 100)) == [["host:backend.winning",
                                            pytest.approx(90e-9)]]
    ev3 = _device_ops([(0, 10)])
    assert sp.idle_gaps(ev3, (0, 100))[0][0] == "host:outside-spans"


PR13_EVENTS = [
    _ev(HOST, "python", "chipbench.window", 0, 1000),
    _ev(HOST, "python", "chipbench.engine.chunk", 100, 500),
    _ev(HOST, "python", "chipbench.policy.assess", 120, 400),
    _ev(HOST, "python", "chipbench.backend.spatial_hits", 150, 100),
    _ev(DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8] %p)", 100, 100),
    _ev(DEV, "XLA Ops", "spatial_kernel", 150, 100),
    _ev(DEV, "XLA Ops", "fusion.1", 600, 100),
    _ev(DEV, "XLA Ops", "late", 950, 100),
    _ev(DEV, "XLA Modules", "jit_pallas_spatial(12)", 100, 150),
    _ev(DEV, "XLA Modules", "jit_loss_fn(3)", 600, 50),
    _ev(DEV, "XLA Modules", "jit_failure_core(3)", 650, 50),
]
PROGRAM_SPANS = [
    _ev(HOST, "python", "repro.sim.tick", 110, 450),
    _ev(HOST, "python", "repro.core.glance", 130, 300),
    _ev(HOST, "python", "repro.accel.spatial_hits", 155, 90),
    _ev(HOST, "python", "repro.accel.wait", 200, 40),
    _ev(HOST, "python", "repro.accel.fetch", 300, 10),
    _ev(HOST, "python", "repro.runtime.step", 700, 200),
]
COUNTERS = {
    "window_wall_s": 30.0, "sim_s": 45.0, "ticks": 40,
    "assess_wall_s": 16.0, "upload_bytes_total": 4.0e8,
    "upload_ticks": 40, "n_nodes": 10_000,
    "tick_work": {"spatial_hits": [(65536, 512)] * 40,
                  "winning": [(65536, 512)] * 40},
    "steps": 150, "tokens_per_s": 21000.0, "seq_len": 512,
    "recovery_s": 0.065, "mb_wasted": 0, "step_p90_ms": 190.0,
}


def _read_all(summary):
    run = harness.Run("fleet10k-tenants512", 1, 30.0, True)
    run.device_kind = "TPU v5 lite"
    run.config = harness.find_cell(harness.load_manifest(),
                                   "qwen05b-dp4-crash")[1]
    run.counters = dict(COUNTERS)
    run.trace_summary = summary
    return {m["name"]: harness.load_metric(m["name"]).read(run)
            for m in harness.load_manifest()["per_layer"]}


def test_existing_readers_ignore_the_program_spans():
    """Every accepted per-layer reader, and the breakdown, read the same
    with and without the program's spans in the trace."""
    plain = tr.reduce_events(PR13_EVENTS)
    spanned = tr.reduce_events(PR13_EVENTS + PROGRAM_SPANS)
    assert spanned == plain
    before, after = _read_all(plain), _read_all(spanned)
    assert after == before
    assert sum(v is not None for v in before.values()) >= 10


# ---------------------------------------------------------------------------
# The tiny cells, traced on the CPU
# ---------------------------------------------------------------------------
def _traced(workload, seconds, tmp_path, tweak=None):
    tweak = tweak or chipbench_tiny.tweak_for(workload)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = sp.main(["--workload", workload, "--seed", "3000000019",
                      "--seconds", str(seconds)], require_chip=False,
                     tweak=tweak, cache=False, out_dir=tmp_path)
    assert rc == 0
    line = out.getvalue().splitlines()[-1]
    assert (tmp_path / f"{workload}-3000000019.json").read_text() \
        == line + "\n"
    return json.loads(line)


FLEET_NESTING = {
    "sim.tick": {""},
    "sim.snapshot": {"sim.tick"},
    "core.glance": {"sim.tick"},
    "core.plan": {"sim.tick"},
    "accel.spatial_hits": {"core.glance"},
    "accel.temporal_zeta": {"core.glance"},
    "accel.failure_masks": {"core.glance"},
    "accel.reap_rows": {"core.plan"},
}
METHODS = {"accel.spatial_hits", "accel.temporal_zeta",
           "accel.failure_masks", "accel.reap_rows", "accel.winning",
           "accel.late_victims"}


@pytest.mark.parametrize("workload", ["fleet10k-tenants512",
                                      "fleet10k-terasort1"])
def test_fleet_cell_spans_on_the_cpu(workload, tmp_path):
    out = _traced(workload, 2.0, tmp_path)
    s = out["spans"]
    for name, parents in FLEET_NESTING.items():
        assert set(s[name]["parents"]) == parents, name
    for name in ("accel.refresh", "accel.upload", "accel.launch",
                 "accel.wait", "accel.fetch"):
        assert set(s[name]["parents"]) <= METHODS, name
    # one tick span per tick the window counted
    assert s["sim.tick"]["count"] == out["ticks"] > 0
    split = out["split"]
    assert all(split[k] is not None for k in split)
    parts = sum(split[k] for k in ("policy_ms", "prep_ms", "wait_ms",
                                   "fetch_ms"))
    assert abs(parts - split["tick_ms"]) * out["ticks"] / 1e3 < 1e-6
    # the span and the host clock time the same region of the tick
    assert split["tick_ms"] == pytest.approx(out["tick_ms_host_clock"],
                                             rel=0.03)
    assert out["counters"]["download_mb"] > 0


def test_train_cell_spans_on_the_cpu(tmp_path):
    # The lost host is declared about 4 s after its last heartbeat (1 s
    # heartbeats); the crash comes in the window's first quarter.
    out = _traced("qwen05b-dp4-crash", 8.0, tmp_path)
    s = out["spans"]
    assert s["runtime.step"]["parents"] == [""]
    for name in ("runtime.gather", "runtime.reduce", "runtime.apply"):
        assert s[name]["parents"] == ["runtime.step"], name
    assert s["runtime.bino_tick"]["parents"] == ["runtime.gather"]
    assert s["core.glance"]["parents"] == ["runtime.bino_tick"]
    assert s["core.plan"]["parents"] == ["runtime.bino_tick"]
    assert s["runtime.step"]["count"] >= out["steps"] > 0
    assert all(v is not None and v > 0 for v in out["split"].values())
    assert out["counters"]["detect_s"] >= 4.0
