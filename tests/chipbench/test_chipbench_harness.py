"""The benchmark's yardstick on the CPU: the trace reduction, the peaks
table, the roofline and MFU arithmetic, the manifest's contract, and the
registry that finds configurations, mixes, limits and metrics by name."""
import json
import shutil
import string
import sys
from pathlib import Path

import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on the path)
from chipbench import costs, harness
from chipbench import trace as tr

ROOT = Path(__file__).resolve().parents[2]
DEV = "/device:TPU:0"


def _ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur))


def test_trace_busy_union_idle_share_and_programs():
    events = [
        _ev("/host:CPU", "python", "chipbench.window", 0, 1000),
        _ev("/host:CPU", "python", "chipbench.engine.chunk", 100, 500),
        _ev("/host:CPU", "python", "chipbench.backend.spatial_hits", 150,
            100),
        # overlapping ops count once: [100, 250) and [600, 700)
        _ev(DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8] %p)", 100,
            100),
        _ev(DEV, "XLA Ops", "spatial_kernel", 150, 100),
        _ev(DEV, "XLA Ops", "fusion.1", 600, 100),
        # clipped to the window
        _ev(DEV, "XLA Ops", "late", 950, 100),
        _ev(DEV, "XLA Modules", "jit_pallas_spatial(12)", 100, 150),
        _ev(DEV, "XLA Modules", "jit_pallas_spatial(12)", 600, 50),
        _ev(DEV, "XLA Modules", "jit_failure_core(3)", 650, 50),
    ]
    s = tr.reduce_events(events)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(300e-9)
    assert s["idle_share"] == pytest.approx(0.7)
    assert s["programs"]["pallas_spatial"] == pytest.approx(200e-9)
    assert s["programs"]["failure_core"] == pytest.approx(50e-9)
    assert s["program_calls"]["pallas_spatial"] == 2
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(200e-9)
    assert ops["late"] == pytest.approx(50e-9)
    gaps = s["breakdown"]["idle_gaps"]
    # longest gap [250, 600) sits inside the engine chunk span
    assert gaps[0][0] == "host:engine.chunk"
    assert gaps[0][1] == pytest.approx(350e-9)
    assert sum(g[1] for g in gaps) == pytest.approx(700e-9)
    assert tr.program_seconds(s, ("pallas_spatial", "nothing")) \
        == pytest.approx(200e-9)
    assert tr.program_seconds(s, ("nothing",)) is None


def test_trace_averages_over_devices_and_names_programs():
    events = [_ev("/device:TPU:0", "XLA Ops", "a", 0, 10),
              _ev("/device:TPU:1", "XLA Ops", "a", 0, 30)]
    s = tr.reduce_events(events, window=(0, 40))
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx(20e-9)
    assert tr.program_name("jit_loss_fn(7)") == "loss_fn"
    assert tr.program_name("pallas_reap") == "pallas_reap"
    assert tr.op_name("%pallas_spatial.1 = (f32[8]) custom-call(s32[8] %a)") \
        == "pallas_spatial.1"
    assert tr.merged([(1, 3), (0, 2), (5, 6)]) == [(0, 3), (5, 6)]


def test_trace_reads_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with harness.span("window"):
        with harness.span("engine.chunk"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert path is not None
    names = {e[2] for e in tr.load_events(path)}
    assert {"chipbench.window", "chipbench.engine.chunk"} <= names
    s = tr.reduce_dir(str(tmp_path))
    assert s["window_s"] > 0


def test_peaks_refuse_an_unknown_device():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")
    src = json.loads(costs.PEAKS.read_text())["source"]
    assert src == "Google Cloud documentation, TPU v5e"


def test_assess_bytes_from_shapes():
    rows, jobs, n = 65536, 512, 10000
    spatial = rows * 14 * 4 + jobs * 2 * n * 2 * 4 + jobs * n
    assert costs.assess_bytes("spatial_hits", rows, jobs, n) == spatial
    assert costs.assess_bytes("failure_masks", rows, jobs, n) \
        == n * 16 + 2 * n
    # per tick its real rows and jobs, not the program's padding
    work = {"spatial_hits": [(rows, jobs), (rows, jobs), (70000, 500)],
            "winning": [(rows, jobs)] * 2, "reap_rows": [(rows, jobs)]}
    total = costs.window_assess_bytes(work, n)
    assert total == 2 * spatial \
        + costs.assess_bytes("spatial_hits", 70000, 500, n) \
        + 2 * costs.assess_bytes("winning", rows, jobs, n) \
        + costs.assess_bytes("reap_rows", rows, jobs, n)
    assert costs.assess_bytes("spatial_hits", 70000, 500, n) < \
        costs.assess_bytes("spatial_hits", 131072, 512, n)


def test_train_flops_of_qwen15_05b():
    cfg = harness.load_json(
        ROOT / "chipbench/configs/qwen1.5-0.5b-dp4.json")["model"]
    d, L, ff, v, s = 1024, 24, 2816, 151936, 512
    per_layer = 4 * d * d + 3 * d + 3 * d * ff
    want = 6.0 * (L * per_layer + v * d) + 12.0 * L * d * s
    assert costs.train_flops_per_token(cfg, s) == pytest.approx(want)
    # every published parameter but the embedding lookup multiplies
    n_params = L * (per_layer + 2 * d) + v * d + d
    assert n_params == 463_987_712


NAME = set(string.ascii_letters + string.digits + "_.-")
UNIT = set(string.ascii_letters + string.digits + "_/%.-")


def test_manifest_meets_its_contract():
    m = harness.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= m["run_seconds"] <= 51
    cells = {w["name"]: w for w in m["workloads"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    configs = {c["name"]: c for c in m["configs"]}
    names = list(cells) + list(e2e) + list(configs) \
        + [p["name"] for p in m["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in cells.values()]:
        assert set(n) <= NAME and len(n) <= 64 and n[0] not in ".-"
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in cells.values())
    reported = {w: {"setup_s"} for w in cells}
    for e in m["end_to_end"]:
        assert set(UNIT) >= set(e["unit"]) and 0 < e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
        for w in e.get("workloads", cells):
            reported[w].add(e["name"])
    assert e2e["setup_s"]["bound"] == 0.25
    for w, cell in cells.items():
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] == 1 and len(cell["why"]) <= 200
        assert len(reported[w]) >= 2
        assert (ROOT / "chipbench/traffic" / f"{cell['traffic']}.json"
                ).is_file()
        assert (ROOT / "chipbench/limits" / f"{w}.json").is_file()
    layers = {}
    for p in m["per_layer"]:
        assert set(UNIT) >= set(p["unit"])
        assert p["moves"] in e2e and p["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        for w in p["workloads"]:
            assert p["moves"] in reported[w]
        assert harness.load_metric(p["name"]).read
        base = harness.base_names(p["name"])
        if any(b.endswith("_roofline") or "mfu" in b for b in base):
            assert p["unit"] == "%"
        layers.setdefault(p["layer"], p["layer"])
        assert "\n" not in p["layer"] and len(p["layer"]) <= 200
    assert len(json.dumps(m)) < 64 * 1024


def test_split_metric_names_read_their_base():
    assert harness.base_names("assess.tick_ms.one_job") == [
        "assess.tick_ms.one_job", "assess.tick_ms", "assess"]
    a = harness.load_metric("assess.tick_ms.one_job")
    run = harness.Run("x", 1, 1.0, True)
    run.counters.update(ticks=4, assess_wall_s=2.0)
    assert a.read(run) == pytest.approx(500.0)
    with pytest.raises(harness.ManifestError):
        harness.load_metric("no.such_metric")


def test_every_cell_reports_a_per_layer_metric():
    m = harness.load_manifest()
    for w in m["workloads"]:
        assert harness.cell_metrics(m, w["name"], trace=True)
        assert {e["name"] for e in harness.cell_metrics(
            m, w["name"], trace=False)} >= {"setup_s"}


def test_new_cell_is_files_and_manifest_entries_only(tmp_path):
    """A configuration, a mix, a cell and a metric added as new files and
    manifest entries are found by name; no harness file changes."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "chipbench").rglob("*.py")}
    cb = tmp_path / "chipbench"
    cfg = json.loads((cb / "configs/yarn-fleet-10k.json").read_text())
    cfg.update(name="yarn-fleet-2k", n_workers=2000)
    (cb / "configs/yarn-fleet-2k.json").write_text(json.dumps(cfg))
    mix = json.loads((cb / "traffic/tenants512.json").read_text())
    mix["tenants"] = 64
    (cb / "traffic/tenants64.json").write_text(json.dumps(mix))
    (cb / "limits/fleet2k-tenants64.json").write_text(
        (cb / "limits/fleet10k-tenants512.json").read_text())
    (cb / "metrics/sim.jobs_per_tick.py").write_text(
        "def read(run):\n    return run.counters.get('jobs_active')\n")
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "yarn-fleet-2k", "source": "x",
                         "file": "chipbench/configs/yarn-fleet-2k.json",
                         "reduced": ["n_workers"], "why": "x"})
    m["workloads"].append({"name": "fleet2k-tenants64",
                           "config": "yarn-fleet-2k",
                           "traffic": "tenants64", "chips": 1, "why": "x"})
    m["end_to_end"][0].setdefault("workloads", []).append(
        "fleet2k-tenants64")
    m["per_layer"].append({"name": "sim.jobs_per_tick", "unit": "jobs",
                           "better": "higher", "source": "program_counter",
                           "layer": "simulator host path",
                           "moves": "sim_rate",
                           "workloads": ["fleet2k-tenants64"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chipbench_copy_harness", cb / "harness.py")
    h = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(h)
    man = h.load_manifest(tmp_path)
    cell, config, traffic = h.find_cell(man, "fleet2k-tenants64", tmp_path)
    assert config["n_workers"] == 2000 and traffic["tenants"] == 64
    assert h.load_limits("fleet2k-tenants64")["exact_mismatch"] == 0
    names = [x["name"] for x in h.cell_metrics(man, "fleet2k-tenants64",
                                                trace=True)]
    assert names == ["sim.jobs_per_tick"]
    run = h.Run("fleet2k-tenants64", 1, 1.0, True)
    run.counters["jobs_active"] = 64
    assert h.load_metric("sim.jobs_per_tick").read(run) == 64
    assert h.load_driver(traffic["driver"]).__name__.endswith("fleet")
    assert before == {p: (tmp_path / p).read_bytes() for p in before}
    sys.modules.pop("chipbench_copy_harness", None)


def test_run_keeps_seeds_apart_and_checks_limits():
    run = harness.Run("x", 2 ** 31 + 5, 1.0, False)
    a = run.seeds(3)
    assert a == harness.Run("x", 2 ** 31 + 5, 1.0, False).seeds(3)
    assert len(set(a)) == 3 and all(0 <= s < 2 ** 32 for s in a)
    assert a != harness.Run("x", 2 ** 31 + 6, 1.0, False).seeds(3)
    assert not run.correct            # nothing compared is not correct
    run.check("gap", 1e-7, 1e-4)
    run.check_at_least("ticks", 3, 1)
    assert run.correct
    run.check("mismatch", 1, 0)
    assert not run.correct
