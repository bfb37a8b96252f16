"""The benchmark's own traffic generators are deterministic per seed,
and every seed runs the same work in another order; the command refuses
to run without a chip or without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on the path)
from chipbench import harness
from chipbench.drivers import fleet as F
from chipbench.reference import qwen as Q

ROOT = Path(__file__).resolve().parents[2]


def _mix():
    return harness.load_json(ROOT / "chipbench/traffic/tenants512.json")


def test_pacman_deck_is_seeded_and_holds_every_share():
    mix = _mix()
    a = F.deck(mix["mix"], 512, np.random.default_rng(1))
    b = F.deck(mix["mix"], 512, np.random.default_rng(1))
    c = F.deck(mix["mix"], 512, np.random.default_rng(2))
    assert a == b and a != c
    assert sorted(a) == sorted(c)          # the same jobs, another order
    sizes = [gb for gb, _ in a]
    assert [sizes.count(s) for s in (1.0, 10.0, 50.0, 100.0)] \
        == [435, 41, 26, 10]
    assert {bench for _, bench in a} == set(mix["mix"]["benches"])


def test_job_stream_and_first_submissions_are_seeded():
    mix = _mix()
    s1 = F.JobStream(mix, 512, 10_000, np.random.default_rng(5))
    s2 = F.JobStream(mix, 512, 10_000, np.random.default_rng(5))
    assert [s1.next() for _ in range(700)] == [s2.next() for _ in range(700)]
    t1 = F.first_submissions(512, 60.0, np.random.default_rng(3))
    t2 = F.first_submissions(512, 60.0, np.random.default_rng(3))
    assert np.array_equal(t1, t2)
    slots = np.floor(np.sort(t1) / (60.0 / 512)).astype(int)
    assert np.array_equal(slots, np.arange(512))   # one per slice
    tera = harness.load_json(ROOT / "chipbench/traffic/terasort1.json")
    gb, bench = F.JobStream(tera, 1, 10_000,
                            np.random.default_rng(0)).next()
    assert bench == "terasort" and gb * 1024 / 128 == 40_000


def _crash_run(seed):
    """One tiny TeraSort under the paper's injection at half its maps."""
    from repro.sim import JobSpec
    from repro.sim.mapreduce import BINO_PARAMS, Simulation
    sim = Simulation(policy="bino", seed=seed, n_workers=24, n_containers=8,
                     params=BINO_PARAMS, net="flat")
    crash = F.MapProgressCrash(sim, 0.5, 600.0)
    job = sim.submit(JobSpec("j0", "terasort", 48 * F.SPLIT_GB,
                             submit_time=0.0))
    at_fire = []
    fire = crash._fire
    crash._fire = lambda j: (at_fire.append(j.maps_completed()), fire(j))
    crash.arm(job)
    sim.engine.run(until=800.0)
    return crash, at_fire, len(job.maps)


def test_map_progress_crash_is_seeded():
    a, fa, n_maps = _crash_run(9)
    b, fb, _ = _crash_run(9)
    assert a.crashed == 1 and a.victims == b.victims and fa == fb
    assert fa[0] >= n_maps / 2            # fired at half the map phase
    t, victim = a.victims[0]
    assert a.restored == 1 and t < 200.0  # restored 600 s later
    with pytest.raises(ValueError):
        F.MapProgressCrash(None, 0.0, 600.0)


def test_tenants_come_from_the_configuration():
    config = harness.load_json(ROOT / "chipbench/configs/yarn-fleet-10k.json")
    assert F.tenants_of(config, _mix()) == config["max_running_apps"] == 512
    assert F.tenants_of(config, {"tenants": 1}) == 1
    with pytest.raises(ValueError):
        F.tenants_of(config, {"tenants": 513})


def test_training_tokens_and_script_are_seeded():
    a = Q.tokens(123, 1, 4, 2, 512, 151936)
    assert a.shape == (2, 513) and a.dtype == np.int32
    assert np.array_equal(a, Q.tokens(123, 1, 4, 2, 512, 151936))
    assert not np.array_equal(a, Q.tokens(123, 2, 4, 2, 512, 151936))
    assert not np.array_equal(a, Q.tokens(124, 1, 4, 2, 512, 151936))
    crash = harness.load_json(ROOT / "chipbench/traffic/crash-once.json")
    # benchmarks/perf_runtime.CRASH_SCRIPT, copied
    assert crash["script"] == [["crash", 1, 0.02, 0.0]]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "fleet10k-tenants512", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_command_refuses_the_cpu():
    p = _command(ROOT)
    assert p.returncode != 0 and _no_result(p), p.stdout + p.stderr


def test_command_refuses_a_tree_without_the_program(tmp_path):
    m = harness.load_manifest()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for path in m["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and _no_result(p), p.stdout + p.stderr
