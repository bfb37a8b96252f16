"""``chip_smoke.py`` on the CPU: its phase functions at a tiny size
(float64, Pallas interpreted), the shadow comparison they rest on, and
its refusal to run anywhere but on a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("policy", ["bino", "yarn"])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_testbed_phase_finishes_and_agrees(smoke, backend, policy):
    r = smoke.phase_testbed(backend, policy, n_workers=20, input_gb=2.0)
    counts = r["tally"].counts
    assert r["tally"].outside() == 0
    # On the CPU the device path is bit-exact: nothing even tolerated.
    assert all(c["tolerated"] == 0 for c in counts.values())
    assert set(smoke.POLICY_METHODS[policy]) <= set(counts)
    assert r["ticks"] > 0 and np.isfinite(r["jct_s"])


def test_check_tally_requires_every_method(smoke):
    from repro.accel.shadow import Tally
    tally = Tally()
    tally.add("reap_rows", "equal", 3)
    smoke._check_tally("t", tally, ("reap_rows",))
    with pytest.raises(AssertionError, match="late_victims"):
        smoke._check_tally("t", tally, ("reap_rows", "late_victims"))


def test_fleet_yarn_phase_reaches_late(smoke):
    r, _sim = smoke.phase_fleet("pallas", "yarn", n_workers=60,
                                sim_seconds=60.0)
    assert r["tally"].outside() == 0
    assert r["tally"].counts["late_victims"]["equal"] > 0


def test_fleet_and_sweep_phases(smoke):
    r, sim = smoke.phase_fleet("pallas", "bino", n_workers=60,
                               sim_seconds=40.0)
    assert r["rows"] > 0 and r["capacity"] >= r["rows"]
    assert r["upload_bytes"] > 0 and r["tally"].outside() == 0
    s = smoke.phase_sweep(sim, n_scenarios=8)
    assert s["tally"].outside() == 0
    assert set(s["kinds"]) == {"crash", "delay", "mof_loss", "fetch_quorum"}
    assert s["tally"].counts["late_victims"]["equal"] > 0


def test_runtime_phase_bit_identical_under_crash(smoke):
    r = smoke.phase_runtime(reduced=True, seq_len=16, steps=8)
    assert r["bit_identical"] and r["recoveries"] > 0
    assert np.all(np.isfinite(r["losses_crash"]))
    assert r["losses_crash"] == r["losses_fault_free"]
    assert r["grad_step_bytes"]["argument"] > 0


def test_shadow_flags_a_wrong_device(smoke):
    # The comparison must catch a device that is actually wrong.
    from repro.accel.numpy_backend import NumpyBackend
    from repro.accel.shadow import ShadowBackend

    class Wrong(NumpyBackend):
        name = "wrong"

        def spatial_hits(self, arr, now, active, neighborhoods):
            return ~super().spatial_hits(arr, now, active, neighborhoods)

    shadow = ShadowBackend(Wrong())
    from benchmarks.common import crash_fault
    from repro.sim import JobSpec, Simulation
    sim = Simulation(policy="bino", seed=0, assess_backend=shadow)
    crash_fault(0.4)(sim, sim.submit(JobSpec("j0", "terasort", 1.0)))
    sim.run()
    assert shadow.tally.counts["spatial_hits"]["outside"] > 0
    assert shadow.tally.examples


def test_main_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_refuses(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
