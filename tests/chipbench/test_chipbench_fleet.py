"""The fleet cells off-chip at a tiny size: the real harness drives the
simulator with the device backend (float64, Pallas interpreted), the
check holds the window's answers to the plain reference, and a broken
timed path or the low-precision control comes out not correct."""
import numpy as np
import pytest

from chipbench_tiny import fleet as tiny_fleet
from chipbench_tiny import run_cell

CELLS = ["fleet10k-tenants512", "fleet10k-terasort1"]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.mark.parametrize("workload", CELLS)
def test_fleet_cell_rehearsal(workload):
    rc, lines, last = run_cell(workload)
    assert rc == 0 and last is not None
    assert set(last) == KEYS and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    from chipbench import harness
    want = {m["name"] for m in harness.cell_metrics(
        harness.load_manifest(), workload, trace=False)}
    assert set(last["metrics"]) == want
    assert any(last["metrics"][n]["unit"] == "sim-s/s" for n in want)
    assert last["checks"]["ticks_compared"]["value"] >= 1
    # every compared tick is one on which the policy sampled zeta
    assert last["checks"]["zeta_compared"]["value"] >= 1
    assert any(x.startswith("window gc:") for x in lines)
    assert any(x.startswith("window compiles:") for x in lines)


def test_fleet_traced_rehearsal_reports_host_metrics():
    rc, _lines, last = run_cell("fleet10k-tenants512", trace=1)
    assert rc == 0 and last["correct"] is True
    names = set(last["metrics"])
    # the CPU has no device plane: device metrics are left out, not 0
    assert {"sim.host_ms_per_sim_s", "assess.tick_ms",
            "assess.upload_mb"} <= names
    assert "kernels.assess_roofline" not in names
    assert "device.idle.sim" not in names
    assert "breakdown" in last


def test_fleet_control_is_not_correct():
    """The reference in bfloat16, in the program's place, fails the
    limits the program meets."""
    import readings as RD
    from chipbench import harness
    limits = harness.load_limits("fleet10k-tenants512")
    out = RD.readings("fleet10k-tenants512", 11, 2.0, tweak=tiny_fleet)
    assert out["correct"] is True
    ctl = out["control"]
    assert ctl["flip_margin"] > limits["flip_margin"] \
        or ctl["zeta_gap"] > limits["zeta_gap"] \
        or ctl["exact_mismatch"] > limits["exact_mismatch"]


class _Broken:
    """The device backend, broken underneath the window."""

    def __init__(self, inner, fault):
        self.inner = inner
        self.name = inner.name
        self.fault = fault

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def spatial_hits(self, arr, now, active, nh):
        out = np.array(self.inner.spatial_hits(arr, now, active, nh))
        if self.fault == "answer_altered" and out.size:
            out[0, 0] = ~out[0, 0]
        return out

    def temporal_zeta(self, arr, now, active, samp, init, prevk):
        from repro.accel.base import TMARK, TPROG
        keep = [np.array(arr.scratch(TMARK, np.int64, -1)),
                np.array(arr.scratch(TPROG, np.float64, np.nan))]
        zn, zp = self.inner.temporal_zeta(arr, now, active, samp, init,
                                          prevk)
        if self.fault == "state_unchanged":
            arr.scratch(TMARK, np.int64, -1)[:] = keep[0]
            arr.scratch(TPROG, np.float64, np.nan)[:] = keep[1]
        if self.fault == "half_batch" and len(zn) > 1:
            zn = np.array(zn)
            zp = np.array(zp)
            zn[len(zn) // 2:] = np.nan
            zp[len(zp) // 2:] = np.nan
        return zn, zp

    def failure_masks(self, *a):
        return self.inner.failure_masks(*a)

    def late_victims(self, *a):
        return self.inner.late_victims(*a)

    def winning(self, *a):
        return self.inner.winning(*a)

    def reap_rows(self, *a):
        return self.inner.reap_rows(*a)


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged",
                                   "half_batch"])
def test_fleet_broken_path_is_not_correct(fault):
    rc, _lines, last = run_cell(
        "fleet10k-tenants512",
        hooks={"backend": lambda inner: _Broken(inner, fault)})
    assert rc == 0 and last is not None
    assert last["correct"] is False and last["failed"] >= 1
