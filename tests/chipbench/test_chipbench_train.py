"""The training cell off-chip at a tiny size: the real harness drives the
trainer under the crash script, the check holds its first steps to the
plain reference and its parameters to a fault-free replay, and a broken
timed path or the low-precision control comes out not correct."""
import os
import re
import subprocess

import pytest

from chipbench_tiny import run_cell
from chipbench_tiny import train as tiny_train

CELL = "qwen05b-dp4-crash"
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def test_train_cell_rehearsal():
    rc, lines, last = run_cell(CELL, seconds=3.0)
    assert rc == 0 and last is not None
    assert set(last) == KEYS and list(last)[-1] == "checks"
    assert last["correct"] is True, last["checks"]
    assert set(last["metrics"]) == {"tokens_per_s", "setup_s"}
    assert last["checks"]["fingerprint_mismatch"]["value"] == 0
    assert last["checks"]["crash_recovered"]["value"] == 1
    window = [x for x in lines if x.startswith("window:")][0]
    assert "crash_step=None" not in window


def test_train_control_is_not_correct():
    """The reference with float8 products, in the program's place, fails
    a limit the program meets."""
    import readings as RD
    from chipbench import harness
    limits = harness.load_limits(CELL)
    out = RD.readings(CELL, 7, 3.0, tweak=tiny_train)
    assert out["correct"] is True
    ctl = out["control"]
    assert any(ctl[k] > limits[k]
               for k in ("loss_gap", "grad_gap", "update_gap"))
    assert out["fault_unchanged"]["update_gap"] == pytest.approx(1.0)


def _unchanged(apply_fn):
    return lambda state, grads: state


def _half_batch(grad_fn):
    def f(params, batch):
        half = {k: v[: max(1, v.shape[0] // 2)] for k, v in batch.items()}
        return grad_fn(params, half)
    return f


@pytest.mark.parametrize("hooks", [
    {"apply_fn": _unchanged},
    {"grad_fn": _half_batch},
], ids=["state_unchanged", "half_batch"])
def test_train_broken_path_is_not_correct(hooks):
    rc, _lines, last = run_cell(CELL, seconds=2.0, hooks=hooks)
    assert rc == 0 and last is not None
    assert last["correct"] is False


def test_train_cell_survives_a_process_pause():
    """The whole process stands still for 2.5 s inside the window, as a
    shared host now and then does: the failure detector, at the
    configuration's heartbeat, declares no live host dead for it, and the
    run ends correct (at a 50 ms heartbeat the step wedges: quorum lost)."""
    child = []

    def stall_once(apply_fn):
        calls = [0]

        def f(state, grads):
            calls[0] += 1
            # the three set-up steps come first; pause once, in the window
            if calls[0] == 4 and not child:
                child.append(subprocess.Popen(
                    ["sh", "-c", f"sleep 1.2; kill -STOP {os.getpid()}; "
                     f"sleep 2.5; kill -CONT {os.getpid()}"]))
            return apply_fn(state, grads)
        return f

    try:
        rc, lines, last = run_cell(CELL, seconds=5.0,
                                   hooks={"apply_fn": stall_once})
    finally:
        for p in child:
            p.wait()
    assert child, "the pause was never started"
    assert rc == 0 and last is not None
    window = [x for x in lines if x.startswith("window:")][0]
    assert float(re.search(r"step_max_ms=([0-9.]+)", window)[1]) >= 2000.0
    assert last["correct"] is True, last["checks"]
