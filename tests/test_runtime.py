"""Training-runtime integration: exactly-once gradient semantics under
faults, recovery behaviour of both strategies, checkpoint/restart — and
the ISSUE 6 chaos matrix: pinned declarative fault scripts (the same
tuple vocabulary the simulator's ``faults.apply_script`` interprets)
injected into live coordinator/host threads via ``ChaosController``,
on a deterministic ``FakeClock`` so no assertion races a real sleep.

The load-bearing invariant everywhere: a faulted run's final parameters
are BIT-identical to the fault-free run's (gradients are keyed by
(shard, microbatch), first writer wins, summed in sorted order).
"""
import os
import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.runtime import (
    ChaosController,
    FakeClock,
    RuntimeConfig,
    StepWedged,
    TrainerRuntime,
)
from repro.runtime.chaos import PINNED_SCRIPTS, parse_script
from repro.train.loop import TrainConfig

CFG = reduced_config(get_config("qwen1.5-0.5b"))
TC = TrainConfig()
HORIZON = 6.0


def _params_vec(trainer):
    return np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree.leaves(trainer.state["params"])])


def _run(recovery, steps=3, inject=None, *, script=None, fake_clock=False,
         **kw):
    clock = FakeClock(auto_advance=True) if fake_clock else None
    chaos = (ChaosController(script, horizon=HORIZON, seed=7)
             if script is not None else None)
    rt = RuntimeConfig(n_hosts=4, microbatches_per_shard=4,
                       recovery=recovery, compute_delay=0.02, **kw)
    t = TrainerRuntime(CFG, TC, rt, seq_len=32, per_shard_batch=2, seed=0,
                       clock=clock, chaos=chaos)
    try:
        reports = t.run(steps, on_step=inject)
        return _params_vec(t), reports, t.coord
    finally:
        t.shutdown()


@pytest.fixture(scope="module")
def fault_free():
    """Golden run: real clock, no chaos, differential columnar/reference
    verification enforced on every assessment tick."""
    vec, reports, _ = _run("bino", verify_columnar=True)
    return vec, reports


def test_fault_free_full_work(fault_free):
    vec, reports = fault_free
    for r in reports:
        assert r.mb_executed >= r.mb_needed
        assert not r.recoveries
        assert np.isfinite(r.metrics["loss"])


# ---------------------------------------------------------------------------
# The chaos matrix (ISSUE 6): pinned fault scripts × both recovery
# policies. Every cell must (a) complete, (b) produce BIT-identical
# parameters to the fault-free golden run. Fault timing rides the
# auto-advancing FakeClock, so wall time stays bounded while the
# failure-detection timelines play out in virtual seconds.
# ---------------------------------------------------------------------------
CHAOS_MATRIX = [(name, policy)
                for name in ("crash", "hang", "delay_hb", "drop", "dup")
                for policy in ("bino", "restart")] + [
    ("crash_restore", "bino"),
    ("hb_outage", "bino"),
    ("reorder", "bino"),
    ("cut", "bino"),
    ("crash_plus_drop", "bino"),
]


@pytest.mark.parametrize("name,policy", CHAOS_MATRIX,
                         ids=[f"{n}-{p}" for n, p in CHAOS_MATRIX])
def test_chaos_matrix_exactly_once(fault_free, name, policy):
    vec_ff, _ = fault_free
    kw = dict(restart_timeout=1.5)
    if policy == "bino":
        kw.update(repair_timeout=0.5, verify_columnar=True)
    vec, reports, _ = _run(policy, script=PINNED_SCRIPTS[name],
                           fake_clock=True, **kw)
    assert len(reports) == 3
    for r in reports:
        assert r.mb_executed >= r.mb_needed
    assert np.array_equal(vec_ff, vec), \
        f"{name}/{policy}: faulted params diverged from fault-free"
    if name.startswith("crash"):
        # a permanent host loss must surface as an explicit recovery
        assert any(r.recoveries or r.restarts for r in reports)


def test_chaos_cut_exercises_retry_backoff(fault_free):
    """A link cut from t0 eats work-item assigns; the coordinator's
    ack-deadline + jittered-backoff redelivery (and, if exhausted,
    failover) must carry the step — bit-identically."""
    vec_ff, _ = fault_free
    vec, reports, coord = _run(
        "bino", script=[("cut", 1, 0.0, 0.4)], fake_clock=True,
        repair_timeout=0.5, verify_columnar=True)
    assert np.array_equal(vec_ff, vec)
    assert coord.resend_count >= 1, "cut never exercised the retry path"


def test_chaos_duplicate_delivery_is_idempotent(fault_free):
    """Duplicated GradMessages must not double-count: mb_executed counts
    arrivals, but the gradient sum dedups on (shard, mb)."""
    vec_ff, _ = fault_free
    vec, reports, _ = _run("bino", script=PINNED_SCRIPTS["dup"],
                           fake_clock=True, verify_columnar=True)
    assert np.array_equal(vec_ff, vec)


def test_differential_decisions_under_straggler(fault_free):
    """Sim-vs-runtime differential gate: the columnar engine (shared with
    the simulator) and the per-object reference engine assess every live
    snapshot identically — enforced action-for-action inside the
    coordinator (verify_columnar), under a fault that actually makes the
    policies fire."""
    vec_ff, _ = fault_free
    vec, reports, _ = _run("bino", script=PINNED_SCRIPTS["slow"],
                           fake_clock=True, verify_columnar=True,
                           repair_timeout=0.5)
    assert np.array_equal(vec_ff, vec)


@pytest.mark.parametrize("policy,floor", [("bino", 0.2), ("restart", 1.5)])
def test_detect_silence_observed_at_each_declaration(policy, floor):
    """Each host declared lost adds its silence at declaration to
    ``detect_silence_s``: past the responsive window (4 heartbeats) under
    bino, past ``restart_timeout`` under the gang baseline."""
    _, _, coord = _run(policy, script=PINNED_SCRIPTS["crash"],
                       fake_clock=True, restart_timeout=1.5,
                       repair_timeout=0.5)
    snap = coord.metrics.snapshot()
    declared = snap.get("detections", 0) + snap.get("expiry_declares", 0)
    assert declared >= 1
    assert snap["detect_silence_s_n"] == declared
    assert snap["detect_silence_s_min"] > floor


def test_gang_restart_also_exact_but_slower(fault_free):
    vec_ff, _ = fault_free
    vec, reports, _ = _run("restart", script=PINNED_SCRIPTS["crash"],
                           fake_clock=True, restart_timeout=1.5)
    assert np.array_equal(vec_ff, vec)
    assert sum(r.restarts for r in reports) >= 1
    # the whole step re-ran: wasted microbatch executions
    assert sum(r.mb_executed for r in reports) > \
        sum(r.mb_needed for r in reports)


def test_checkpoint_restart_resumes_exactly(tmp_path, fault_free):
    vec_ff, _ = fault_free
    rt = RuntimeConfig(n_hosts=4, microbatches_per_shard=4,
                       recovery="bino", compute_delay=0.02,
                       checkpoint_dir=str(tmp_path), checkpoint_every=2)
    t1 = TrainerRuntime(CFG, TC, rt, seq_len=32, per_shard_batch=2, seed=0)
    try:
        t1.run(2)  # checkpoint at step 2
    finally:
        t1.shutdown()
    # "crash" the coordinator; a fresh trainer restores step 2 and finishes
    t2 = TrainerRuntime(CFG, TC, rt, seq_len=32, per_shard_batch=2, seed=0)
    try:
        assert t2._start_step == 2
        t2.run(1)  # step 3 (0-indexed: steps 0,1 done, now 2)
        vec = _params_vec(t2)
    finally:
        t2.shutdown()
    assert np.array_equal(vec_ff, vec)


def test_elastic_continue_with_fewer_hosts(fault_free):
    """After a permanent host loss the shards re-pack onto survivors and
    training continues (elastic scaling)."""
    vec_ff, _ = fault_free
    vec, reports, _ = _run("bino", steps=4,
                           script=PINNED_SCRIPTS["crash"], fake_clock=True,
                           repair_timeout=0.5)
    assert len(reports) == 4
    assert all(r.mb_executed >= r.mb_needed for r in reports)


def test_quorum_loss_raises_step_wedged():
    """Losing 3 of 4 hosts drops below quorum; the step rolls back, retries
    on the survivors, then surfaces StepWedged (no silent hang)."""
    script = [("crash", 1, 0.0, 0.0), ("crash", 2, 0.0, 0.0),
              ("crash", 3, 0.0, 0.0)]
    clock = FakeClock(auto_advance=True)
    chaos = ChaosController(script, horizon=HORIZON, seed=7)
    rt = RuntimeConfig(n_hosts=4, microbatches_per_shard=4,
                       recovery="bino", compute_delay=0.02,
                       step_retry_limit=1, repair_timeout=0.5,
                       step_deadline=20.0)
    t = TrainerRuntime(CFG, TC, rt, seq_len=32, per_shard_batch=2, seed=0,
                       clock=clock, chaos=chaos)
    try:
        with pytest.raises(StepWedged):
            t.run(2)
    finally:
        t.shutdown()


# ---------------------------------------------------------------------------
# The reduce barrier's compiled ordered mean (``ordered_mean``) against the
# eager formula it replaced: the same adds in the same order, so the same
# bits.
# ---------------------------------------------------------------------------
def _eager_mean(trees, denom):
    return jax.tree.map(
        lambda *xs: sum(x.astype(np.float32) for x in xs) / denom, *trees)


def _grad_tree(rng):
    """Mixed bf16 and f32 leaves, a scalar leaf, one nested group."""
    return {
        "embed": jnp.asarray(rng.standard_normal((17, 8)), jnp.bfloat16),
        "layers": {
            "w": jnp.asarray(rng.standard_normal((3, 5, 4)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((3, 5)), jnp.bfloat16)},
        "norm": jnp.asarray(rng.standard_normal(8) * 1e-3, jnp.float32),
        "scale": jnp.asarray(rng.standard_normal(()), jnp.float32),
    }


def _reinserted(tree):
    """The same tree, every dict's keys inserted in reverse order."""
    if isinstance(tree, dict):
        return {k: _reinserted(tree[k]) for k in reversed(list(tree))}
    return tree


def _bits(tree):
    return [np.asarray(l).view(np.uint32).tobytes()
            for l in jax.tree.leaves(tree)]


@pytest.mark.parametrize("case", ["bitwise", "signed_zero", "key_order"])
@pytest.mark.parametrize("n", [3, 4, 6])
def test_ordered_mean_matches_eager_sum(n, case):
    from repro.runtime.coordinator import ordered_mean
    denom = float(n)
    trees = [_grad_tree(np.random.default_rng(100 + i)) for i in range(n)]
    if case == "signed_zero":
        # every tree holds -0.0 in one place: the eager sum's leading
        # ``0 +`` gave +0.0 there, the compiled sum keeps -0.0
        trees = [jax.tree.map(lambda x: x.at[(0,) * x.ndim].set(-0.0), t)
                 for t in trees]
    got = ordered_mean(trees, np.float32(denom))
    want = _eager_mean(trees, denom)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == np.float32 and g.shape == w.shape
    if case == "signed_zero":
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert np.array_equal(np.asarray(g), np.asarray(w))
        assert _bits(got) != _bits(want)
    else:
        assert _bits(got) == _bits(want)
    if case == "key_order":
        flipped = [_reinserted(t) for t in trees]
        assert list(flipped[0]) != list(trees[0])
        assert _bits(ordered_mean(flipped, np.float32(denom))) == _bits(got)


def test_reduce_compiles_once():
    """The ordered mean compiles in the first step and never again, and
    steps after the first compile nothing at all."""
    from repro.compile_cache import CompileCounter
    from repro.runtime.coordinator import ordered_mean
    CompileCounter.install()
    ordered_mean.clear_cache()
    marks = {}

    def on_step(i, trainer):
        marks[i] = CompileCounter.snapshot()

    _, reports, _ = _run("bino", steps=4, inject=on_step)
    assert len(reports) == 4
    assert ordered_mean._cache_size() == 1
    assert CompileCounter.since(marks[1])["executables"] == 0


# ---------------------------------------------------------------------------
# Optional randomized chaos sweep: REPRO_CHAOS_EXAMPLES=N runs N extra
# random scripts (quorum-preserving kinds only) — the runtime sibling of
# the fuzz lane's REPRO_FUZZ_EXAMPLES knob.
# ---------------------------------------------------------------------------
_N_RANDOM = int(os.environ.get("REPRO_CHAOS_EXAMPLES", "0"))
_RANDOM_KINDS = ["crash_restore", "hang", "slow", "hb", "delay_hb",
                 "drop", "dup", "reorder", "cut", "part", "disk"]


@pytest.mark.parametrize("i", range(_N_RANDOM))
def test_chaos_random_scripts(fault_free, i):
    vec_ff, _ = fault_free
    rng = random.Random(1000 + i)
    script = [(rng.choice(_RANDOM_KINDS), rng.randrange(4),
               round(rng.random() * 0.5, 3), round(rng.random(), 3))
              for _ in range(rng.randrange(1, 3))]
    policy = rng.choice(["bino", "restart"])
    kw = dict(restart_timeout=1.5)
    if policy == "bino":
        kw.update(repair_timeout=0.5, verify_columnar=True)
    vec, reports, _ = _run(policy, script=script, fake_clock=True, **kw)
    assert len(reports) == 3
    assert np.array_equal(vec_ff, vec), f"script {script} diverged"


# ---------------------------------------------------------------------------
# FakeClock semantics (the anti-flake substrate itself)
# ---------------------------------------------------------------------------
def test_fake_clock_manual_advance_is_deterministic():
    clk = FakeClock(start=1000.0)
    woke = []

    def sleeper():
        clk.sleep(5.0)
        woke.append(clk.time())

    th = threading.Thread(target=sleeper, daemon=True)
    th.start()
    deadline = time.time() + 2.0
    while not clk._waiters and time.time() < deadline:
        time.sleep(0.001)
    clk.advance(4.9)
    time.sleep(0.05)
    assert not woke, "sleeper woke before its deadline"
    clk.advance(0.2)
    th.join(timeout=2.0)
    assert woke and woke[0] == pytest.approx(1005.1)
    clk.close()


def test_fake_clock_auto_advance_jumps_to_deadline():
    clk = FakeClock(start=0.0, auto_advance=True)
    t0 = time.time()
    clk.sleep(30.0)  # half a real minute, virtually
    assert time.time() - t0 < 5.0
    assert clk.time() >= 30.0
    clk.close()


def test_parse_script_named_and_inline():
    assert parse_script("crash") == PINNED_SCRIPTS["crash"]
    assert parse_script("cut:1:0.25:0.5,dup:0:0:0.9") == \
        [("cut", 1, 0.25, 0.5), ("dup", 0, 0.0, 0.9)]
