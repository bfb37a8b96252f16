"""Driver of the live data-parallel trainer (``TrainerRuntime``) under a
chaos script.

The configuration file holds the model's published ``config.json`` and
the trainer's settings (``runtime``, ``trainer``); the traffic file the
steps and the fault:

- ``first_steps``: committed steps run in set-up through the window's
  own call (``TrainerRuntime.run``) and feed; the reference follows them;
- ``script``: the chaos script (``benchmarks/perf_runtime.CRASH_SCRIPT``,
  copied: host index 1 lost 0.02 s after release), ``horizon``,
  ``restart_timeout``, ``repair_timeout``;
- ``release_in_first``: the script is released once, at an instant
  drawn from the seed within that share of the window.

The weights are the benchmark's (``reference.qwen.make_params``, one
jitted call from the seed), installed into the trainer's state before
its first step; the token batches are the benchmark's too
(``reference.qwen.tokens``), fed through the trainer's ``batch_fn``.

The check, once the window has closed and the trainer is freed:

- the first steps against the plain float32 reference: each step's
  loss, the per-leaf norms of the first clipped gradient (from the
  optimizer's first moment after step one) and of the parameters'
  change over the first steps;
- exactly-once: the parameters after the step the crash disturbed equal,
  bit for bit (a 64-bit fingerprint per leaf), those of a fault-free
  run of the same steps from the same weights.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference import qwen as ref


class _WindowEnd(Exception):
    pass


@jax.jit
def _fingerprint(leaves):
    out = []
    for x in leaves:
        bits = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        u = jax.lax.bitcast_convert_type(x, bits).astype(
            jnp.uint32).reshape(-1)
        i = jnp.arange(u.size, dtype=jnp.uint32)
        out.append(jnp.sum(u * (i * jnp.uint32(2654435761) | 1),
                           dtype=jnp.uint32))
        out.append(jnp.sum((u ^ i) * jnp.uint32(40503), dtype=jnp.uint32))
    return jnp.stack(out)


def fingerprint(params) -> np.ndarray:
    """Two 32-bit position-weighted sums of every leaf's bit pattern."""
    return np.asarray(_fingerprint(jax.tree.leaves(params)))


@dataclasses.dataclass
class Train:
    trainer: object
    chaos: object
    model_cfg: dict
    config: dict
    traffic: dict
    seed: int
    data_seed: int
    release_u: float
    first: Dict[str, object]
    crash_step: Optional[int] = None
    crash_fp: Optional[np.ndarray] = None
    released_late_s: float = 0.0
    want: Optional[dict] = None


def _program_config(config: dict):
    from repro.configs import get_config, reduced_config
    cfg = get_config(config["program_arch"])
    if config.get("program_reduced"):
        cfg = reduced_config(cfg)    # the CPU tests' tiny size
    hf = config["model"]
    got = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
           "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.n_kv_heads,
           "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
           "rope_theta": cfg.rope_theta,
           "rms_norm_eps": cfg.norm_eps,
           "tie_word_embeddings": cfg.tie_embeddings}
    bad = {k: (v, hf[k]) for k, v in got.items() if v != hf[k]}
    tr = config["trainer"]
    for key, have in (("param_dtype", cfg.param_dtype),
                      ("compute_dtype", cfg.activation_dtype)):
        if have != tr[key]:
            bad[key] = (have, tr[key])
    if bad:
        raise ValueError(f"the program's {config['program_arch']} differs "
                         f"from the configuration: {bad}")
    return cfg


def build(run, config: dict, traffic: dict, *, seed: int, data_seed: int,
          chaos: bool):
    """A trainer with the benchmark's weights and feed installed."""
    from repro.optim.adamw import adamw_init
    from repro.runtime import ChaosController, RuntimeConfig, TrainerRuntime
    from repro.train.loop import TrainConfig

    cfg = _program_config(config)
    r = config["runtime"]
    tr = config["trainer"]
    rt = RuntimeConfig(
        n_hosts=int(r["n_hosts"]),
        microbatches_per_shard=int(r["microbatches_per_shard"]),
        recovery=r["recovery"], compute_delay=float(r["compute_delay"]),
        heartbeat_period=float(r["heartbeat_period"]),
        restart_timeout=float(traffic["restart_timeout"]),
        repair_timeout=float(traffic["repair_timeout"]))
    ctl = None
    if chaos:
        script = [tuple(x) for x in traffic["script"]]
        ctl = ChaosController(script, horizon=float(traffic["horizon"]),
                              seed=seed, defer_arm=True)
    tc = TrainConfig(learning_rate=float(tr["learning_rate"]),
                     b1=float(tr["b1"]), b2=float(tr["b2"]),
                     weight_decay=float(tr["weight_decay"]),
                     grad_clip_norm=float(tr["grad_clip_norm"]),
                     remat=r["remat"])
    t = TrainerRuntime(cfg, tc, rt, seq_len=int(r["seq_len"]),
                       per_shard_batch=int(r["per_shard_batch"]),
                       seed=seed, chaos=ctl)
    old = t.coord.state["params"]
    params = ref.make_params(config["model"], seed,
                             jnp.dtype(tr["param_dtype"]))
    if jax.tree.structure(old) != jax.tree.structure(params) or any(
            a.shape != b.shape or a.dtype != b.dtype for a, b in
            zip(jax.tree.leaves(old), jax.tree.leaves(params))):
        raise ValueError("the benchmark's weights do not fit the trainer")
    del old
    t.coord.state = {"params": params, "opt": adamw_init(params),
                     "step": jnp.zeros((), jnp.int32)}
    vocab = int(config["model"]["vocab_size"])
    b, s = int(r["per_shard_batch"]), int(r["seq_len"])

    def batch_fn(ds):
        tok = ref.tokens(data_seed, ds.shard_id, ds.offset, b, s, vocab)
        return {"tokens": jnp.asarray(tok[:, :-1]),
                "labels": jnp.asarray(tok[:, 1:])}

    t.coord.batch_fn = batch_fn
    for h in t.coord.hosts.values():
        h.batch_fn = batch_fn
        h.grad_fn = run.hook("grad_fn", h.grad_fn)
        h.set_params(params)
    t.coord.apply_fn = run.hook("apply_fn", t.coord.apply_fn)
    step = t.coord.run_step

    def run_step(i):
        with harness.span("coordinator.run_step"):
            return step(i)
    t.coord.run_step = run_step
    gc.collect()
    return t, ctl


def setup(run, config: dict, traffic: dict) -> Train:
    seeds = run.seeds(3)
    seed = seeds[0] % (2 ** 31)
    t, ctl = build(run, config, traffic, seed=seed, data_seed=seeds[1],
                   chaos=True)
    b1 = float(config["trainer"]["b1"])
    first: Dict[str, object] = {}
    # Compile the microbatch step once before four host threads race to
    # the same cold jit.
    batch0 = t.coord.batch_fn(t.coord.datastates[0])
    jax.block_until_ready(t.grad_step(t.state["params"], batch0))
    del batch0

    def on_step(i, trainer):
        if i == 1:
            g1 = jax.tree.map(lambda x: x / (1.0 - b1),
                              trainer.state["opt"]["m"])
            first["grad_norms"] = ref.leaf_norms(g1)
            first["first_grad"] = ref.host_leaves(g1)
            del g1

    n_first = int(traffic["first_steps"])
    with harness.span("setup.first_steps"):
        reps = t.run(n_first, on_step=on_step)
    first["losses"] = [float(r.metrics["loss"]) for r in reps]
    p0 = ref.make_params(config["model"], seed,
                         t.state["params"]["embed"].dtype)
    first["update_norms"] = ref.leaf_norms(jax.tree.map(
        lambda a, b: a.astype("float32") - b.astype("float32"),
        t.state["params"], p0))
    del p0
    fingerprint(t.state["params"])       # compiled here, not in the window
    # The window resumes after the committed first steps.
    t._start_step = n_first
    run.note(f"first steps: losses={first['losses']} "
             f"walls_s={[round(r.wall_s, 4) for r in reps]}")
    return Train(trainer=t, chaos=ctl, model_cfg=config["model"],
                 config=config, traffic=traffic, seed=seed,
                 data_seed=seeds[1],
                 release_u=float(np.random.default_rng(seeds[2]).random()),
                 first=first)


def window(run, state: Train, seconds: float, traffic: dict) -> None:
    t = state.trainer
    coord = t.coord
    n0 = len(coord.reports)
    t0 = time.perf_counter()
    release_at = t0 + state.release_u * float(
        traffic["release_in_first"]) * seconds
    released = False

    def on_step(i, trainer):
        nonlocal released
        now = time.perf_counter()
        if not released and now >= release_at:
            state.released_late_s = now - release_at
            with harness.span("chaos.release"):
                state.chaos.release()
            released = True
        reps = coord.reports
        if state.crash_step is None and len(reps) > n0 \
                and reps[-1].recoveries:
            state.crash_step = reps[-1].step
            with harness.span("check.fingerprint"):
                state.crash_fp = fingerprint(trainer.state["params"])
        if now - t0 >= seconds:
            raise _WindowEnd

    try:
        with harness.span("trainer.run"):
            t.run(10 ** 9, on_step=on_step)
    except _WindowEnd:
        pass
    wall = time.perf_counter() - t0
    reps = coord.reports[n0:]
    r = state.config["runtime"]
    tokens = int(r["n_hosts"]) * int(r["microbatches_per_shard"]) \
        * int(r["per_shard_batch"]) * int(r["seq_len"])
    walls = np.array([x.wall_s for x in reps])
    run.attempted = len(reps)
    run.e2e["tokens_per_s"] = len(reps) * tokens / wall
    med = float(np.median(walls)) if len(reps) else 0.0
    disturbed = [x for x in reps if x.recoveries]
    run.counters.update({
        "window_wall_s": wall, "steps": len(reps), "tokens_per_step": tokens,
        "tokens_per_s": len(reps) * tokens / wall,
        "recovery_s": (disturbed[0].wall_s - med) if disturbed else None,
        "mb_wasted": int(sum(x.mb_executed - x.mb_needed for x in reps)),
        "step_p90_ms": (float(np.percentile(walls, 90)) * 1e3
                        if len(reps) else None),
        "seq_len": int(r["seq_len"]),
    })
    run.note(
        f"window: wall_s={wall:.3f} steps={len(reps)} "
        f"step_p50_ms={med * 1e3:.2f} "
        f"step_max_ms={walls.max(initial=0) * 1e3:.2f} "
        f"crash_step={state.crash_step} "
        f"recoveries={sum(len(x.recoveries) for x in reps)} "
        f"mb_wasted={run.counters['mb_wasted']} "
        f"release_late_s={state.released_late_s:.4f}")


def release(state: Train) -> None:
    state.trainer.shutdown()
    state.trainer = None
    state.chaos = None
    gc.collect()


def replay_fingerprint(run, state: Train, steps: int) -> np.ndarray:
    """The same first ``steps`` steps, fault-free, from the same weights."""
    t, _ = build(run, state.config, state.traffic, seed=state.seed,
                 data_seed=state.data_seed, chaos=False)
    try:
        t.run(steps)
        return fingerprint(t.state["params"])
    finally:
        t.shutdown()
        del t
        gc.collect()


def reference_batches(state: Train, steps: int) -> List[List[np.ndarray]]:
    r = state.config["runtime"]
    vocab = int(state.model_cfg["vocab_size"])
    shards = int(r["n_hosts"])
    M = int(r["microbatches_per_shard"])
    return [[ref.tokens(state.data_seed, s, k * M + j,
                        int(r["per_shard_batch"]), int(r["seq_len"]), vocab)
             for s in range(shards) for j in range(M)]
            for k in range(steps)]


def compare(want: dict, got: dict) -> Dict[str, float]:
    """The program's first steps (``got``) against the reference's."""
    keep = ref.moving_leaves(want["grad_norms"])
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(got["losses"], want["losses"]))
    return {"loss_gap": loss,
            "grad_gap": ref.worst_leaf_gap(got["grad_norms"],
                                           want["grad_norms"], keep),
            "grad_diff": max(ref.diff_gaps(
                got["first_grad"], want["first_grad"], want["grad_norms"],
                keep).values()),
            "update_gap": ref.worst_leaf_gap(got["update_norms"],
                                             want["update_norms"], keep),
            "leaves_left_out": sorted(set(want["grad_norms"]) - set(keep))}


def reference(state: Train, cast=None, fault: Optional[str] = None
              ) -> dict:
    """The reference's first steps; with ``fault``, broken as a program
    could be: ``half_batch`` leaves out half of every microbatch's
    sequences, ``no_exchange`` applies one shard's gradient alone."""
    n = len(state.first["losses"])
    params = ref.make_params(state.model_cfg, state.seed,
                             jnp.dtype(state.config["trainer"][
                                 "param_dtype"]))
    batches = reference_batches(state, n)
    if fault == "half_batch":
        batches = [[tok[:max(1, len(tok) // 2)] for tok in step]
                   for step in batches]
    elif fault == "no_exchange":
        batches = [step[:1] for step in batches]
    return ref.train(state.model_cfg, state.config["trainer"], params,
                     batches, cast=cast)


def upper_readings(state: Train) -> dict:
    """The control (the reference in float8 e4m3 products, the step
    below the configuration's bfloat16) and each fault, in the
    program's place, against the float32 reference."""
    want = state.want
    out = {"control": compare(want, reference(state,
                                              cast=jnp.float8_e4m3fn))}
    unchanged = dict(want, update_norms={k: 0.0 for k in
                                         want["update_norms"]})
    # (a step that returns its state unchanged reads 1 by update_gap)
    out["fault_unchanged"] = compare(want, unchanged)
    for fault in ("half_batch", "no_exchange"):
        out["fault_" + fault] = compare(want, reference(state, fault=fault))
    for v in out.values():
        v.pop("leaves_left_out", None)
    return out


def check(run, state: Train, limits: dict) -> None:
    if state.crash_step is not None:
        fp = replay_fingerprint(run, state, state.crash_step + 1)
        mismatch = int((fp != state.crash_fp).sum())
    else:
        mismatch = -1
    run.note(f"exactly-once: crash_step={state.crash_step} "
             f"fingerprint_mismatch={mismatch}")
    with harness.span("check.reference"):
        want = reference(state)
    state.want = want
    gaps = compare(want, state.first)
    worst = {}
    for key in ("grad_norms", "update_norms"):
        g = ref.leaf_gaps(state.first[key], want[key],
                          ref.moving_leaves(want["grad_norms"]))
        k = max(g, key=g.get)
        worst[key] = (k, round(g[k], 6), want[key][k], state.first[key][k])
    run.note(f"reference: losses={want['losses']} "
             f"program={state.first['losses']} "
             f"leaves_left_out={gaps['leaves_left_out']} "
             f"worst_leaf(name, gap, reference, program)={worst}")
    run.check("loss_gap", gaps["loss_gap"], limits["loss_gap"])
    run.check("grad_gap", gaps["grad_gap"], limits["grad_gap"])
    run.check("grad_diff", gaps["grad_diff"], limits["grad_diff"])
    run.check("update_gap", gaps["update_gap"], limits["update_gap"])
    run.check_at_least("crash_recovered", 0 if mismatch < 0 else 1, 1)
    run.check("fingerprint_mismatch", max(mismatch, 0),
              limits["fingerprint_mismatch"])
