"""Driver of the simulated YARN fleet: closed-loop tenants submitting
PACMan jobs, the paper's node-crash injection, and the assessment tick
on the device backend.

The configuration gives the cluster (``n_workers``, ``n_containers``,
``net``), the policy and its parameters (``policy``, ``params``: a name
in ``repro.sim.mapreduce``), the backend and its ``precision`` (checked
against the one the backend runs in), and ``max_running_apps``, the most
jobs the deployment runs at once. The traffic file gives every other
parameter (see ``chipbench/traffic``):

- ``tenants``: clients in a closed loop; each submits its next job when
  its last one has finished (checked once per ``chunk_s`` of simulated
  time, so the think time is under one chunk). Absent, the
  configuration's ``max_running_apps``; never more than it;
- ``stagger_s``: first submissions spread over ``[0, stagger_s)``,
  one tenant per equal slice, in an order drawn from the seed;
- ``mix``: job sizes (GB) with their shares and the benches. Jobs are
  dealt from decks of ``tenants`` jobs that hold every size in its
  share (largest remainder) and the benches in turn, shuffled by the
  seed: every seed runs the same set of jobs, in another order
  (copied from ``repro.sim.workload.pacman_workload``'s PACMan sizes,
  with the draw replaced by the deck);
- ``job``: instead of ``mix``, one fixed job (``bench`` and
  ``splits_per_worker``: input = splits x workers x 128 MB, the
  proportional TeraSort of ``benchmarks/perf_accel``);
- ``crash_at_map_progress`` / ``restore_after_s``: the paper's crash
  injection on every job (``MapProgressCrash``): when the job has
  completed this share of its maps, the node holding most of its map
  work crashes, and is restored ``restore_after_s`` later (YARN's
  NodeManager expiry, 600 s by default). Absent, no node crashes;
- ``warmup_sim_s`` / ``warmup_jobs`` / ``warmup_rows``: set-up runs at
  least this many simulated seconds, until this many jobs have been
  launched and until the attempt rows have once reached this many. The
  program's job registry keeps every job it has seen and its device
  arrays are padded to the next power of two of it: the window has to
  start past the power of two that it would otherwise cross, or it
  compiles (1,025 jobs keep a window of under 1,023 more at 2,048). The
  rows likewise: every seed's window starts at the same padded size;
- ``warmup_compacted``: set-up then runs on until the program has
  compacted its attempt table once (seen as its row count falling
  between chunks). The table drops its finished rows only once they are
  half of it, and a tick's host work grows with the rows: a window that
  opens just after a compaction is at the same point of that cycle for
  every seed;
- ``check``: which ticks of the window are held against the reference:
  the first tick on which the policy samples zeta, then, from a seeded
  share of the others, each drawn tick or the next one that samples
  zeta, at most ``max``. Every compared tick holds every method the
  policy calls, zeta included.

The program pads its attempt rows to a power of two, and the rows of a
closed loop keep growing while long jobs accumulate attempts. Set-up
therefore also runs the backend's programs once at the next padded row
count, on a copy of the snapshot padded with finished rows, so that a
window whose rows cross it does not compile.

The window drives ``Engine.run(until=...)`` one chunk at a time until
``--seconds`` of wall time have passed; ``sim_rate`` is all simulated
seconds over all wall seconds of it.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import harness
from chipbench.reference import assess as ref

SPLIT_GB = 128 / 1024


# ---------------------------------------------------------------------------
# Traffic generators (deterministic per seed)
# ---------------------------------------------------------------------------
def deck(mix: Dict[str, object], size: int, rng: np.random.Generator
         ) -> List[tuple]:
    """One deck of ``size`` jobs: every size in its share (largest
    remainder), benches dealt in turn, then shuffled."""
    sizes = list(mix["sizes_gb"])
    shares = np.asarray(mix["shares"], dtype=np.float64)
    exact = shares / shares.sum() * size
    counts = np.floor(exact).astype(int)
    rest = size - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    jobs = [s for s, c in zip(sizes, counts) for _ in range(c)]
    benches = list(mix["benches"])
    cards = [(gb, benches[i % len(benches)]) for i, gb in enumerate(jobs)]
    order = rng.permutation(len(cards))
    return [cards[i] for i in order]


def tenants_of(config: dict, traffic: dict) -> int:
    """The traffic's closed-loop clients: its ``tenants``, else the
    configuration's ``max_running_apps``, which it may not exceed."""
    cap = int(config["max_running_apps"])
    tenants = int(traffic.get("tenants", cap))
    if not 1 <= tenants <= cap:
        raise ValueError(f"{tenants} tenants: the configuration runs 1 to "
                         f"max_running_apps={cap} jobs at once")
    return tenants


class JobStream:
    """The jobs the tenants submit, in submission order."""

    def __init__(self, traffic: Dict[str, object], tenants: int,
                 n_workers: int, rng: np.random.Generator):
        self.traffic = traffic
        self.tenants = tenants
        self.n_workers = n_workers
        self.rng = rng
        self._cards: List[tuple] = []
        self.issued = 0

    def next(self) -> tuple:
        job = self.traffic.get("job")
        self.issued += 1
        if job is not None:
            gb = job["splits_per_worker"] * self.n_workers * SPLIT_GB
            return gb, job["bench"]
        if not self._cards:
            self._cards = deck(self.traffic["mix"], self.tenants, self.rng)
        return self._cards.pop()


def first_submissions(tenants: int, stagger_s: float,
                      rng: np.random.Generator) -> np.ndarray:
    """Per tenant, its first submit time: one tenant per equal slice of
    ``[0, stagger_s)``, uniform within the slice, slices in seeded order."""
    slot = rng.permutation(tenants)
    return (slot + rng.random(tenants)) * (stagger_s / max(tenants, 1))


class MapProgressCrash:
    """The crash injection of the paper's experiments, copied from
    ``repro.sim.faults.crash_busiest_node_at_map_progress`` (which
    ``benchmarks/common.crash_fault`` applies): when a job has completed
    ``frac`` of its maps, the node hosting the most of its map work
    (running attempts, then map outputs; ties to the first node id)
    crashes, and is restored ``restore_after`` simulated seconds later.
    Departure: a victim already down is left alone."""

    def __init__(self, sim, frac: float, restore_after: float):
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"crash_at_map_progress {frac} not in (0, 1]")
        self.sim = sim
        self.frac = frac
        self.restore_after = restore_after
        self.crashed = 0
        self.restored = 0
        self.victims: List[tuple] = []

    def arm(self, job) -> None:
        job.map_progress_triggers.append(
            (self.frac, lambda: self._fire(job)))

    def _fire(self, job) -> None:
        counts: Dict[str, int] = {}
        for t in job.maps:
            for a in t.running_attempts():
                counts[a.node_id] = counts.get(a.node_id, 0) + 1
            for n in t.output_nodes:
                counts[n] = counts.get(n, 0) + 1
        if not counts:
            return
        victim = max(sorted(counts), key=lambda n: counts[n])
        sim = self.sim
        if not sim.cluster.nodes[victim].alive:
            return
        sim.crash_node(victim)
        self.crashed += 1
        self.victims.append((sim.engine.now, victim))
        sim.engine.after(self.restore_after, self._restore, victim)

    def _restore(self, nid: str) -> None:
        self.restored += 1
        self.sim.restore_node(nid)


class GcClock:
    """Host seconds spent in Python's garbage collector, by generation,
    while installed in ``gc.callbacks``."""

    def __init__(self) -> None:
        self.seconds = [0.0, 0.0, 0.0]
        self.count = [0, 0, 0]
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            g = int(info["generation"])
            self.seconds[g] += time.perf_counter() - self._t0
            self.count[g] += 1
            self._t0 = None


# ---------------------------------------------------------------------------
# The assessment backend as the window drives it: spans around every
# method, the work of every tick, and the inputs and answers of the
# ticks drawn for the check
# ---------------------------------------------------------------------------
def capture_backend_class():
    from repro.accel.base import TMARK, TPROG, AssessmentBackend

    cols = ("a_state", "t_state", "kind", "job", "node", "spec", "start",
            "work_done", "work_total", "last_sync", "fetched", "deps",
            "compute", "active", "skey")

    class CaptureBackend(AssessmentBackend):
        def __init__(self, inner, picks: np.ndarray, max_caps: int) -> None:
            self.inner = inner
            self.name = inner.name
            self.arrays = None
            self.picks = picks        # per window tick: drawn for the check?
            self.max_caps = max_caps
            self.armed = False
            self.ticks = 0
            self.calls: Dict[str, int] = {}
            # per method, (rows, jobs) of each window tick that called it
            self.tick_work: Dict[str, List[tuple]] = {}
            self._seen: set = set()
            self.captures: List[dict] = []
            self._want = False
            self._now = None
            self._work = (0, 0)
            self._cap: Optional[dict] = None
            self._uploaded = False
            self.upload_total = 0
            self.upload_ticks = 0

        @property
        def upload_bytes(self) -> int:
            return getattr(self.inner, "upload_bytes", 0)

        def flush(self) -> None:
            """Close the tick that just ended: count its upload, and drop
            its capture unless the policy sampled zeta on it."""
            if self._uploaded and self.armed:
                self.upload_total += self.upload_bytes
                self.upload_ticks += 1
            self._uploaded = False
            if self._cap is not None and not self._cap.get("sampled"):
                self.captures.remove(self._cap)
            self._cap = None

        def _tick(self, now: float, cols: bool = True) -> Optional[dict]:
            if now != self._now:
                self.flush()
                self._now = now
                self._seen = set()
                if self.armed:
                    k = self.ticks
                    self.ticks += 1
                    arr = self.arrays
                    self._work = (int(arr.n), len(arr.active_jobs()))
                    self._want |= bool(k < len(self.picks)
                                       and self.picks[k])
                    if self._want and len(self.captures) < self.max_caps:
                        self._cap = self._snapshot(now)
                        self.captures.append(self._cap)
            self._uploaded |= cols
            return self._cap

        def _snapshot(self, now: float) -> dict:
            arr = self.arrays
            n = arr.n
            return {
                "now": float(now),
                "cols": {c: np.array(getattr(arr, c)[:n]) for c in cols},
                "mark": np.array(arr.scratch(TMARK, np.int64, -1)[:n]),
                "tprog": np.array(arr.scratch(TPROG, np.float64,
                                              np.nan)[:n]),
                "node_speed": np.array(arr.node_speed),
                "active": list(arr.active_jobs()),
                "called": set(), "out": {},
            }

        def _count(self, name: str) -> None:
            if not self.armed:
                return
            self.calls[name] = self.calls.get(name, 0) + 1
            if name not in self._seen:
                self._seen.add(name)
                self.tick_work.setdefault(name, []).append(self._work)

        def spatial_hits(self, arr, now, active, neighborhoods):
            cap = self._tick(now)
            self._count("spatial_hits")
            with harness.span("backend.spatial_hits"):
                out = self.inner.spatial_hits(arr, now, active,
                                              neighborhoods)
            if cap is not None:
                cap["called"].add("spatial")
                cap["out"]["spatial"] = out
                cap["nh_ok"] = bool(np.array_equal(
                    neighborhoods, ref.ring_neighbourhoods(
                        len(arr.node_ids))))
            return out

        def temporal_zeta(self, arr, now, active, samp_flag, init_flag,
                          prevk):
            cap = self._tick(now)
            self._count("temporal_zeta")
            if cap is not None:
                cap["mark"] = np.array(arr.scratch(TMARK, np.int64,
                                                   -1)[:arr.n])
                cap["tprog"] = np.array(arr.scratch(TPROG, np.float64,
                                                    np.nan)[:arr.n])
                cap["temporal_args"] = {
                    "samp": np.array(samp_flag), "init": np.array(init_flag),
                    "prevk": np.array(prevk)}
            with harness.span("backend.temporal_zeta"):
                zn, zp = self.inner.temporal_zeta(arr, now, active,
                                                  samp_flag, init_flag,
                                                  prevk)
            if cap is not None:
                cap["called"].add("temporal")
                cap["out"]["temporal"] = {
                    "zeta_now": zn, "zeta_prev": zp,
                    "mark": np.array(arr.scratch(TMARK, np.int64,
                                                 -1)[:arr.n]),
                    "tprog": np.array(arr.scratch(TPROG, np.float64,
                                                  np.nan)[:arr.n])}
                if np.any(samp_flag):
                    cap["sampled"] = True
                    self._want = False
            return zn, zp

        def failure_masks(self, now, node_hb, node_marked, declared,
                          thresholds, responsive_window):
            cap = self._tick(now, cols=False)
            self._count("failure_masks")
            if cap is not None:
                cap["failure_args"] = {
                    "node_hb": np.array(node_hb),
                    "node_marked": np.array(node_marked),
                    "declared": np.array(declared),
                    "thresholds": np.array(thresholds),
                    "window": float(responsive_window)}
            with harness.span("backend.failure_masks"):
                out = self.inner.failure_masks(now, node_hb, node_marked,
                                               declared, thresholds,
                                               responsive_window)
            if cap is not None:
                cap["called"].add("failure")
                cap["out"]["failure"] = out
            return out

        def late_victims(self, arr, now, active, eligible, min_runtime,
                         slow_task_percentile):
            self._tick(now)
            self._count("late_victims")
            with harness.span("backend.late_victims"):
                return self.inner.late_victims(arr, now, active, eligible,
                                               min_runtime,
                                               slow_task_percentile)

        def winning(self, arr, now, job_idx, win_factor):
            cap = self._tick(now)
            self._count("winning")
            with harness.span("backend.winning"):
                out = self.inner.winning(arr, now, job_idx, win_factor)
            if cap is not None:
                cap["called"].add("winning")
                cap.setdefault("winning_args", []).append(
                    (int(job_idx), float(win_factor), None))
                cap["out"].setdefault("winning", []).append(
                    (int(job_idx), float(win_factor), bool(out)))
            return out

        def reap_rows(self, arr, now):
            cap = self._tick(now)
            self._count("reap_rows")
            with harness.span("backend.reap_rows"):
                out = self.inner.reap_rows(arr, now)
            if cap is not None:
                cap["called"].add("reap")
                cap["out"]["reap"] = np.array(out)
            return out

    return CaptureBackend


def assess_span(speculator) -> None:
    """Wrap the policy's ``assess`` in a span, from outside."""
    inner = speculator.assess

    def assess(snap):
        with harness.span("policy.assess"):
            return inner(snap)
    speculator.assess = assess


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Fleet:
    sim: object
    backend: object
    stream: JobStream
    crashes: Optional[MapProgressCrash]
    owner: Dict[str, int]
    tenants_done: int = 0
    submit_late_s: float = 0.0


def _submit(state: Fleet, tenant: int, at: float) -> None:
    from repro.sim import JobSpec
    gb, bench = state.stream.next()
    jid = f"t{tenant:04d}j{state.stream.issued:06d}"
    state.owner[jid] = tenant
    job = state.sim.submit(JobSpec(jid, bench, float(gb), submit_time=at))
    if state.crashes is not None:
        state.crashes.arm(job)


def _feed(state: Fleet) -> None:
    """Closed loop: each finished job's tenant submits its next one."""
    results = state.sim.results
    now = state.sim.engine.now
    while state.tenants_done < len(results):
        res = results[state.tenants_done]
        state.tenants_done += 1
        state.submit_late_s = max(state.submit_late_s,
                                  now - res.finish_time)
        _submit(state, state.owner[res.job_id], now)


def _advance(state: Fleet, until: float, chunk: float) -> None:
    eng = state.sim.engine
    while eng.now < until - 1e-9:
        with harness.span("engine.chunk"):
            eng.run(until=min(eng.now + chunk, until))
        _feed(state)


def _params(config: dict):
    """The policy parameters the configuration names."""
    from repro.sim import mapreduce
    params = getattr(mapreduce, config["params"])
    if not isinstance(params, mapreduce.SimParams):
        raise ValueError(f"{config['params']!r} is no SimParams")
    return params


def _check_precision(config: dict) -> None:
    """The backend's float type on this platform is the configuration's."""
    from repro.accel.jax_backend import on_tpu
    got = "float32" if on_tpu() else "float64"
    if got != config["precision"]:
        raise ValueError(f"the backend runs in {got} here, the "
                         f"configuration states {config['precision']}")


def setup(run: "harness.Run", config: dict, traffic: dict) -> Fleet:
    from repro.accel.base import get_backend
    from repro.sim.mapreduce import Simulation

    _check_precision(config)
    tenants = tenants_of(config, traffic)
    seeds = run.seeds(4)
    jobs_rng, stagger_rng, _unused, pick_rng = (
        np.random.default_rng(s) for s in seeds)
    n_workers = int(config["n_workers"])
    check = traffic["check"]
    # The window's first tick, and a seeded share of the rest.
    picks = pick_rng.random(100_000) < float(check["share"])
    picks[0] = True
    inner = run.hook("backend", get_backend(config["assess_backend"]))
    backend = capture_backend_class()(inner, picks, int(check["max"]))
    sim = Simulation(policy=config["policy"], seed=int(seeds[0]),
                     n_workers=n_workers,
                     n_containers=int(config["n_containers"]),
                     params=_params(config), net=config["net"],
                     assess_backend=backend)
    backend.arrays = sim.arrays
    assess_span(sim.speculator)
    crashes = None
    if "crash_at_map_progress" in traffic:
        crashes = MapProgressCrash(sim,
                                   float(traffic["crash_at_map_progress"]),
                                   float(traffic["restore_after_s"]))
    state = Fleet(sim=sim, backend=backend,
                  stream=JobStream(traffic, tenants, n_workers, jobs_rng),
                  crashes=crashes, owner={})
    first = first_submissions(tenants, float(traffic["stagger_s"]),
                              stagger_rng)
    for tenant in np.argsort(first, kind="stable"):
        _submit(state, int(tenant), float(first[tenant]))
    chunk = float(traffic["chunk_s"])
    _advance(state, float(traffic["warmup_sim_s"]), chunk)
    while len(sim.active_jobs) + len(sim.results) \
            < int(traffic.get("warmup_jobs", 0)) \
            or sim.arrays.n < int(traffic.get("warmup_rows", 0)):
        _advance(state, sim.engine.now + chunk, chunk)
    if traffic.get("warmup_compacted"):
        rows, give_up = sim.arrays.n, sim.engine.now + 600.0
        while True:
            _advance(state, sim.engine.now + chunk, chunk)
            if sim.arrays.n < rows:
                break
            if sim.engine.now > give_up:
                raise RuntimeError("no compaction of the attempt table "
                                   "in 600 simulated s of warm-up")
            rows = sim.arrays.n
    with harness.span("setup.prewarm"):
        prewarm(config, sim, _pad_rows(backend) + 1)
    run.note(f"warm-up: sim_s={sim.engine.now:.1f} "
             f"rows={sim.arrays.n} jobs_active={len(sim.active_jobs)} "
             f"jobs_launched={len(sim.active_jobs) + len(sim.results)} "
             f"jobs_done={len(sim.results)} ticks={sim.assess_ticks} "
             f"crashes={_crashed(state)} "
             f"rows_pad={_pad_rows(backend)} jcap={_jcap(backend)}")
    return state


def prewarm(config: dict, sim, rows: int) -> None:
    """Run every backend program the policy calls at ``rows`` attempt
    rows (padded to the next power of two) on a copy of the snapshot."""
    from repro.accel.base import get_backend
    from repro.core.glance import build_neighborhoods
    from repro.core.types import TaskKind, TaskState
    clone = sim.arrays.clone_for_assessment()
    active = clone.active_jobs()
    if not active or rows <= clone.n:
        return
    now = sim.engine.now
    pad = active[0][1]
    for i in range(clone.n, rows):
        r = clone.add_attempt(None, f"pad{i}", f"pad{i}", (1 << 40) + i, 0,
                              pad, 0, TaskKind.MAP, False, now, 0.0, 1.0,
                              1, TaskState.COMPLETED)
        clone.active[r] = False
    b = get_backend(config["assess_backend"])
    J = len(active)
    b.failure_masks(now, clone.node_hb, clone.node_marked,
                    np.zeros(len(clone.node_ids), bool),
                    np.full(len(clone.node_ids), 10.0), 1.5)
    b.spatial_hits(clone, now, active, build_neighborhoods(clone.node_ids))
    b.temporal_zeta(clone, now, active, np.zeros(J, bool),
                    np.zeros(J, bool), np.full(J, -2, np.int64))
    b.winning(clone, now, pad, 1.0)
    b.reap_rows(clone, now)


def window(run: "harness.Run", state: Fleet, seconds: float,
           traffic: dict) -> None:
    sim = state.sim
    backend = state.backend
    backend.captures.clear()
    backend.flush()
    backend.armed = True
    chunk = float(traffic["chunk_s"])
    eng = sim.engine
    c0 = {"sim": eng.now, "assess_wall": sim.assess_wall,
          "ticks": sim.assess_ticks, "jobs": len(sim.results),
          "crashed": _crashed(state), "rows_max": sim.arrays.n,
          **_attempts(sim)}
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)
    t0 = time.perf_counter()
    try:
        while True:
            with harness.span("engine.chunk"):
                eng.run(until=eng.now + chunk)
            _feed(state)
            c0["rows_max"] = max(c0["rows_max"], sim.arrays.n)
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
    finally:
        gc.callbacks.remove(gc_clock)
    backend.flush()
    backend.armed = False
    sim_s = eng.now - c0["sim"]
    ticks = sim.assess_ticks - c0["ticks"]
    assess = sim.assess_wall - c0["assess_wall"]
    run.attempted = ticks
    run.e2e["sim_rate"] = sim_s / wall
    run.counters.update({
        "window_wall_s": wall, "sim_s": sim_s, "ticks": ticks,
        "assess_wall_s": assess,
        "upload_bytes_total": backend.upload_total,
        "upload_ticks": backend.upload_ticks,
        "backend_calls": dict(backend.calls),
        "tick_work": {m: list(w) for m, w in backend.tick_work.items()},
        "rows_max": c0["rows_max"], "rows_pad": _pad_rows(backend),
        "jobs_active": len(sim.active_jobs),
        "jcap": _jcap(backend), "n_nodes": len(sim.cluster.node_ids),
    })
    run.note(
        f"window: wall_s={wall:.3f} sim_s={sim_s:.1f} ticks={ticks} "
        f"assess_wall_s={assess:.3f} rows_max={c0['rows_max']} "
        f"rows_pad={_pad_rows(backend)} jcap={_jcap(backend)} "
        f"jobs_done={len(sim.results) - c0['jobs']} "
        f"attempts={_attempts(sim)['attempts'] - c0['attempts']} "
        f"spec_launches={_attempts(sim)['spec'] - c0['spec']} "
        f"crashes={_crashed(state) - c0['crashed']} "
        f"nodes_declared_failed={len(sim._marked_failed)} "
        f"submit_late_s={state.submit_late_s:.2f} "
        f"calls={dict(backend.calls)}")
    run.note(f"window gc: collections={gc_clock.count} "
             f"seconds={[round(s, 3) for s in gc_clock.seconds]}")


def _crashed(state: Fleet) -> int:
    return state.crashes.crashed if state.crashes is not None else 0


def _attempts(sim) -> Dict[str, int]:
    jobs = sim.jobs.values()
    return {"attempts": sum(j.n_attempts for j in jobs),
            "spec": sum(j.n_spec_attempts for j in jobs)}


def _dc(backend):
    return getattr(backend.inner, "_dc", None)


def _pad_rows(backend) -> int:
    dc = _dc(backend)
    return int(dc.cap) if dc is not None else 0


def _jcap(backend) -> int:
    dc = _dc(backend)
    return int(dc.jcap) if dc is not None else 0


def release(state: Fleet) -> None:
    """Drop the simulation; the captured ticks stay for the check."""
    state.sim = None


def check(run: "harness.Run", state: Fleet, limits: dict) -> None:
    """Every captured tick against the reference, once the window has
    closed; the numbers compared go to ``run.check``."""
    results = []
    nh_ok = True
    for cap in state.backend.captures:
        nh_ok &= cap.get("nh_ok", True)
        results.append(ref.compare_tick(cap, cap["out"]))
    s = ref.summarize(results)
    run.note(f"check: ticks={s['ticks']} compared={s['counts']}")
    run.check("flip_margin", s["flip_margin"], limits["flip_margin"])
    run.check("zeta_gap", s["zeta_gap"], limits["zeta_gap"])
    run.check("exact_mismatch", s["exact_mismatch"],
              limits["exact_mismatch"])
    run.check("neighbourhood_mismatch", 0 if nh_ok else 1, 0)
    run.check_at_least("ticks_compared", s["ticks"],
                       limits["ticks_compared"])
    run.check_at_least("zeta_compared", s["counts"].get("zeta", 0),
                       limits["zeta_compared"])


def upper_readings(state: Fleet) -> dict:
    """The control: the same captured ticks answered by the reference in
    bfloat16, in the program's place (float32 is the configuration's)."""
    import ml_dtypes
    results = [ref.compare_tick(cap, ref.control_answers(
        cap, ml_dtypes.bfloat16)) for cap in state.backend.captures]
    s = ref.summarize(results)
    out = {k: s[k] for k in ("flip_margin", "zeta_gap", "exact_mismatch")}
    out["zeta_compared"] = s["counts"].get("zeta", 0)
    return {"control": out}
