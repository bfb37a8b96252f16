"""Plain reference of one binocular-speculation assessment tick.

It implements, in straightforward numpy, what each method of the
assessment backend computes from a snapshot of the cluster's attempt and
node columns (the paper's §III, Eq. 1-4, and the collective and reaping
rules of §III.B):

- progress ζ of an attempt: maps ``min(done + accrued, total) / total``,
  where a running attempt accrues ``(now - last_sync) * node_speed``;
  reduces ``1/3 * fetched/deps + 2/3 * compute share`` (YARN's
  ProgressScore), accruing only once their compute has started;
- Eq. 1 (spatial): per (job, phase, node) the mean progress rate
  ``P = mean(ζ / max(now - start, 1e-9))`` of the running attempts of
  running tasks; a node is slow for a job when, for either phase,
  ``P < mean - σ`` over its ring neighbourhood of 4 nodes (offsets -2..1),
  counting only neighbours with a rate and requiring at least two;
- Eq. 2-3 (temporal): per (job, node) the sums of ζ now and at the
  previous sample over attempts alive at both samples, and the
  per-attempt sample marks written back;
- Eq. 4 (failure): a node is responsive when silent for at most the
  window, and a failure candidate when silent longer than its threshold
  and neither declared nor marked;
- collective: a job is winning when one of its tasks has a running
  speculative attempt and either no running original or a speculative
  rate above ``win_factor`` times the best original rate;
- reaping: running attempts of completed tasks that have a completed
  attempt.

Departures from the published equations: none in the decisions. Times
enter as offsets from ``now`` (the tick is the time origin), which is
what makes a low-precision control comparable; in float64 the result is
the same as with absolute times. Sums are taken with ``np.bincount`` in
float64 and rounded once to the working precision.

``Precision(None)`` computes in float64. ``Precision(dtype)`` rounds
every input and every intermediate result to ``dtype`` (the control in
bfloat16). Nothing here imports the program under test.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

SHUFFLE_FRACTION = 1.0 / 3.0
NEIGHBOURS = 4
TASK_SHIFT = 20          # canonical key = task order << 20 | attempt seq
RUNNING, COMPLETED = 0, 1
T_RUNNING, T_COMPLETED = 1, 2


class Precision:
    """Rounds to a working precision after every operation."""

    def __init__(self, dtype=None):
        self.dtype = dtype

    def __call__(self, x):
        x = np.asarray(x, dtype=np.float64)
        if self.dtype is None:
            return x
        return x.astype(self.dtype).astype(np.float64)


F64 = Precision(None)


def ring_neighbourhoods(n: int, k: int = NEIGHBOURS) -> np.ndarray:
    k = min(k, n)
    offsets = np.arange(k) - (k // 2)
    return (np.arange(n)[:, None] + offsets[None, :]) % n


def rel_gap(a, b) -> np.ndarray:
    """|a - b| relative to the larger magnitude; 0 where equal."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    scale = np.maximum(np.maximum(np.abs(a), np.abs(b)), 1e-300)
    with np.errstate(invalid="ignore"):
        return np.where(a == b, 0.0, np.abs(a - b) / scale)


class Tick:
    """The columns of one captured tick, and the quantities every
    method shares, in the working precision ``r``."""

    def __init__(self, cap: Dict[str, object], r: Precision = F64):
        self.cap = cap
        self.r = r
        c = cap["cols"]
        self.c = c
        self.n_nodes = len(cap["node_speed"])
        active = cap["active"]
        self.J = len(active)
        n_jobs = 1 + int(max([j for _, j in active]
                             + [c["job"].max(initial=0)]))
        jl = np.full(n_jobs, -1, dtype=np.int64)
        for pos, (_jid, jidx) in enumerate(active):
            jl[jidx] = pos
        self.jl_of_job = jl
        self.jl = jl[c["job"]]
        # Times as offsets from now (the tick is the origin).
        now = cap["now"]
        self.start_rel = r(c["start"] - now)
        ls_rel = r(c["last_sync"] - now)
        speed = r(cap["node_speed"])[c["node"]]
        accrue = (c["a_state"] == RUNNING) & ((c["kind"] == 0)
                                              | c["compute"].astype(bool))
        wd = r(r(c["work_done"]) + accrue * r(r(0.0 - ls_rel) * speed))
        wt = r(c["work_total"])
        wd = np.minimum(wd, wt)
        comp = r(wd / wt)
        shuffle = r(c["fetched"].astype(np.float64) / c["deps"])
        reduce_prog = r(r(SHUFFLE_FRACTION * shuffle)
                        + r((1.0 - SHUFFLE_FRACTION) * comp))
        self.prog = np.where(c["kind"] == 0, comp, reduce_prog)
        self.elapsed = np.maximum(r(0.0 - self.start_rel), 1e-9)
        self.rate = r(self.prog / self.elapsed)
        self.live = c["active"].astype(bool)
        self.running = self.live & (c["a_state"] == RUNNING) \
            & (c["t_state"] == T_RUNNING) & (self.jl >= 0)
        self.task = c["skey"] >> TASK_SHIFT


# ---------------------------------------------------------------------------
# Eq. 1
# ---------------------------------------------------------------------------
def spatial(t: Tick) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(hits (J, n), fired (J*2, n), margin (J*2, n)): the merged verdict,
    each phase's verdict, and each phase's relative distance from its
    threshold (inf where the gate is closed)."""
    r, c, n, J = t.r, t.c, t.n_nodes, t.J
    m = t.running
    seg = (t.jl[m] * 2 + c["kind"][m]) * n + c["node"][m]
    sums = r(np.bincount(seg, weights=t.rate[m], minlength=J * 2 * n))
    cnts = np.bincount(seg, minlength=J * 2 * n).astype(np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        P = np.where(cnts > 0, r(sums / np.maximum(cnts, 1.0)), np.nan)
    P = P.reshape(J * 2, n)
    nh = ring_neighbourhoods(n)
    Pn = P[:, nh]
    valid = ~np.isnan(Pn)
    cnt = valid.sum(axis=2)
    with np.errstate(invalid="ignore"):
        mean = r(r(np.nansum(Pn, axis=2)) / np.maximum(cnt, 1))
        dev = r(np.where(valid, Pn - mean[:, :, None], 0.0))
        var = r(r(np.sum(r(dev * dev), axis=2)) / np.maximum(cnt, 1))
        std = r(np.sqrt(var))
        thr = r(mean - std)
        ok = (cnt >= 2) & ~np.isnan(P)
        fired = ok & (P < thr)
        scale = np.maximum.reduce([np.abs(P), np.abs(mean), np.abs(std)])
        margin = np.where(ok, np.abs(P - thr) / np.maximum(scale, 1e-300),
                          np.inf)
    hits = fired.reshape(J, 2, n).any(axis=1)
    return hits, fired, margin


def any_flip_margin(dev: np.ndarray, ref_parts: np.ndarray,
                    margins: np.ndarray) -> np.ndarray:
    """Per merged verdict (an 'any' over parts) that the program got
    differently, the smallest threshold distance that explains it: where
    the reference fires, every firing part had to flip (max of their
    margins); where it does not, one part had to (min of the margins)."""
    ref = ref_parts.any(axis=1)
    fire_m = np.where(ref_parts, margins, -np.inf).max(axis=1)
    quiet_m = margins.min(axis=1)
    need = np.where(ref, fire_m, quiet_m)
    return need[dev != ref]


# ---------------------------------------------------------------------------
# Eq. 2-3
# ---------------------------------------------------------------------------
def temporal(t: Tick, samp, init, prevk) -> Dict[str, np.ndarray]:
    r, c, n, J = t.r, t.c, t.n_nodes, t.J
    samp = np.asarray(samp, dtype=bool)
    init = np.asarray(init, dtype=bool)
    prevk = np.asarray(prevk, dtype=np.int64)
    mark = t.cap["mark"]
    tprog = r(t.cap["tprog"])
    jls = np.where(t.jl >= 0, t.jl, 0)
    alive = t.running & samp[jls] & (mark == prevk[jls])
    seg = t.jl[alive] * n + c["node"][alive]
    zn = r(np.bincount(seg, weights=t.prog[alive], minlength=J * n))
    zp = r(np.bincount(seg, weights=tprog[alive], minlength=J * n))
    cnt = np.bincount(seg, minlength=J * n)
    w = t.running & (samp | init)[jls]
    new_mark = mark.copy()
    new_tprog = np.asarray(t.cap["tprog"], dtype=np.float64).copy()
    newk = np.where(samp, prevk + 1, 0)
    new_mark[w] = newk[jls[w]]
    new_tprog[w] = t.prog[w]
    return {"zeta_now": np.where(cnt > 0, zn, np.nan).reshape(J, n),
            "zeta_prev": np.where(cnt > 0, zp, np.nan).reshape(J, n),
            "mark": new_mark, "tprog": new_tprog}


# ---------------------------------------------------------------------------
# Eq. 4
# ---------------------------------------------------------------------------
def failure(r: Precision, now, node_hb, node_marked, declared, thresholds,
            window) -> Dict[str, np.ndarray]:
    silent = r(0.0 - r(np.asarray(node_hb, dtype=np.float64) - now))
    thr = r(thresholds)
    win = float(r(window))
    resp = silent <= win
    cand = ~resp & ~np.asarray(declared, bool) \
        & ~np.asarray(node_marked, bool) & (silent > thr)
    return {"responsive": resp, "failed": cand,
            "margin_resp": rel_gap(silent, win),
            "margin_fail": np.minimum(rel_gap(silent, thr),
                                      rel_gap(silent, win))}


# ---------------------------------------------------------------------------
# Collective
# ---------------------------------------------------------------------------
def winning(t: Tick, job_idx: int, win_factor: float) -> Tuple[bool, float]:
    """(verdict, smallest threshold distance that would flip it)."""
    r, c = t.r, t.c
    if job_idx >= len(t.jl_of_job) or t.jl_of_job[job_idx] < 0:
        return False, np.inf
    m = t.live & (c["a_state"] == RUNNING) & (c["job"] == job_idx)
    if not m.any():
        return False, np.inf
    tasks, inv = np.unique(t.task[m], return_inverse=True)
    spec = c["spec"][m].astype(bool)
    rate = t.rate[m]
    k = len(tasks)
    hi = np.full(k, -np.inf)
    lo = np.full(k, -np.inf)
    np.maximum.at(hi, inv[spec], rate[spec])
    np.maximum.at(lo, inv[~spec], rate[~spec])
    has_spec = np.bincount(inv, weights=spec, minlength=k) > 0
    has_orig = np.bincount(inv, weights=~spec, minlength=k) > 0
    lo_w = r(lo * win_factor)
    both = has_spec & has_orig
    with np.errstate(invalid="ignore"):
        beat = hi > lo_w
    win = has_spec & (~has_orig | beat)
    margins = np.where(both, rel_gap(hi, lo_w), np.inf)
    if win.any():
        # Every winning task compared by rate had to flip.
        need = np.where(win & has_orig, margins, np.inf)
        if (win & ~has_orig).any():
            return True, np.inf
        return True, float(need[win].max())
    return False, float(margins.min(initial=np.inf))


# ---------------------------------------------------------------------------
# Reaping
# ---------------------------------------------------------------------------
def reap(t: Tick) -> np.ndarray:
    c = t.c
    live = t.live & (c["t_state"] == T_COMPLETED)
    rows = np.flatnonzero(live)
    if not len(rows):
        return rows
    tasks, inv = np.unique(t.task[rows], return_inverse=True)
    done = np.bincount(inv, weights=c["a_state"][rows] == COMPLETED,
                       minlength=len(tasks)) > 0
    return np.sort(rows[done[inv] & (c["a_state"][rows] == RUNNING)])


# ---------------------------------------------------------------------------
# Comparison of one captured tick
# ---------------------------------------------------------------------------
def compare_tick(cap: Dict[str, object], out: Dict[str, object],
                 r_ref: Precision = F64) -> Dict[str, float]:
    """Hold the answers ``out`` given at one tick against the reference
    computed from the tick's captured inputs ``cap``.

    Returns ``flip_margin`` (largest threshold distance among verdicts
    the answers got otherwise than the reference; 0 when all agree),
    ``zeta_gap`` (largest relative gap of a ζ value, inf on a NaN
    pattern that differs), ``exact_mismatch`` (sample marks and reaped
    rows that differ), and per method the number of answers compared
    (``zeta``: the ζ values both sides give, neither NaN)."""
    t = Tick(cap, F64)
    flips: List[float] = [0.0]
    zeta: List[float] = [0.0]
    exact = 0
    counts: Dict[str, int] = {}

    def seen(name, k=1):
        counts[name] = counts.get(name, 0) + int(k)

    if "spatial" in out:
        hits, fired, margin = spatial(t)
        dev = np.asarray(out["spatial"], dtype=bool)
        J, n = hits.shape
        flips += list(any_flip_margin(
            dev, fired.reshape(J, 2, n), margin.reshape(J, 2, n)))
        seen("spatial", hits.size)
    if "temporal" in out:
        a = cap["temporal_args"]
        ref = temporal(t, a["samp"], a["init"], a["prevk"])
        got = out["temporal"]
        for key in ("zeta_now", "zeta_prev", "tprog"):
            g = np.asarray(got[key], dtype=np.float64)
            e = ref[key]
            gn, en = np.isnan(g), np.isnan(e)
            if (gn != en).any():
                zeta.append(np.inf)
            both = ~gn & ~en
            if both.any():
                zeta.append(float(rel_gap(g[both], e[both]).max()))
            if key == "zeta_now":
                seen("zeta", both.sum())
        exact += int((np.asarray(got["mark"]) != ref["mark"]).sum())
        seen("temporal", ref["zeta_now"].size)
    if "failure" in out:
        a = cap["failure_args"]
        ref = failure(F64, cap["now"], a["node_hb"], a["node_marked"],
                      a["declared"], a["thresholds"], a["window"])
        d_resp, d_fail = out["failure"]
        bad = np.asarray(d_resp) != ref["responsive"]
        flips += list(ref["margin_resp"][bad])
        bad = np.asarray(d_fail) != ref["failed"]
        flips += list(ref["margin_fail"][bad])
        seen("failure", 2 * len(ref["failed"]))
    for job_idx, win_factor, got in out.get("winning", ()):
        v, m = winning(t, job_idx, win_factor)
        if bool(got) != v:
            flips.append(m)
        seen("winning")
    if "reap" in out:
        got = np.sort(np.asarray(out["reap"], dtype=np.int64))
        exact += len(np.setxor1d(got, reap(t)))
        seen("reap")
    return {"flip_margin": float(max(flips)), "zeta_gap": float(max(zeta)),
            "exact_mismatch": exact, "counts": counts}


def control_answers(cap: Dict[str, object], dtype) -> Dict[str, object]:
    """The reference computed in a lower precision, in the program's
    place: the answers the same tick gets at ``dtype``."""
    r = Precision(dtype)
    t = Tick(cap, r)
    out: Dict[str, object] = {}
    if "temporal_args" in cap:
        a = cap["temporal_args"]
        out["temporal"] = temporal(t, a["samp"], a["init"], a["prevk"])
    if "spatial" in cap["called"]:
        out["spatial"] = spatial(t)[0]
    if "failure_args" in cap:
        a = cap["failure_args"]
        f = failure(r, cap["now"], a["node_hb"], a["node_marked"],
                    a["declared"], a["thresholds"], a["window"])
        out["failure"] = (f["responsive"], f["failed"])
    out["winning"] = [(j, wf, winning(t, j, wf)[0])
                      for j, wf, _ in cap.get("winning_args", ())]
    if "reap" in cap["called"]:
        out["reap"] = reap(t)
    return out


def summarize(results: List[Dict[str, float]]) -> Dict[str, float]:
    """Worst of each number over the compared ticks."""
    out = {"flip_margin": 0.0, "zeta_gap": 0.0, "exact_mismatch": 0,
           "ticks": len(results)}
    counts: Dict[str, int] = {}
    for res in results:
        out["flip_margin"] = max(out["flip_margin"], res["flip_margin"])
        out["zeta_gap"] = max(out["zeta_gap"], res["zeta_gap"])
        out["exact_mismatch"] += res["exact_mismatch"]
        for k, v in res["counts"].items():
            counts[k] = counts.get(k, 0) + v
    out["counts"] = counts
    return out


__all__ = ["Precision", "F64", "Tick", "compare_tick", "control_answers",
           "summarize", "spatial", "temporal", "failure", "winning",
           "reap", "ring_neighbourhoods"]
