"""Shadow comparison of a device backend with the numpy reference, under
the §13.3 tolerance contract (DESIGN.md §13.3).

:class:`ShadowBackend` serves every assessment call from a device
backend and replays the same call on the numpy reference over
``ArraySnapshot.clone_for_assessment()`` of the same snapshot, taken at
the tick's first call (the temporal pass writes its scratch columns, so
the reference must never share the live snapshot). Each output entry is
classified:

- ``equal`` — the device and the reference agree exactly;
- ``tolerated`` — they differ, and the reference's own scores put the
  deciding comparison within ``TOL`` (relative to the magnitudes
  compared) of its threshold, where float32 rounding may flip it;
- ``outside`` — any other difference. A correct device path has none.

Value outputs (the Eq. 2 ζ sums) are ``tolerated`` within ``TOL``
relative; integer and set outputs (reaping) must be ``equal``.
:func:`compare_sweep` applies the same rules to a batched sweep.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.accel.base import TMARK, TPROG, AssessmentBackend, get_backend
from repro.accel.numpy_backend import NumpyBackend
from repro.core import metrics as M
from repro.core.arrays import ArraySnapshot

TOL = 1e-6
_KINDS = ("equal", "tolerated", "outside")


def _near(a, b, tol=TOL) -> np.ndarray:
    """|a − b| within ``tol`` of the larger magnitude compared."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    with np.errstate(invalid="ignore"):
        scale = np.maximum(np.abs(a), np.abs(b))
        return np.abs(a - b) <= tol * scale


class Tally:
    """Per-method counts of equal / tolerated / outside entries, plus the
    first few outside examples for the report."""

    def __init__(self) -> None:
        self.counts: Dict[str, Dict[str, int]] = {}
        self.examples: List[str] = []

    def add(self, method: str, kind: str, n: int = 1,
            example: str = "") -> None:
        c = self.counts.setdefault(method, dict.fromkeys(_KINDS, 0))
        c[kind] += int(n)
        if kind == "outside" and example and len(self.examples) < 8:
            self.examples.append(f"{method}: {example}")

    def outside(self) -> int:
        return sum(c["outside"] for c in self.counts.values())


# ---------------------------------------------------------------------------
# Reference scores (numpy, float64) behind each decision
# ---------------------------------------------------------------------------
def _rel(a, b) -> np.ndarray:
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    scale = np.maximum(np.maximum(np.abs(a), np.abs(b)), 1e-300)
    return np.abs(a - b) / scale


def spatial_possible(arr, now, active, nh):
    """Per (job, node): (firm verdict, can flip) — a phase whose
    ``P < mean − σ`` test sits within TOL of its threshold may go either
    way; the merged verdict can flip only if no firm phase fires."""
    J, n = len(active), len(arr.node_ids)
    P = NumpyBackend().spatial_rates(arr, now, active)
    if P is None:
        return np.zeros((J, n), dtype=bool), np.zeros((J, n), dtype=bool)
    mean, std, ok = M.spatial_stats_batch_np(P, nh)
    thr = mean - std
    scale = np.maximum.reduce([np.abs(P), np.abs(mean), np.abs(std)])
    with np.errstate(invalid="ignore"):
        fired = ok & (P < thr)
        near = ok & (np.abs(P - thr) <= TOL * scale)
    firm = (fired & ~near).reshape(J, 2, n).any(axis=1)
    flex = near.reshape(J, 2, n).any(axis=1)
    return firm, flex


def late_margin(arr, now, job_idx, min_runtime, q) -> float:
    """Smallest relative margin among the comparisons behind one job's
    LATE pick: best attempt per task (ζ ties), the runtime floor, each
    candidate's rate against the percentile, and the top two remaining
    estimates. ``inf`` when the job has nothing to compare."""
    sc = NumpyBackend.late_scores(arr, now, job_idx, min_runtime, q)
    if sc is None:
        return np.inf
    prog, inv, segmax = sc["prog"], sc["inv"], sc["segmax"]
    runner = np.where(prog == segmax[inv], -np.inf, prog)
    second = np.maximum.reduceat(runner, sc["starts"])
    has2 = np.isfinite(second)
    margins = [np.inf, _rel(sc["elapsed"], min_runtime).min()]
    if has2.any():
        margins.append(_rel(segmax[has2], second[has2]).min())
    if "thresh" in sc:
        margins.append(_rel(sc["rho"], sc["thresh"]).min())
        est = np.sort(sc["est"][sc["rho"] < sc["thresh"]])
        if len(est) >= 2:
            margins.append(float(_rel(est[-2], est[-1])))
    return float(min(margins))


def winning_near(arr, now, job_idx, win_factor) -> bool:
    """True iff some task's speculative-vs-original rate comparison sits
    within TOL of the ``win_factor`` threshold."""
    r = NumpyBackend.task_rates(arr, now, job_idx)
    if r is None:
        return False
    hi, lo = r[0], r[1]
    both = np.isfinite(hi) & np.isfinite(lo)
    return bool(_near(hi[both], lo[both] * win_factor).any())


# ---------------------------------------------------------------------------
# Per-method classification (shared by the live shadow and the sweep)
# ---------------------------------------------------------------------------
def check_spatial(tally, dev, ref, arr, now, active, nh) -> None:
    diff = dev != ref
    tally.add("spatial_hits", "equal", (~diff).sum())
    if not diff.any():
        return
    firm, flex = spatial_possible(arr, now, active, nh)
    # Possible merged verdicts: firm → {True}; else flex → {F, T}.
    ok = diff & ~firm & flex
    tally.add("spatial_hits", "tolerated", ok.sum())
    bad = diff & ~ok
    if bad.any():
        j, i = np.argwhere(bad)[0]
        tally.add("spatial_hits", "outside", bad.sum(),
                  f"t={now} job {j} node {i} dev={dev[j, i]}")


def check_failure(tally, dev, ref, now, node_hb, thresholds, window,
                  name) -> None:
    diff = dev != ref
    tally.add(name, "equal", (~diff).sum())
    if not diff.any():
        return
    silent = now - np.asarray(node_hb, dtype=np.float64)
    ok = diff & (_near(silent, window) | _near(silent, thresholds))
    tally.add(name, "tolerated", ok.sum())
    bad = diff & ~ok
    if bad.any():
        i = int(np.flatnonzero(bad)[0])
        tally.add(name, "outside", bad.sum(),
                  f"t={now} node {i} silent={silent[i]}")


def check_late(tally, dev, ref, arr, now, active, eligible, min_runtime,
               q) -> None:
    for pos, (_jid, jidx) in enumerate(active):
        if not eligible[pos]:
            continue
        if dev[pos] == ref[pos]:
            tally.add("late_victims", "equal")
        elif late_margin(arr, now, jidx, min_runtime, q) <= TOL:
            tally.add("late_victims", "tolerated")
        else:
            tally.add("late_victims", "outside", 1,
                      f"t={now} job {jidx} dev={dev[pos]} ref={ref[pos]}")


def check_winning(tally, dev, ref, arr, now, job_idx, win_factor) -> None:
    if dev == ref:
        tally.add("winning", "equal")
    elif winning_near(arr, now, job_idx, win_factor):
        tally.add("winning", "tolerated")
    else:
        tally.add("winning", "outside", 1,
                  f"t={now} job {job_idx} dev={dev}")


def check_values(tally, name, dev, ref, now) -> None:
    """Float outputs: NaN patterns equal, values within TOL relative."""
    dev = np.asarray(dev, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    dn, rn = np.isnan(dev), np.isnan(ref)
    same = (dev == ref) | (dn & rn)
    tally.add(name, "equal", same.sum())
    ok = ~same & ~dn & ~rn & _near(dev, ref)
    tally.add(name, "tolerated", ok.sum())
    bad = ~same & ~ok
    if bad.any():
        i = np.argwhere(bad)[0]
        tally.add(name, "outside", bad.sum(),
                  f"t={now} at {tuple(i)} dev={dev[tuple(i)]} "
                  f"ref={ref[tuple(i)]}")


def check_exact(tally, name, dev, ref, now) -> None:
    if np.array_equal(np.asarray(dev), np.asarray(ref)):
        tally.add(name, "equal")
    else:
        tally.add(name, "outside", 1, f"t={now} dev={dev} ref={ref}")


# ---------------------------------------------------------------------------
# The live shadow
# ---------------------------------------------------------------------------
class ShadowBackend(AssessmentBackend):
    """A device backend whose every call is replayed on the numpy
    reference and classified into :attr:`tally`. The policies act on
    the device's results, exactly as without the shadow."""

    def __init__(self, device) -> None:
        self.device = get_backend(device)
        self.name = self.device.name
        self.ref = NumpyBackend()
        self.tally = Tally()
        self._clone: Tuple[float, Optional[ArraySnapshot],
                           Optional[ArraySnapshot]] = (np.nan, None, None)

    def _shadow(self, arr: ArraySnapshot, now: float) -> ArraySnapshot:
        if self._clone[0] != now or self._clone[1] is not arr:
            self._clone = (now, arr, arr.clone_for_assessment())
        return self._clone[2]

    def spatial_hits(self, arr, now, active, neighborhoods):
        clone = self._shadow(arr, now)
        dev = self.device.spatial_hits(arr, now, active, neighborhoods)
        ref = self.ref.spatial_hits(clone, now, active, neighborhoods)
        check_spatial(self.tally, dev, ref, clone, now, active,
                      neighborhoods)
        return dev

    def temporal_zeta(self, arr, now, active, samp_flag, init_flag, prevk):
        clone = self._shadow(arr, now)
        dn, dp = self.device.temporal_zeta(arr, now, active, samp_flag,
                                           init_flag, prevk)
        rn, rp = self.ref.temporal_zeta(clone, now, active, samp_flag,
                                        init_flag, prevk)
        check_values(self.tally, "temporal_zeta", np.stack([dn, dp]),
                     np.stack([rn, rp]), now)
        # The scratch write-back: marks exact, recorded ζ within TOL.
        n = arr.n
        check_exact(self.tally, "temporal_marks",
                    arr.scratch(TMARK, np.int64, -1)[:n],
                    clone.scratch(TMARK, np.int64, -1)[:n], now)
        check_values(self.tally, "temporal_marks_zeta",
                     arr.scratch(TPROG, np.float64, np.nan)[:n],
                     clone.scratch(TPROG, np.float64, np.nan)[:n], now)
        return dn, dp

    def failure_masks(self, now, node_hb, node_marked, declared,
                      thresholds, responsive_window):
        dr, dc = self.device.failure_masks(now, node_hb, node_marked,
                                           declared, thresholds,
                                           responsive_window)
        rr, rc = self.ref.failure_masks(now, node_hb, node_marked,
                                        declared, thresholds,
                                        responsive_window)
        for name, d, r in (("responsive", dr, rr), ("failed", dc, rc)):
            check_failure(self.tally, d, r, now, node_hb, thresholds,
                          responsive_window, name)
        return dr, dc

    def late_victims(self, arr, now, active, eligible, min_runtime,
                     slow_task_percentile):
        clone = self._shadow(arr, now)
        dev = self.device.late_victims(arr, now, active, eligible,
                                       min_runtime, slow_task_percentile)
        ref = self.ref.late_victims(clone, now, active, eligible,
                                    min_runtime, slow_task_percentile)
        check_late(self.tally, dev, ref, clone, now, active, eligible,
                   min_runtime, slow_task_percentile)
        return dev

    def winning(self, arr, now, job_idx, win_factor):
        clone = self._shadow(arr, now)
        dev = self.device.winning(arr, now, job_idx, win_factor)
        ref = self.ref.winning(clone, now, job_idx, win_factor)
        check_winning(self.tally, dev, ref, clone, now, job_idx,
                      win_factor)
        return dev

    def reap_rows(self, arr, now):
        clone = self._shadow(arr, now)
        dev = self.device.reap_rows(arr, now)
        ref = self.ref.reap_rows(clone, now)
        check_exact(self.tally, "reap_rows", np.sort(dev), np.sort(ref),
                    now)
        return dev


def compare_sweep(sweep, batched, serial) -> Tally:
    """Classify a batched sweep's verdicts against the serial numpy
    scoring of the same clones (:class:`repro.accel.sweep.BatchedSweep`)."""
    tally = Tally()
    now = sweep.now
    J = len(sweep.active)
    eligible = np.ones(J, dtype=bool)
    for clone, dev, ref in zip(sweep.clones, batched, serial):
        check_spatial(tally, dev["spatial_hits"], ref["spatial_hits"],
                      clone, now, sweep.active, sweep.neighborhoods)
        check_failure(tally, dev["failed"], ref["failed"], now,
                      clone.node_hb, sweep.thresholds,
                      sweep.responsive_window, "failed")
        check_late(tally, dev["late_victims"], ref["late_victims"], clone,
                   now, sweep.active, eligible, sweep.min_runtime,
                   sweep.slow_task_percentile)
        for pos, (_jid, jidx) in enumerate(sweep.active):
            check_winning(tally, bool(dev["winning"][pos]),
                          bool(ref["winning"][pos]), clone, now, jidx,
                          sweep.win_factor)
        check_exact(tally, "n_reap", dev["n_reap"], ref["n_reap"], now)
    return tally


__all__ = ["ShadowBackend", "Tally", "TOL", "compare_sweep"]
