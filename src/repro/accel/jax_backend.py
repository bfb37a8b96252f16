"""JAX assessment backend: jit-compiled kernels over device-resident
copies of the §11 columns (DESIGN.md §13.2–§13.3).

Precision follows the platform (§13.3). On the CPU every kernel traces
and runs under a scoped ``jax.enable_x64(True)`` in float64 and
replicates the numpy reference *accumulation order*, so results are bit
for bit the reference's:

- rows are visited in the canonical (§11.3) order: the padded ``order``
  export is gathered first, and every segmented sum is an XLA scatter-add
  whose updates apply sequentially in operand order (bit-equal to
  ``np.bincount`` on CPU);
- small fixed axes (the k-wide neighborhoods) are summed by *unrolled*
  sequential adds — ``jnp.sum`` may re-associate, ``np.nansum`` does not
  for k < 128;
- order statistics (LATE's percentile) are found by bisection over the
  bit patterns of non-negative floats, then mirror ``np.percentile``'s
  linear-interpolation formula term for term;
- order-insensitive reductions (max, min, any) need no special care;
- ``a ± b·c`` chains are guarded against LLVM's FMA contraction (which
  skips the product's rounding step) by multiplying the product with a
  runtime-opaque ``one``: even if the compiler contracts, ``fma(x, 1, c)``
  rounds exactly like ``x + c``. Constants adjacent to such products
  (e.g. the reduce shuffle fraction) are shipped as opaque scalars too,
  so the HLO simplifier cannot re-fold the guard away.

A TPU has no native float64, so there the same kernels run in float32
(and int32), decisions are held to the §13.3 tolerance instead of bit
equality, and nothing sorts: the TPU compiler takes minutes to build a
sort at 64k rows. On every platform times reach the device relative to
the tick's ``now`` (the host subtracts in float64), so float32 never
differences two large simulated times; on the CPU that subtraction is an
exact negation of the reference's ``now - t``.

Shapes are padded by :class:`repro.core.arrays.DeviceColumns` (grow by
doubling), so a jit specialization retraces only when the simulation
outgrows its row/job capacity, never per tick.

The traced cores (``spatial_core`` etc.) are shared: the pallas backend
swaps the segmented reductions for hand-written kernels and reuses the
``*_pick`` tails, and the batched sweep (:mod:`repro.accel.sweep`)
``vmap``s :func:`assess_summary_core` across fault scenarios.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.accel.base import TMARK, TPROG, AssessmentBackend
from repro.core.arrays import SHUFFLE_FRACTION, ArraySnapshot, DeviceColumns
from repro.obs.metrics import span


# ---------------------------------------------------------------------------
# Platform regime (§13.3): one predicate, read when a call is made
# ---------------------------------------------------------------------------
def on_tpu() -> bool:
    """True when JAX's default backend is a TPU: kernels then run
    compiled, in float32. Elsewhere they run in float64, Pallas kernels
    in interpret mode."""
    return jax.default_backend() == "tpu"


def precision():
    """Scoped dtype regime for one backend call: float64 off the TPU,
    float32 on it. Nothing in the process-global default changes."""
    return jax.enable_x64(not on_tpu())


# Columns holding absolute simulated times: shipped as ``t - now``.
_TIME_COLS = ("start", "last_sync", "node_hb")


def to_device(host: Dict[str, object], now: float) -> Dict[str, object]:
    """Host mirror (:meth:`DeviceColumns.refresh`, optionally stacked on
    a leading scenario axis) → device arrays in the platform's precision.
    Call inside :func:`precision`. Times become relative to ``now``; the
    kernels then evaluate at ``now = 0``."""
    fdt = np.float64 if not on_tpu() else np.float32
    dev: Dict[str, object] = {}
    for k, v in host.items():
        v = np.asarray(v)
        if k in _TIME_COLS:
            v = v - now
        if v.dtype.kind == "f":
            v = v.astype(fdt)
        elif v.dtype.kind in "iu":
            v = v.astype(np.int32)
        dev[k] = jnp.asarray(v)
    # Opaque scalars: anti-FMA guard + the shuffle fraction (shipped as
    # data so the simplifier cannot re-fold, §13.3).
    for k, val in (("one", 1.0), ("sf", SHUFFLE_FRACTION)):
        if k not in dev:
            dev[k] = jnp.asarray(fdt(val))
    return dev


def scalar(x):
    """A host float as a device scalar of the platform's float type."""
    return jnp.asarray(x, jnp.float32 if on_tpu() else jnp.float64)


# ---------------------------------------------------------------------------
# Traced helpers
# ---------------------------------------------------------------------------
def ordered_sum(x):
    """Sum the last axis by sequential left-to-right adds — the same
    association order as ``np.nansum`` over a small axis. ``jnp.sum``
    may re-associate, which breaks bit-exactness (§13.3)."""
    acc = x[..., 0]
    for j in range(1, x.shape[-1]):
        acc = acc + x[..., j]
    return acc


def prep(cols, now):
    """Canonical-order gather + the §11 elementwise projections, traced.

    Returns a dict of (cap,) arrays in canonical row order; ``posv``
    masks live positions, ``tseg`` is the global task-segment id (task
    segments are contiguous in canonical order). Times are relative:
    ``now`` is the tick's zero."""
    order = cols["order"]
    cap = order.shape[0]
    fdt = cols["one"].dtype
    pos = jnp.arange(cap, dtype=order.dtype)
    posv = pos < cols["n_rows"]

    def g(name):
        return cols[name][order]

    a_state = g("a_state")
    t_state = g("t_state")
    kind = g("kind")
    node = g("node")
    start = g("start")
    work_total = g("work_total")
    active = g("active") & posv
    one = cols["one"]          # opaque 1.0 — the anti-FMA guard (§13.3)
    sf = cols["sf"]            # opaque SHUFFLE_FRACTION
    # ProgressScore ζ, replicating ArraySnapshot.progress_at op-for-op.
    accrue = (a_state == 0) & ((kind == 0) | g("compute"))
    wd = g("work_done") + (accrue * (
        (now - g("last_sync")) * cols["node_speed"][node])) * one
    wd = jnp.minimum(wd, work_total)
    comp = wd / work_total
    # int/int: numpy promotes to float, jax keeps int — cast first.
    shuffle = g("fetched").astype(fdt) / g("deps").astype(fdt)
    prog = jnp.where(kind == 0, comp,
                     (sf * shuffle) * one + ((one - sf) * comp) * one)
    jl = cols["job_local"][g("job")]
    jls = jnp.where(jl >= 0, jl, 0)
    return {
        "cap": cap, "pos": pos, "posv": posv, "order": order,
        "a_state": a_state, "t_state": t_state, "kind": kind,
        "node": node, "spec": g("spec"), "start": start, "active": active,
        "prog": prog, "jl": jl, "jls": jls, "tseg": cols["tseg"],
        "mark": g(TMARK) if TMARK in cols else None,
        "tprog": g(TPROG) if TPROG in cols else None,
        "running": active & (a_state == 0) & (t_state == 1),
        "one": one,
    }


def seg_sum(mask, seg, vals, nb):
    """Masked scatter-add into ``nb`` buckets (+1 dump), updates applied
    in operand (canonical) order — bit-equal to np.bincount (§13.3)."""
    idx = jnp.where(mask, seg, nb)
    return jnp.zeros(nb + 1, vals.dtype).at[idx].add(
        jnp.where(mask, vals, 0))[:nb]


def seg_sum2(mask, seg, vals_a, vals_b, nb):
    """Two parallel masked bincounts sharing one scatter pass (scatter
    cost is per-update, so fusing the weight vectors halves it).
    Per-bucket accumulation order is operand order, as in seg_sum."""
    idx = jnp.where(mask, seg, nb)
    upd = jnp.stack([jnp.where(mask, vals_a, 0.0),
                     jnp.where(mask, vals_b, 0.0)], axis=-1)
    acc = jnp.zeros((nb + 1, 2), vals_a.dtype).at[idx].add(upd)[:nb]
    return acc[:, 0], acc[:, 1]


def seg_max(mask, seg, vals, nb, init):
    idx = jnp.where(mask, seg, nb)
    return jnp.full(nb + 1, init, vals.dtype).at[idx].max(
        jnp.where(mask, vals, init))[:nb]


def seg_min(mask, seg, vals, nb, init):
    idx = jnp.where(mask, seg, nb)
    return jnp.full(nb + 1, init, vals.dtype).at[idx].min(
        jnp.where(mask, vals, init))[:nb]


def seg_any(mask, seg, nb):
    return seg_max(mask, seg, mask.astype(jnp.int32), nb, 0) > 0


def job_count(mask, key, jcap):
    """(jcap,) count of ``mask`` entries per job key — a dense compare
    matrix; integer sums are exact under any association."""
    jrow = jnp.arange(jcap, dtype=key.dtype)[:, None]
    return (mask[None, :] & (key[None, :] == jrow)).sum(axis=1)


def spatial_mask(P, nh):
    """Eq. 1 over batched groups — mirror of
    ``metrics.spatial_slow_mask_batch_np`` with unrolled k-sums."""
    Pn = P[:, nh]                                  # (g, n, k)
    valid = ~jnp.isnan(Pn)
    cnt = valid.sum(axis=2)
    mean = ordered_sum(jnp.where(valid, Pn, 0.0)) / jnp.maximum(cnt, 1)
    var = ordered_sum(jnp.where(valid, (Pn - mean[:, :, None]) ** 2, 0.0)) \
        / jnp.maximum(cnt, 1)
    std = jnp.sqrt(var)
    ok = (cnt >= 2) & ~jnp.isnan(P)
    return ok & (P < (mean - std))


def percentile_indexes(m, q, cap, one):
    """numpy's virtual percentile index over ``m`` sorted samples:
    (clipped floor index, clipped ceil index, interpolation weight).
    ``one`` is the opaque anti-FMA guard (§13.3)."""
    v = ((m - 1) * (q / 100.0)) * one
    lo = jnp.floor(v)
    gamma = v - lo
    loi = jnp.clip(lo.astype(jnp.int32), 0, cap - 1)
    hii = jnp.clip(loi + 1, 0, jnp.maximum(m - 1, 0))
    return loi, hii, gamma


def percentile_lerp(a, b, gamma, one):
    """numpy's ``_lerp`` (including its t ≥ 0.5 symmetric form)."""
    diff = b - a
    return jnp.where(gamma >= 0.5, b - (diff * (1 - gamma)) * one,
                     a + (diff * gamma) * one)


def order_stat(member, vals, ks):
    """Exact order statistics without a sort: ``out[..., j]`` is the
    ``ks[..., j]``-th smallest (0-based) of ``vals`` over the entries
    ``member[j]`` selects, ``inf`` where there are too few. Non-negative
    floats order like their bit patterns, so a bisection over the bit
    range lands on the exact value. It stops as soon as every member
    left in the interval has one value — a few steps for well-separated
    samples, at most 31 in float32 and 63 in float64. ``member`` is
    (J, cap), ``ks`` is (..., J)."""
    fdt = vals.dtype
    idt = jnp.int64 if fdt == jnp.float64 else jnp.int32
    bits = jax.lax.bitcast_convert_type(jnp.where(vals > 0, vals, 0.0), idt)
    top = jax.lax.bitcast_convert_type(jnp.asarray(jnp.inf, fdt), idt)

    def cond(lohi):
        lo, hi = lohi
        return jnp.any(hi - lo > 1)

    def body(lohi):
        # Invariant: the answer, if it exists, lies in (lo, hi].
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ge = (member & (bits <= mid[..., None])).sum(axis=-1) > ks
        lo = jnp.where(ge, lo, mid)
        hi = jnp.where(ge, mid, hi)
        inside = member & (bits > lo[..., None]) & (bits <= hi[..., None])
        vmin = jnp.where(inside, bits, top).min(axis=-1)
        vmax = jnp.where(inside, bits, -1).max(axis=-1)
        # One value left in the interval: it is the answer.
        hi = jnp.where(vmin == vmax, vmin, hi)
        return jnp.where(vmin == vmax, hi - 1, lo), hi

    # Start from the members' own range [min, max]; where there is no
    # answer, from the empty (inf − 1, inf].
    exists = ks < member.sum(axis=-1)
    lo = jnp.where(exists, jnp.where(member, bits, top).min(axis=-1) - 1,
                   top - 1)
    hi = jnp.where(exists, jnp.where(member, bits, -1).max(axis=-1), top)
    _, hi = jax.lax.while_loop(cond, body, (lo, hi))
    return jax.lax.bitcast_convert_type(hi, fdt)


def member_percentile(member, vals, q, one):
    """``np.percentile(vals[member[j]], q)`` for every row ``j`` of the
    (J, cap) ``member`` mask (non-negative ``vals``), and the member
    counts."""
    m = member.sum(axis=1)
    loi, hii, gamma = percentile_indexes(m, q, vals.shape[-1], one)
    ab = order_stat(member, vals, jnp.stack([loi, hii]))
    return percentile_lerp(ab[0], ab[1], gamma, one), m


# ---------------------------------------------------------------------------
# Pick tails: per-bucket reductions in, verdicts out. Shared by the XLA
# cores below and the Pallas kernels, which compute the same reductions.
# ---------------------------------------------------------------------------
def spatial_pick(sums, counts, nh, jcap):
    """(jcap, n) Eq. 1 hits from per-(job, phase, node) ρ sums/counts."""
    n = nh.shape[0]
    P = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0),
                  jnp.nan).reshape(jcap * 2, n)
    return spatial_mask(P, nh).reshape(jcap, 2, n).any(axis=1)


def temporal_pick(p, zn, zp, cnt, samp, init, prevk, jcap, n):
    """ζ sums → (zeta_now, zeta_prev) + this sample's scratch marks."""
    zeta_now = jnp.where(cnt > 0, zn, jnp.nan).reshape(jcap, n)
    zeta_prev = jnp.where(cnt > 0, zp, jnp.nan).reshape(jcap, n)
    m = p["running"]
    wmask = (m & samp[p["jls"]]) | (m & init[p["jls"]])
    newk = jnp.where(samp, prevk + 1, 0)
    newmark = jnp.where(wmask, newk[p["jls"]], p["mark"])
    newtprog = jnp.where(wmask, p["prog"], p["tprog"])
    return zeta_now, zeta_prev, wmask, newmark, newtprog


def temporal_alive(p, samp, prevk):
    """Running rows of sampled jobs alive at both Eq. 2 samples."""
    return p["running"] & samp[p["jls"]] & (p["mark"] == prevk[p["jls"]])


def late_pick(p, best_prog, bpos, has_spec, now, min_runtime, q, jcap):
    """(jcap,) LATE victim rows (-1 = no victim) from the per-task best
    running attempt (``bpos`` = its canonical position, ``cap`` if the
    task has none) — mirror of ``NumpyBackend._late_victim``."""
    cap = p["cap"]
    one = p["one"]
    rep = bpos < cap
    bp = jnp.minimum(bpos, cap - 1)
    best_prog = jnp.where(rep, best_prog, 0.0)
    best_start = p["start"][bp]
    sjl = p["jls"][bp]
    ok = rep & ~has_spec & (now - best_start >= min_runtime)
    rho = best_prog / jnp.maximum(now - best_start, 1e-9)
    est = (1.0 - best_prog) / jnp.maximum(rho, 1e-9)
    # Per-job percentile over the ok candidates: exact order statistics
    # + numpy's linear interpolation.
    jrow = jnp.arange(jcap, dtype=sjl.dtype)[:, None]
    member = ok[None, :] & (sjl[None, :] == jrow)          # (jcap, cap)
    thresh, msel = member_percentile(member, rho, q, one)
    slow = ok & (rho < thresh[sjl])
    # Victim = max est_remaining among slow, lowest task on ties.
    top = seg_max(slow, sjl, est, jcap, -jnp.inf)
    vt = seg_min(slow & (est == top[sjl]), sjl,
                 jnp.arange(cap, dtype=bp.dtype), jcap, cap)
    vict_row = p["order"][bp[jnp.minimum(vt, cap - 1)]]
    good = (job_count(p["running"], p["jls"], jcap) >= 2) & (msel >= 2) \
        & (vt < cap)
    return jnp.where(good, vict_row, -1)


def winning_pick(has_spec, has_orig, hi, lo, wjl, win_factor, jcap):
    """(jcap,) collective 'speculation is winning' verdicts from the
    per-task spec/orig presence, max rates and job (-1 = no attempt)."""
    win_seg = has_spec & (~has_orig | (hi > lo * win_factor))
    return seg_any(win_seg & (wjl >= 0), jnp.where(wjl >= 0, wjl, 0), jcap)


# ---------------------------------------------------------------------------
# Cores (traced; shared by jit entry points and the batched sweep)
# ---------------------------------------------------------------------------
def spatial_core(cols, nh, now, jcap):
    """(jcap, n_nodes) Eq. 1 hits (both phases merged)."""
    p = prep(cols, now)
    n = nh.shape[0]
    rho = p["prog"] / jnp.maximum(now - p["start"], 1e-9)
    seg = (p["jls"] * 2 + p["kind"]) * n + p["node"]
    sums, counts = seg_sum2(p["running"], seg, rho,
                            jnp.ones_like(rho), jcap * 2 * n)
    return spatial_pick(sums, counts, nh, jcap)


def temporal_core(cols, now, samp, init, prevk, n_nodes):
    """ζ sums per (job, node) over attempts alive at both samples, plus
    the scratch write-back (returned, applied host-side)."""
    p = prep(cols, now)
    jcap = samp.shape[0]
    n = n_nodes
    alive = temporal_alive(p, samp, prevk)
    seg = p["jls"] * n + p["node"]
    nb = jcap * n
    zn = seg_sum(alive, seg, p["prog"], nb)
    zp = seg_sum(alive, seg, p["tprog"], nb)
    cnt = seg_sum(alive, seg, jnp.ones_like(p["prog"]), nb)
    return temporal_pick(p, zn, zp, cnt, samp, init, prevk, jcap, n)


def failure_core(now, node_hb, node_marked, declared, thresholds,
                 responsive_window):
    silent = now - node_hb
    resp = silent <= responsive_window
    cand = ~resp & ~declared & ~node_marked & (silent > thresholds)
    return resp, cand


def late_core(cols, now, min_runtime, q, jcap):
    """(jcap,) LATE victim rows (-1 = no victim). Per-task best running
    attempt: max ζ, first canonical position on ties (= Python max()'s
    first-wins) — a scatter-max, then a scatter-min over the rows that
    reach it."""
    p = prep(cols, now)
    cap = p["cap"]
    m = p["running"]
    tseg = p["tseg"]
    best = seg_max(m, tseg, p["prog"], cap, -jnp.inf)
    bpos = seg_min(m & (p["prog"] == best[tseg]), tseg, p["pos"], cap, cap)
    has_spec = seg_any(m & p["spec"], tseg, cap)
    return late_pick(p, best, bpos, has_spec, now, min_runtime, q, jcap)


def winning_core(cols, now, win_factor, jcap):
    """(jcap,) collective 'speculation is winning' verdicts."""
    p = prep(cols, now)
    cap = p["cap"]
    m = p["active"] & (p["a_state"] == 0)    # running attempts, any task
    tseg = p["tseg"]
    rate = p["prog"] / jnp.maximum(now - p["start"], 1e-9)
    hi = seg_max(m & p["spec"], tseg, rate, cap, -jnp.inf)
    lo = seg_max(m & ~p["spec"], tseg, rate, cap, -jnp.inf)
    has_spec = seg_any(m & p["spec"], tseg, cap)
    has_orig = seg_any(m & ~p["spec"], tseg, cap)
    wjl = seg_max(m, tseg, p["jls"], cap, -1)
    return winning_pick(has_spec, has_orig, hi, lo, wjl, win_factor, jcap)


def reap_core(cols, now):
    """(cap,) canonical-position mask of reapable sibling attempts."""
    p = prep(cols, now)
    cap = p["cap"]
    live = p["active"] & (p["t_state"] == 2)
    done = seg_any(live & (p["a_state"] == 1), p["tseg"], cap)
    return live & done[p["tseg"]] & (p["a_state"] == 0)


def assess_summary_core(cols, nh, now, min_runtime, q, win_factor,
                        declared, thresholds, responsive_window, jcap):
    """One whole assessment step as a pure function — the unit the
    batched sweep vmaps across fault scenarios (§13.4). Temporal state
    is scenario-independent here: the sweep scores a single step, so ζ
    deltas (which need two samples) are not part of the summary."""
    hits = spatial_core(cols, nh, now, jcap)
    resp, cand = failure_core(now, cols["node_hb"], cols["node_marked"],
                              declared, thresholds, responsive_window)
    victims = late_core(cols, now, min_runtime, q, jcap)
    win = winning_core(cols, now, win_factor, jcap)
    reap = reap_core(cols, now)
    return {
        "spatial_hits": hits,
        "responsive": resp,
        "failed": cand,
        "late_victims": victims,
        "winning": win,
        "n_reap": reap.sum(),
    }


# ---------------------------------------------------------------------------
# Jit entry points (module-level: the compile cache is shared across
# simulations; padded shapes keep it warm)
# ---------------------------------------------------------------------------
_spatial_jit = jax.jit(spatial_core, static_argnames=("jcap",))
_temporal_jit = jax.jit(temporal_core, static_argnames=("n_nodes",))
_failure_jit = jax.jit(failure_core)
_late_jit = jax.jit(late_core, static_argnames=("jcap",))
_winning_jit = jax.jit(winning_core, static_argnames=("jcap",))
_reap_jit = jax.jit(reap_core)


def _spanned(method):
    """Run a backend method inside the span ``repro.accel.<method>``."""
    name = "accel." + method.__name__

    @functools.wraps(method)
    def spanned(self, *args, **kwargs):
        with span(name):
            return method(self, *args, **kwargs)
    return spanned


def _launch(fn, *args):
    """Dispatch one jitted or Pallas program; the device runs it after
    this returns."""
    with span("accel.launch"):
        return fn(*args)


class JaxBackend(AssessmentBackend):
    name = "jax"

    def __init__(self) -> None:
        self._dc: Optional[DeviceColumns] = None
        self._memo: Tuple[float, Optional[tuple]] = (np.nan, None)
        # The collective queries winning() once per straggler job within
        # a tick; the whole (jcap,) vector is computed on the first call.
        self._win_memo = (np.nan, np.nan, None, None)
        self._nh_dev = None
        self._nh_host = None
        self.upload_bytes = 0     # bytes of the last per-tick upload
        self.fetch_bytes = 0      # bytes copied to the host, cumulative

    # Entry points — the pallas subclass overrides the segmented passes.
    def _spatial_fn(self, cols, nh, now, jcap):
        return _spatial_jit(cols, nh, now, jcap=jcap)

    def _temporal_fn(self, cols, now, samp, init, prevk, n_nodes):
        return _temporal_jit(cols, now, samp, init, prevk, n_nodes=n_nodes)

    def _late_fn(self, cols, now, min_runtime, q, jcap):
        return _late_jit(cols, now, min_runtime, q, jcap=jcap)

    def _winning_fn(self, cols, now, win_factor, jcap):
        return _winning_jit(cols, now, win_factor, jcap=jcap)

    def _reap_fn(self, cols, now):
        return _reap_jit(cols, now)

    # ------------------------------------------------------------------
    def _cols(self, arr: ArraySnapshot, now: float, active) -> tuple:
        """Upload the padded mirror once per tick (assessments never
        mutate state mid-tick; the clock strictly increases). Keyed on
        the snapshot too — an instance may be shared across sims."""
        if self._memo[0] == now and self._dc is not None \
                and self._dc.arr is arr:
            return self._memo[1]
        if self._dc is None or self._dc.arr is not arr:
            self._dc = DeviceColumns(arr)
        arr.scratch(TMARK, np.int64, -1)
        arr.scratch(TPROG, np.float64, np.nan)
        with span("accel.refresh"):
            host = self._dc.refresh(active, scratch_names=(TMARK, TPROG))
        with precision(), span("accel.upload"):
            dev = to_device(host, now)
        self.upload_bytes = sum(int(v.nbytes) for v in dev.values())
        out = (dev, self._dc.jcap)
        self._memo = (now, out)
        return out

    def _nh(self, neighborhoods: np.ndarray):
        if self._nh_host is not neighborhoods:
            self._nh_dev = jnp.asarray(
                np.asarray(neighborhoods, dtype=np.int32))
            self._nh_host = neighborhoods
        return self._nh_dev

    @staticmethod
    def _wait(*outs) -> None:
        """Block until the device has computed ``outs``. Their copies to
        the host are queued first, so the device starts them as soon as
        it is done: one wait for all of them, not one per array."""
        for x in outs:
            x.copy_to_host_async()
        with span("accel.wait"):
            jax.block_until_ready(outs)

    def _fetch(self, *outs, dtype=None) -> list:
        """Device arrays on the host (``dtype`` converts there): the rest
        of their copies, and the conversion."""
        with span("accel.fetch"):
            host = [np.asarray(x, dtype=dtype) for x in outs]
        self.fetch_bytes += sum(int(x.nbytes) for x in outs)
        return host

    # ------------------------------------------------------------------
    @_spanned
    def spatial_hits(self, arr, now, active, neighborhoods):
        cols, jcap = self._cols(arr, now, active)
        nh = self._nh(neighborhoods)
        with precision():
            hits = _launch(self._spatial_fn, cols, nh, scalar(0.0), jcap)
        self._wait(hits)
        hits, = self._fetch(hits)
        return hits[:len(active)]

    @_spanned
    def temporal_zeta(self, arr, now, active, samp_flag, init_flag, prevk):
        cols, jcap = self._cols(arr, now, active)
        J = len(active)
        n = len(arr.node_ids)
        sampd = np.zeros(jcap, dtype=bool)
        sampd[:J] = samp_flag
        initd = np.zeros(jcap, dtype=bool)
        initd[:J] = init_flag
        prevkd = np.full(jcap, -2, dtype=np.int32)
        prevkd[:J] = prevk
        with precision():
            args = (cols, scalar(0.0), jnp.asarray(sampd),
                    jnp.asarray(initd), jnp.asarray(prevkd), n)
            outs = _launch(self._temporal_fn, *args)
        zn, zp, wmask, newmark, newtprog = outs
        self._wait(zn, zp, wmask)   # the marks are copied only if used
        zn, zp = self._fetch(zn, zp, dtype=np.float64)
        # Scratch write-back: the device computed this sample's marks in
        # canonical order; apply them to the host columns.
        n_rows = arr.n
        w = self._fetch(wmask)[0][:n_rows]
        if w.any():
            newmark, newtprog = self._fetch(newmark, newtprog)
            rows = arr.order()[w]
            arr.scratch(TMARK, np.int64, -1)[rows] = newmark[:n_rows][w]
            arr.scratch(TPROG, np.float64, np.nan)[rows] = \
                newtprog[:n_rows][w]
        return zn[:J], zp[:J]

    @_spanned
    def failure_masks(self, now, node_hb, node_marked, declared,
                      thresholds, responsive_window):
        with precision():
            f = scalar(0.0).dtype
            args = (scalar(0.0), jnp.asarray(np.asarray(node_hb) - now, f),
                    jnp.asarray(node_marked), jnp.asarray(declared),
                    jnp.asarray(thresholds, f), scalar(responsive_window))
            resp, cand = _launch(_failure_jit, *args)
        self._wait(resp, cand)
        return tuple(self._fetch(resp, cand))

    @_spanned
    def late_victims(self, arr, now, active, eligible, min_runtime,
                     slow_task_percentile):
        cols, jcap = self._cols(arr, now, active)
        with precision():
            victims = _launch(self._late_fn, cols, scalar(0.0),
                              scalar(min_runtime),
                              scalar(slow_task_percentile), jcap)
        self._wait(victims)
        victims, = self._fetch(victims, dtype=np.int64)
        return victims[:len(active)]

    @_spanned
    def winning(self, arr, now, job_idx, win_factor):
        active = arr.active_jobs()
        if self._win_memo[0] == now and self._win_memo[1] == win_factor \
                and self._win_memo[3] is arr:
            win = self._win_memo[2]
        else:
            cols, jcap = self._cols(arr, now, active)
            with precision():
                win = _launch(self._winning_fn, cols, scalar(0.0),
                              scalar(win_factor), jcap)
            self._wait(win)
            win, = self._fetch(win)
            self._win_memo = (now, win_factor, win, arr)
        jl = arr.job_local_map(active)
        pos = jl[job_idx] if 0 <= job_idx < len(jl) else -1
        if pos < 0:
            return False
        return bool(win[pos])

    @_spanned
    def reap_rows(self, arr, now):
        active = arr.active_jobs()
        cols, _jcap = self._cols(arr, now, active)
        with precision():
            reap = _launch(self._reap_fn, cols, scalar(0.0))
        self._wait(reap)
        mask, = self._fetch(reap)
        return arr.order()[mask[:arr.n]]


__all__ = [
    "JaxBackend",
    "assess_summary_core",
    "late_core",
    "late_pick",
    "member_percentile",
    "on_tpu",
    "order_stat",
    "ordered_sum",
    "percentile_indexes",
    "percentile_lerp",
    "precision",
    "prep",
    "reap_core",
    "spatial_core",
    "spatial_mask",
    "spatial_pick",
    "temporal_core",
    "temporal_pick",
    "to_device",
    "winning_core",
    "winning_pick",
]
