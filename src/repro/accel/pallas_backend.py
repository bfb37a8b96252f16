"""Pallas assessment backend: hand-written kernels for the segmented
reductions of an assessment tick (DESIGN.md §13.2).

Kernel layout
-------------
Every pass is one kernel shape, the **bucket pass**: per canonical row
``r`` a segment id ``seg[r]`` (``-1`` = row unused) and a few scalars;
per bucket ``b`` a few accumulators. The grid is (bucket tiles, row
blocks). Row blocks stream through scalar memory (SMEM), 1024 rows at
a time; the accumulators of one tile stay in vector memory as whole
``(8·v, 128)`` float tiles. Each row is read as scalars and folded into
every bucket of the tile at once by compare-and-select, so one bucket
sees its rows strictly in canonical order — the sequential accumulation
of ``np.bincount`` (bit-exact in float64 interpret mode, §13.3) without
a single scalar store to vector memory, which Mosaic refuses. A row
block whose segment ids cannot reach the tile is skipped, read from
scalar-prefetched per-block bounds: contiguous keys (task segments)
touch one or two blocks per tile.

- **Glance kernel** — Eq. 1 ρ sums/counts per (job, phase, node).
- **Temporal kernel** — Eq. 2–3 ζ sums per (job, node) over attempts
  alive at both samples.
- **LATE kernel** — per task, the best running attempt (max ζ, first in
  canonical order on ties) and whether a speculative sibling runs.
- **Collective kernel** — per task, max speculative / original rates,
  their presence, and the task's job.
- **Reap kernel** — per task, whether an attempt completed.

Elementwise projections (ζ progress, rates, masks) come from the shared
:func:`repro.accel.jax_backend.prep`, and the verdicts from the shared
``*_pick`` tails; the kernels own the reductions.

Interpret mode is chosen from the platform when a call is made
(:func:`repro.accel.jax_backend.on_tpu`): compiled for Mosaic on a TPU,
interpreted elsewhere. There is no switch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.accel.jax_backend import (
    JaxBackend,
    late_pick,
    on_tpu,
    prep,
    spatial_pick,
    temporal_alive,
    temporal_pick,
    winning_pick,
)

ROW_BLOCK = 1024          # rows per SMEM block
LANES = 128
_VREG = 8 * LANES         # buckets per (8, 128) float32 vreg


def bucket_pass(update, inits, seg, cols, nb, dtype, *, tile, interpret):
    """Segmented reduction of ``cols`` over ``nb`` buckets.

    ``seg`` is (cap,) int32 (``-1`` skips a row), ``cols`` a list of
    (cap,) per-row scalars, ``inits`` one fill per accumulator.
    ``update(hit, r, vals, accs) -> accs`` folds row ``r`` (canonical
    position; ``hit`` = bucket ids equal to ``seg[r]``) into the tile's
    accumulators. ``tile`` is the tile width in vregs. Returns one (nb,)
    array of ``dtype`` per accumulator."""
    cap = seg.shape[0]
    rb = min(ROW_BLOCK, cap)
    nblk = cap // rb
    tb = min(tile * _VREG, pl.cdiv(nb, _VREG) * _VREG)
    nt = pl.cdiv(nb, tb)
    tbs = tb // LANES
    segb = seg.reshape(nblk, rb)
    used = segb >= 0
    blo = jnp.where(used, segb, jnp.iinfo(jnp.int32).max).min(axis=1)
    bhi = jnp.where(used, segb, -1).max(axis=1)
    ncol = len(cols)

    def kernel(blo_ref, bhi_ref, seg_ref, *refs):
        col_refs, acc_refs = refs[:ncol], refs[ncol:]
        t = pl.program_id(0)
        k = pl.program_id(1)
        b0 = t * tb

        @pl.when(k == 0)
        def _init():
            for ref, v in zip(acc_refs, inits):
                ref[...] = jnp.full(ref.shape, v, ref.dtype)

        @pl.when((bhi_ref[k] >= b0) & (blo_ref[k] < b0 + tb))
        def _scan():
            bid = b0 + jax.lax.broadcasted_iota(jnp.int32, (tbs, LANES), 0) \
                * LANES + jax.lax.broadcasted_iota(jnp.int32, (tbs, LANES), 1)

            def row(i, accs):
                vals = [ref[i] for ref in col_refs]
                return tuple(update(bid == seg_ref[i], k * rb + i, vals,
                                    list(accs)))

            accs = jax.lax.fori_loop(
                0, rb, row, tuple(ref[...] for ref in acc_refs))
            for ref, a in zip(acc_refs, accs):
                ref[...] = a

    row_spec = pl.BlockSpec((rb,), lambda t, k, *_: (k,),
                            memory_space=pltpu.SMEM)
    acc_spec = pl.BlockSpec((tbs, LANES), lambda t, k, *_: (t, 0))
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nt, nblk),
            in_specs=[row_spec] * (1 + ncol),
            out_specs=[acc_spec] * len(inits)),
        out_shape=[jax.ShapeDtypeStruct((nt * tbs, LANES), dtype)]
        * len(inits),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(blo.astype(jnp.int32), bhi.astype(jnp.int32), seg.astype(jnp.int32),
      *cols)
    return [o.reshape(-1)[:nb] for o in outs]


def _i32(x):
    return x.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Row updates (one per kernel)
# ---------------------------------------------------------------------------
def _sum_update(hit, r, vals, accs):
    # Masked buckets add 0.0 — a bitwise no-op on the accumulators.
    out = [a + jnp.where(hit, v, 0.0) for a, v in zip(accs, vals)]
    return out + [accs[-1] + jnp.where(hit, 1.0, 0.0)]


def _late_update(hit, r, vals, accs):
    prog, spec = vals
    bprog, bpos, hspec = accs
    # Strictly-greater update in ascending canonical order: first wins.
    take = hit & (prog > bprog)
    return [jnp.where(take, prog, bprog),
            jnp.where(take, r.astype(bpos.dtype), bpos),
            jnp.where(hit & (spec == 1), 1.0, hspec)]


def _collective_update(hit, r, vals, accs):
    rate, spec, jls = vals
    hi, lo, has_spec, has_orig, wjl = accs
    hs = hit & (spec == 1)
    ho = hit & (spec == 0)
    return [jnp.where(hs, jnp.maximum(hi, rate), hi),
            jnp.where(ho, jnp.maximum(lo, rate), lo),
            jnp.where(hs, 1.0, has_spec),
            jnp.where(ho, 1.0, has_orig),
            jnp.where(hit, jnp.maximum(wjl, jls.astype(wjl.dtype)), wjl)]


def _mark_update(hit, r, vals, accs):
    return [jnp.where(hit, 1.0, accs[0])]


# ---------------------------------------------------------------------------
# Entry points (same signatures as the jax cores, plus ``interpret``)
# ---------------------------------------------------------------------------
def pallas_spatial(cols, nh, now, jcap, interpret):
    p = prep(cols, now)
    n = nh.shape[0]
    rho = p["prog"] / jnp.maximum(now - p["start"], 1e-9)
    seg = jnp.where(p["running"], (p["jls"] * 2 + p["kind"]) * n + p["node"],
                    -1)
    sums, counts = bucket_pass(_sum_update, [0.0, 0.0], seg, [rho],
                               jcap * 2 * n, rho.dtype, tile=8,
                               interpret=interpret)
    return spatial_pick(sums, counts, nh, jcap)


def pallas_temporal(cols, now, samp, init, prevk, n_nodes, interpret):
    p = prep(cols, now)
    jcap = samp.shape[0]
    n = n_nodes
    alive = temporal_alive(p, samp, prevk)
    seg = jnp.where(alive, p["jls"] * n + p["node"], -1)
    tprog = jnp.where(alive, p["tprog"], 0.0)
    zn, zp, cnt = bucket_pass(_sum_update, [0.0, 0.0, 0.0], seg,
                              [p["prog"], tprog], jcap * n,
                              p["prog"].dtype, tile=8, interpret=interpret)
    return temporal_pick(p, zn, zp, cnt, samp, init, prevk, jcap, n)


def pallas_late(cols, now, min_runtime, q, jcap, interpret):
    p = prep(cols, now)
    cap = p["cap"]
    fdt = p["prog"].dtype
    seg = jnp.where(p["running"], p["tseg"], -1)
    best, bpos, has_spec = bucket_pass(
        _late_update, [-jnp.inf, float(cap), 0.0], seg,
        [p["prog"], _i32(p["spec"])], cap, fdt, tile=8,
        interpret=interpret)
    return late_pick(p, best, _i32(bpos), has_spec > 0, now, min_runtime,
                     q, jcap)


def pallas_winning(cols, now, win_factor, jcap, interpret):
    p = prep(cols, now)
    cap = p["cap"]
    fdt = p["prog"].dtype
    m = p["active"] & (p["a_state"] == 0)    # running attempts, any task
    rate = p["prog"] / jnp.maximum(now - p["start"], 1e-9)
    seg = jnp.where(m, p["tseg"], -1)
    hi, lo, has_spec, has_orig, wjl = bucket_pass(
        _collective_update, [-jnp.inf, -jnp.inf, 0.0, 0.0, -1.0], seg,
        [rate, _i32(p["spec"]), _i32(p["jls"])], cap, fdt, tile=4,
        interpret=interpret)
    return winning_pick(has_spec > 0, has_orig > 0, hi, lo, _i32(wjl),
                        win_factor, jcap)


def pallas_reap(cols, now, interpret):
    p = prep(cols, now)
    cap = p["cap"]
    live = p["active"] & (p["t_state"] == 2)
    seg = jnp.where(live & (p["a_state"] == 1), p["tseg"], -1)
    done, = bucket_pass(_mark_update, [0.0], seg, [], cap,
                        p["prog"].dtype, tile=8, interpret=interpret)
    return live & (done[p["tseg"]] > 0) & (p["a_state"] == 0)


_jit = functools.partial(jax.jit, static_argnames=("jcap", "interpret"))
_spatial_jit = _jit(pallas_spatial)
_temporal_jit = jax.jit(pallas_temporal,
                        static_argnames=("n_nodes", "interpret"))
_late_jit = _jit(pallas_late)
_winning_jit = _jit(pallas_winning)
_reap_jit = jax.jit(pallas_reap, static_argnames=("interpret",))


class PallasBackend(JaxBackend):
    """Device layout, upload discipline and host glue are inherited from
    the jax backend; the segmented reductions run as Pallas kernels."""

    name = "pallas"

    def _spatial_fn(self, cols, nh, now, jcap):
        return _spatial_jit(cols, nh, now, jcap=jcap, interpret=not on_tpu())

    def _temporal_fn(self, cols, now, samp, init, prevk, n_nodes):
        return _temporal_jit(cols, now, samp, init, prevk, n_nodes=n_nodes,
                             interpret=not on_tpu())

    def _late_fn(self, cols, now, min_runtime, q, jcap):
        return _late_jit(cols, now, min_runtime, q, jcap=jcap,
                         interpret=not on_tpu())

    def _winning_fn(self, cols, now, win_factor, jcap):
        return _winning_jit(cols, now, win_factor, jcap=jcap,
                            interpret=not on_tpu())

    def _reap_fn(self, cols, now):
        return _reap_jit(cols, now, interpret=not on_tpu())


__all__ = ["PallasBackend", "bucket_pass", "pallas_late", "pallas_reap",
           "pallas_spatial", "pallas_temporal", "pallas_winning"]
