"""Eq. 1–4 of the paper, as vectorized, substrate-agnostic math.

Two mirrored implementations are provided:

- numpy (``*_np``) — used by the coordinator / simulator hot path, where a
  single assessment tick covers every node at once and runs millions of
  times inside the discrete-event benchmarks;
- jax (``*_jax``) — jit-able versions used by the live runtime's coordinator
  (assessments over thousands of node rows batch nicely on-device) and by
  the property tests that pin the two implementations together.

Notation follows §III.A:
  ρ(t)   task progress rate  = ζ(t)/τ_t
  P(N^J) NodeProgressRate    = avg over tasks of job J on node N of ρ
  ζ(N^J) node progress score = Σ ProgressScore of *ongoing* tasks
  Δ(N^J) NodeProgressChangeRate (Eq. 2)
  Eq. 1  spatial slow-node test:   P < mean_NH(P) − σ_NH(P)
  Eq. 3  temporal slow-node test:  Δ|Ti < threshold × Δ|Ti−1
  Eq. 4  adaptive unresponsiveness estimate over the last L outages
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "node_progress_rate_np",
    "spatial_slow_mask_np",
    "spatial_slow_mask_batch_np",
    "spatial_stats_batch_np",
    "temporal_slow_mask_np",
    "eq4_estimate_np",
    "eq4_estimate_weights",
    "node_progress_rate_jax",
    "spatial_slow_mask_jax",
    "temporal_slow_mask_jax",
    "eq4_estimate_jax",
]


# ---------------------------------------------------------------------------
# Eq. 1 — spatial neighborhood assessment
# ---------------------------------------------------------------------------
def node_progress_rate_np(progress: np.ndarray, runtime: np.ndarray,
                          node_of_task: np.ndarray, n_nodes: int
                          ) -> np.ndarray:
    """P(N^J) per node: mean ρ(t_i) over the job-J tasks on each node.

    progress/runtime/node_of_task are parallel arrays over the job's
    *running* tasks. Nodes with no tasks get NaN (excluded from Eq. 1).
    """
    rho = progress / np.maximum(runtime, 1e-9)
    sums = np.zeros(n_nodes)
    counts = np.zeros(n_nodes)
    np.add.at(sums, node_of_task, rho)
    np.add.at(counts, node_of_task, 1.0)
    with np.errstate(invalid="ignore"):
        return np.where(counts > 0, sums / np.maximum(counts, 1.0), np.nan)


def spatial_slow_mask_np(P: np.ndarray, neighborhoods: np.ndarray
                         ) -> np.ndarray:
    """Eq. 1: mark node i slow iff
    ``P[i] < mean(P[NH{i}]) − std(P[NH{i}])`` (NaN rows never fire).

    ``neighborhoods`` is (n_nodes, SIZE_NEIGHBOR) int indices of each node's
    neighborhood (including itself, per the paper's NH{N_i} collection).
    """
    Pn = P[neighborhoods]                      # (n, k)
    valid = ~np.isnan(Pn)
    cnt = valid.sum(axis=1)
    with np.errstate(invalid="ignore"):
        mean = np.nansum(Pn, axis=1) / np.maximum(cnt, 1)
        var = np.nansum((Pn - mean[:, None]) ** 2 * valid, axis=1) \
            / np.maximum(cnt, 1)
    std = np.sqrt(var)
    # Need ≥2 live neighbors for variation to be meaningful, and a live P.
    ok = (cnt >= 2) & ~np.isnan(P)
    return ok & (P < (mean - std))


def spatial_stats_batch_np(P: np.ndarray, neighborhoods: np.ndarray
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Eq. 1's scores batched over assessment groups: ``P`` is (groups,
    n_nodes) — one row per (job, phase) — and the result is ``(mean,
    std, ok)``, each (groups, n_nodes): the neighborhood mean and σ, and
    the gate (≥ 2 live neighbors and a live ``P``).

    Operation-for-operation identical to :func:`spatial_slow_mask_np`
    applied per row (same nansum element order, same clip constants), so
    the vectorized glance path is bit-equivalent to the per-job reference
    loop (DESIGN.md §11.3).
    """
    Pn = P[:, neighborhoods]                   # (g, n, k)
    valid = ~np.isnan(Pn)
    cnt = valid.sum(axis=2)
    with np.errstate(invalid="ignore"):
        mean = np.nansum(Pn, axis=2) / np.maximum(cnt, 1)
        var = np.nansum((Pn - mean[:, :, None]) ** 2 * valid, axis=2) \
            / np.maximum(cnt, 1)
    std = np.sqrt(var)
    ok = (cnt >= 2) & ~np.isnan(P)
    return mean, std, ok


def spatial_slow_mask_batch_np(P: np.ndarray, neighborhoods: np.ndarray
                               ) -> np.ndarray:
    """Eq. 1 batched over assessment groups: (groups, n_nodes) hits."""
    mean, std, ok = spatial_stats_batch_np(P, neighborhoods)
    return ok & (P < (mean - std))


# ---------------------------------------------------------------------------
# Eq. 2–3 — temporal assessment
# ---------------------------------------------------------------------------
def temporal_slow_mask_np(zeta_now: np.ndarray, zeta_prev: np.ndarray,
                          dt_now: float, delta_prev: np.ndarray,
                          threshold_slowdown: float = 0.1,
                          min_prev_delta: float = 1e-9) -> np.ndarray:
    """Eq. 2–3 over all nodes at once.

    Returns (slow_mask, delta_now). ``zeta_*`` are per-node sums of ongoing
    ProgressScores (completed tasks excluded — the paper's guard against
    end-of-job decline); ``delta_prev`` is Δ|Ti−1 (NaN before two samples).
    """
    delta_now = (zeta_now - zeta_prev) / max(dt_now, 1e-9)
    with np.errstate(invalid="ignore"):
        slow = (~np.isnan(delta_prev)) \
            & (delta_prev > min_prev_delta) \
            & (delta_now < threshold_slowdown * delta_prev)
    return slow, delta_now


# ---------------------------------------------------------------------------
# Eq. 4 — adaptive failure threshold
# ---------------------------------------------------------------------------
def eq4_estimate_weights(L: int) -> np.ndarray:
    """Weights 2^{L+1-k} for k = 1..L (most recent outage first)."""
    k = np.arange(1, L + 1)
    return 2.0 ** (L + 1 - k)


def eq4_estimate_np(history: Sequence[float], L: int) -> Optional[float]:
    """P_{n+1} = Σ_{k=1..L} 2^{L+1−k}·R_{n+1−k} / Σ_{k=1..L} 2^k.

    ``history`` lists past outage durations, most recent LAST. Uses the last
    ``L`` entries (fewer ⇒ window shrinks to what exists; none ⇒ None).

    Note the paper's denominator Σ 2^k = 2^{L+1} − 2 differs from the
    numerator weight sum (Σ 2^{L+1−k} over k=1..L = 2^{L+1} − 2 as well —
    the two sums are equal, so this *is* a proper weighted mean).
    """
    if not history:
        return None
    h = list(history)[-L:]
    Leff = len(h)
    w = eq4_estimate_weights(Leff)
    # h is oldest→newest; R_{n+1-k} pairs k=1 with the newest entry.
    r = np.asarray(h[::-1], dtype=float)
    denom = float(np.sum(2.0 ** np.arange(1, Leff + 1)))
    return float(np.dot(w, r) / denom)


# ---------------------------------------------------------------------------
# JAX mirrors (imported lazily so the simulator never pays jax startup).
# ---------------------------------------------------------------------------
def node_progress_rate_jax(progress, runtime, node_of_task, n_nodes: int):
    import jax.numpy as jnp

    rho = progress / jnp.maximum(runtime, 1e-9)
    sums = jnp.zeros(n_nodes).at[node_of_task].add(rho)
    counts = jnp.zeros(n_nodes).at[node_of_task].add(1.0)
    return jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), jnp.nan)


def spatial_slow_mask_jax(P, neighborhoods):
    import jax.numpy as jnp

    Pn = P[neighborhoods]
    valid = ~jnp.isnan(Pn)
    cnt = valid.sum(axis=1)
    mean = jnp.nansum(Pn, axis=1) / jnp.maximum(cnt, 1)
    var = jnp.nansum(jnp.where(valid, (Pn - mean[:, None]) ** 2, 0.0),
                     axis=1) / jnp.maximum(cnt, 1)
    std = jnp.sqrt(var)
    ok = (cnt >= 2) & ~jnp.isnan(P)
    return ok & (P < (mean - std))


def temporal_slow_mask_jax(zeta_now, zeta_prev, dt_now, delta_prev,
                           threshold_slowdown: float = 0.1,
                           min_prev_delta: float = 1e-9):
    import jax.numpy as jnp

    delta_now = (zeta_now - zeta_prev) / jnp.maximum(dt_now, 1e-9)
    slow = (~jnp.isnan(delta_prev)) \
        & (delta_prev > min_prev_delta) \
        & (delta_now < threshold_slowdown * delta_prev)
    return slow, delta_now


def eq4_estimate_jax(history, L: int):
    """history: (L,) most recent LAST, NaN-padded at the front."""
    import jax.numpy as jnp

    h = history[-L:]
    # Reverse so index j (0-based) is the j-th most recent sample (k = j+1).
    r = h[::-1]
    v = ~jnp.isnan(r)
    leff = v.sum()  # live window length (may be < L early on)
    j = jnp.arange(L, dtype=h.dtype)
    # weight 2^{Leff+1-k} = 2^{Leff-j}; denominator Σ_{k=1..Leff} 2^k.
    w = jnp.where(v, 2.0 ** (leff - j), 0.0)
    denom = 2.0 ** (leff + 1) - 2.0
    num = jnp.sum(w * jnp.where(v, r, 0.0))
    return jnp.where(leff > 0, num / jnp.maximum(denom, 1.0), jnp.nan)
