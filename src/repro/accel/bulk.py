"""Bulk-launch network solver backends (DESIGN.md §17.2).

The ε-fair model's per-drain work factors into two dense steps over the
columnar flow/link tables:

- ``waterfill(eff, links, valid)`` — the ε-fair max-min solve: per-link
  equilibrium shares plus per-flow rates (the §15.3 water-fill,
  previously inlined in ``FairNetwork._recompute``);
- ``price(share, links, valid)`` — batch pricing: the frozen-rate rule
  ``max(min(share[links]), 1)`` for a *batch* of flows at once (used by
  the drain-boundary re-allocation of in-flight transfers, §17.4).

Mirroring the :class:`repro.accel.base.AssessmentBackend` discipline,
three implementations ship behind one protocol:

- ``numpy`` — the bit-exact reference (the PR 5 solver loop, verbatim);
- ``jax`` — the same rounds as a jit ``lax.while_loop``, in the
  platform's precision (scoped float64 on the CPU, float32 on a TPU;
  §13.3); per-round link loads are scatter-adds of exact small
  integers, so CPU runs match numpy bit-for-bit;
- ``pallas`` — the jax steps: neither has a Pallas kernel yet
  (:class:`PallasBulk`).

Backends are resolved lazily (:func:`get_bulk_backend`) so the numpy
path never pays jax import cost; the network layer stays import-clean
of the simulator.
"""
from __future__ import annotations

from typing import Tuple, Union

import numpy as np

BULK_BACKENDS = ("numpy", "jax", "pallas")


class BulkBackend:
    """One drain's dense network math. Stateless w.r.t. the flow tables;
    may cache jit specializations / padded device buffers internally."""

    name: str = "?"

    def waterfill(self, eff: np.ndarray, links: np.ndarray,
                  valid: np.ndarray, eps: float
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """ε-fair max-min solve over ``k`` flows and ``nL`` links.

        ``eff`` (nL,) effective link capacities; ``links`` (k, 4) int
        link ids, -1 padded; ``valid = links >= 0``. Returns
        ``(share, rate)``: per-link equilibrium shares (never-bottleneck
        links expose residual headroom) and per-flow equilibrium rates.
        """
        raise NotImplementedError

    def price(self, share: np.ndarray, links: np.ndarray,
              valid: np.ndarray) -> np.ndarray:
        """Frozen-rate batch pricing: per-flow ``max(min(share[links
        over valid]), 1.0)`` — the launch rule applied to many flows in
        one step."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# numpy — bit-exact reference
# ---------------------------------------------------------------------------
class NumpyBulk(BulkBackend):
    name = "numpy"

    def waterfill(self, eff, links, valid, eps):
        nL = len(eff)
        k = len(links)
        share = eff.copy()
        rate = np.zeros(k)
        if not k:
            return share, rate
        flat_links = np.where(valid, links, 0)
        rem = eff.copy()
        alive = valid.any(axis=1)
        was_bott = np.zeros(nL, dtype=bool)
        eps1 = 1.0 + eps
        while True:
            a_links = flat_links[alive][valid[alive]]
            if not len(a_links):
                break
            cnt = np.bincount(a_links, minlength=nL)
            live = cnt > 0
            s_all = np.where(live, rem / np.maximum(cnt, 1), np.inf)
            s = float(s_all.min())
            bott = live & (s_all <= s * eps1)
            hit = alive & (bott[flat_links] & valid).any(axis=1)
            rate[hit] = s
            h_links = flat_links[hit][valid[hit]]
            rem = np.maximum(
                rem - np.bincount(h_links, minlength=nL) * s, 0.0)
            share[bott] = s
            was_bott |= bott
            alive &= ~hit
        free = ~was_bott
        share[free] = rem[free]
        return share, rate

    def price(self, share, links, valid):
        if not len(links):
            return np.zeros(0)
        per = np.where(valid, share[np.where(valid, links, 0)], np.inf)
        return np.maximum(per.min(axis=1), 1.0)


# ---------------------------------------------------------------------------
# jax — jit while_loop rounds, f64, padded specializations
# ---------------------------------------------------------------------------
class JaxBulk(BulkBackend):
    """Same rounds as the reference under ``lax.while_loop``. Flow count
    is padded to the next power of two so the jit specializes per
    (link-count, capacity) pair, not per call; padded rows carry no
    valid links and can never be hit."""

    name = "jax"

    def __init__(self):
        self._fills = {}
        self._prices = {}

    @staticmethod
    def _pad(k: int) -> int:
        cap = 16
        while cap < k:
            cap *= 2
        return cap

    def _fill_fn(self, nL: int, cap: int, eps: float):
        key = (nL, cap, eps)
        fn = self._fills.get(key)
        if fn is None:
            fn = _make_waterfill(nL, eps)
            self._fills[key] = fn
        return fn

    def waterfill(self, eff, links, valid, eps):
        k = len(links)
        if not k:
            return eff.copy(), np.zeros(0)
        import jax.numpy as jnp
        from repro.accel.jax_backend import precision, scalar
        cap = self._pad(k)
        L = np.zeros((cap, 4), dtype=np.int32)
        V = np.zeros((cap, 4), dtype=bool)
        L[:k] = np.where(valid, links, 0)
        V[:k] = valid
        with precision():
            fn = self._fill_fn(len(eff), cap, float(eps))
            one = scalar(1.0)
            share, rate = fn(jnp.asarray(eff, one.dtype), jnp.asarray(L),
                             jnp.asarray(V), one)
            return (np.asarray(share, np.float64),
                    np.asarray(rate, np.float64)[:k])

    def _price_core(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def price(share, L, V):
            per = jnp.where(V, share[L], jnp.inf)
            return jnp.maximum(per.min(axis=1), 1.0)
        return price

    def price(self, share, links, valid):
        k = len(links)
        if not k:
            return np.zeros(0)
        import jax.numpy as jnp
        from repro.accel.jax_backend import precision, scalar
        cap = self._pad(k)
        L = np.zeros((cap, 4), dtype=np.int32)
        V = np.zeros((cap, 4), dtype=bool)
        L[:k] = np.where(valid, links, 0)
        V[:k] = valid
        fn = self._prices.get("price")
        if fn is None:
            fn = self._prices["price"] = self._price_core()
        with precision():
            out = fn(jnp.asarray(share, scalar(0.0).dtype), jnp.asarray(L),
                     jnp.asarray(V))
            return np.asarray(out, np.float64)[:k]


def _make_waterfill(nL: int, eps: float):
    import jax
    import jax.numpy as jnp

    eps1 = 1.0 + eps

    @jax.jit
    def fill(eff, L, V, one):
        # ``one`` is the runtime-opaque anti-FMA guard (jax_backend
        # §13.3): ``rem - cnt·s`` must round the product before the
        # subtract, exactly as the numpy reference does.
        k = L.shape[0]
        has_link = V.any(axis=1)

        def cond(st):
            alive = st[0]
            return alive.any()

        def body(st):
            alive, rem, share, rate, was_bott = st
            w = alive[:, None] & V
            cnt = jnp.zeros(nL, eff.dtype).at[L].add(
                jnp.where(w, 1.0, 0.0))
            live = cnt > 0
            s_all = jnp.where(live, rem / jnp.maximum(cnt, 1.0), jnp.inf)
            s = s_all.min()
            bott = live & (s_all <= s * eps1)
            hit = alive & (bott[L] & V).any(axis=1)
            rate = jnp.where(hit, s, rate)
            hw = hit[:, None] & V
            dec = (jnp.zeros(nL, eff.dtype).at[L].add(
                jnp.where(hw, 1.0, 0.0)) * s) * one
            rem = jnp.maximum(rem - dec, 0.0)
            share = jnp.where(bott, s, share)
            was_bott = was_bott | bott
            alive = alive & ~hit
            return alive, rem, share, rate, was_bott

        init = (has_link, eff, eff,
                jnp.zeros(k, eff.dtype), jnp.zeros(nL, bool))
        alive, rem, share, rate, was_bott = jax.lax.while_loop(
            cond, body, init)
        share = jnp.where(was_bott, share, rem)
        return share, rate

    return fill


# ---------------------------------------------------------------------------
# pallas — the jax steps under the pallas name
# ---------------------------------------------------------------------------
class PallasBulk(JaxBulk):
    """Water-fill is a data-dependent while_loop with no natural grid,
    and pricing is a gather ``share[links]`` followed by a min that XLA
    fuses; Mosaic has no general gather. Both steps therefore run as
    JaxBulk's XLA, so ``pallas`` runs end to end behind one name."""

    name = "pallas"


def get_bulk_backend(spec: Union[str, BulkBackend, None]) -> BulkBackend:
    """Resolve a bulk backend name (or pass an instance through); jax
    and pallas import lazily, mirroring :func:`repro.accel.base.
    get_backend`."""
    if isinstance(spec, BulkBackend):
        return spec
    name = (spec or "numpy").lower()
    if name == "numpy":
        return NumpyBulk()
    if name == "jax":
        return JaxBulk()
    if name == "pallas":
        return PallasBulk()
    raise ValueError(
        f"unknown bulk backend {spec!r}; expected one of {BULK_BACKENDS}")
