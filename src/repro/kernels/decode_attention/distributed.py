"""Distributed flash decode: sequence-parallel attention over a sharded KV
cache (the §Perf fix for collective-bound decode).

Layout problem it solves: with the KV cache sharded seq→``model`` and query
heads sharded heads→``model``, plain XLA SPMD must all-gather the whole
cache on every layer (and re-shard the scatter writeback) — ~19 GB/token
per device for granite-20b decode_32k. But softmax is an online
reduction: each model-shard can attend over its LOCAL seq chunk and emit
``(o_partial, lse_partial)``; combining across shards costs
``heads × (head_dim + 1)`` floats per sequence — five orders of magnitude
less traffic.

Under ``shard_map`` (over the ``model`` axis):
  1. the token's K/V is written into the ONE local chunk that owns
     position ``pos`` (masked dynamic-update — no resharding);
  2. each shard runs the decode kernel/oracle over its chunk with a
     per-shard valid length clip(pos+1 − chunk_start, 0, chunk);
  3. partials merge with the standard online-softmax combine via
     ``jax.lax.all_gather`` over the axis.

Heads stay replicated across the model axis inside this op (they ride
batch/data outside); the cache is the thing worth sharding at 32k–500k
context.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.decode_attention import ref as _ref


def _local_attend(q, k, v, valid, scale):
    """Partial attention over a local chunk → (o, lse); safe when valid==0.
    q: (b, h, d); k/v: (b, c, kv, d); valid: (b,) int32.

    GQA/MQA via a grouped einsum — NEVER ``jnp.repeat`` the cache: at
    kv=1 / 48 q-heads that materializes 48× the cache bytes and turns the
    whole op memory-bound (measured: 169 GB/device on granite decode)."""
    b, h, d = q.shape
    _, c, kvh, _ = k.shape
    group = h // kvh
    qg = q.reshape(b, kvh, group, d)
    # MXU-native mixed precision: bf16 operands, f32 accumulation — no
    # materialized f32 copy of the cache chunk.
    logits = jnp.einsum("bkgd,bckd->bkgc", qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(c)[None, None, None, :]
            < valid[:, None, None, None])
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1)                       # (b, kv, g)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                            # (b, kv, g)
    # standard flash practice: PV in bf16 with f32 accumulation
    o = jnp.einsum("bkgc,bckd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    l_safe = jnp.maximum(l, 1e-30)
    lse = jnp.where(l > 0, m + jnp.log(l_safe), -jnp.inf)
    return (o / l_safe[..., None]).reshape(b, h, d), \
        lse.reshape(b, h)


def dist_decode_update_attend(
    q: jax.Array,            # (b, h, d)
    new_k: jax.Array,        # (b, kv, d) this token's key
    new_v: jax.Array,        # (b, kv, d)
    cache_k: jax.Array,      # (b, S, kv, d) seq-sharded over `axis`
    cache_v: jax.Array,
    pos: jax.Array,          # (b,) write position (== tokens so far)
    *,
    axis: str = "model",
    mesh=None,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (attn_out (b,h,d), new_cache_k, new_cache_v).

    Must run under a mesh containing ``axis``; cache_k/v are expected
    sharded P(batch_axes, axis, None, None).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if mesh is None:
        from repro.parallel.sharding import current_mesh
        mesh = current_mesh()
    n_shards = mesh.shape[axis]
    S = cache_k.shape[1]
    chunk = S // n_shards
    # batch stays wherever the rule table puts it (data/pod); only the
    # cache seq dim rides `axis` inside this op. q arrives heads-sharded
    # over `axis` from the projection — the implied gather is b×h×d bytes,
    # noise next to the cache traffic this op eliminates.
    from repro.parallel.sharding import _current_rules, physical_spec
    _, act_rules = _current_rules()
    bspec = physical_spec((q.shape[0],), ("batch",),
                          act_rules, mesh)[0]

    def body(q, nk, nv, ck, cv, pos):
        idx = jax.lax.axis_index(axis)
        start = idx * chunk
        # 1. local masked writeback of the new token
        local = pos - start                          # (b,)
        in_range = (local >= 0) & (local < chunk)
        li = jnp.clip(local, 0, chunk - 1)
        bidx = jnp.arange(q.shape[0])
        ck_new = ck.at[bidx, li].set(
            jnp.where(in_range[:, None, None], nk, ck[bidx, li]))
        cv_new = cv.at[bidx, li].set(
            jnp.where(in_range[:, None, None], nv, cv[bidx, li]))
        # 2. partial attention over the local chunk
        valid = jnp.clip(pos + 1 - start, 0, chunk)
        o, lse = _local_attend(q, ck_new, cv_new, valid, scale)
        # 3. online-softmax combine across shards via psum (an all-gather
        # would move n× these bytes; the reduction form is the minimum)
        m = jax.lax.pmax(lse, axis)                  # (b, h)
        w = jnp.exp(lse - m)
        w = jnp.where(jnp.isfinite(w), w, 0.0)       # empty shard → 0
        num = jax.lax.psum(o * w[..., None], axis)   # (b, h, d)
        den = jnp.maximum(jax.lax.psum(w, axis), 1e-30)
        out = num / den[..., None]
        return out.astype(q.dtype), ck_new, cv_new

    pspec_cache = P(bspec, axis, None, None)
    pspec_bhd = P(bspec, None, None)
    specs = dict(
        mesh=mesh,
        in_specs=(pspec_bhd, pspec_bhd, pspec_bhd,
                  pspec_cache, pspec_cache, P(bspec)),
        out_specs=(pspec_bhd, pspec_cache, pspec_cache))
    mapped = jax.shard_map(body, check_vma=False, **specs)
    return mapped(q, new_k, new_v, cache_k, cache_v, pos)


def reference(q, new_k, new_v, cache_k, cache_v, pos, *, scale=None):
    """Oracle: plain update + full decode attention."""
    b = q.shape[0]
    bidx = jnp.arange(b)
    ck = cache_k.at[bidx, pos].set(new_k)
    cv = cache_v.at[bidx, pos].set(new_v)
    out = _ref.decode_attention_reference(q, ck, cv, pos + 1, scale=scale)
    return out, ck, cv
