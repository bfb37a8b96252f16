"""Plain reference of training a Qwen1.5 decoder (``Qwen2ForCausalLM``).

What it implements, from the published architecture (HF
``Qwen/Qwen1.5-0.5B``, ``config.json`` in ``chipbench/configs``):

- token embedding; per layer RMSNorm (eps from the config, no bias),
  causal multi-head attention with biases on q, k and v, rotary position
  embedding of the "rotate half" form with base ``rope_theta``,
  softmax(q k^T / sqrt(head_dim)), output projection without bias, a
  residual add; RMSNorm, SwiGLU MLP ``(silu(x W_gate) * x W_up) W_down``,
  a residual add; a final RMSNorm and the output head tied to the
  embedding;
- the loss: mean next-token cross entropy over every position of every
  sequence of the step's batch (each shard's microbatch weighs the same);
- AdamW as the configuration's trainer states it: global-norm clipping
  to ``grad_clip_norm``, bias-corrected moments, decoupled weight decay
  ``p -= lr * (step + wd * p)``, moments in float32, parameters stored
  in the configuration's ``param_dtype`` (bfloat16) after every update.

Departures from the published description: weights are random, drawn
from the seed (matrices normal with the config's ``initializer_range``,
norm scales 1, biases 0); everything is computed in float32 with
``jax.default_matmul_precision("highest")``, layer by layer under
``jax.checkpoint`` and one microbatch at a time, so that it fits next to
nothing else on the chip. With ``cast`` set (the control) every matrix
product takes its operands rounded to that dtype first.

Nothing here imports the program under test.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Parameters: the layout the trainer under test takes, filled from a seed
# ---------------------------------------------------------------------------
def layout(cfg: Dict[str, object]) -> Dict[str, tuple]:
    """Path -> (shape, kind) of every parameter, kind in normal/ones/zeros.
    Layers are stacked on a leading axis."""
    d = cfg["hidden_size"]
    L = cfg["num_hidden_layers"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = d // h
    ff = cfg["intermediate_size"]
    v = cfg["vocab_size"]
    return {
        "embed": ((v, d), "normal"),
        "final_norm/scale": ((d,), "ones"),
        "layers/ffn/w_down": ((L, ff, d), "normal"),
        "layers/ffn/w_gate": ((L, d, ff), "normal"),
        "layers/ffn/w_up": ((L, d, ff), "normal"),
        "layers/ln1/scale": ((L, d), "ones"),
        "layers/ln2/scale": ((L, d), "ones"),
        "layers/mixer/bk": ((L, kv, hd), "zeros"),
        "layers/mixer/bq": ((L, h, hd), "zeros"),
        "layers/mixer/bv": ((L, kv, hd), "zeros"),
        "layers/mixer/wk": ((L, d, kv, hd), "normal"),
        "layers/mixer/wo": ((L, h, hd, d), "normal"),
        "layers/mixer/wq": ((L, d, h, hd), "normal"),
        "layers/mixer/wv": ((L, d, kv, hd), "normal"),
    }


def nest(flat: Dict[str, object]) -> Dict[str, object]:
    out: Dict[str, object] = {}
    for path, x in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = x
    return out


def flatten(tree: Dict[str, object], prefix: str = "") -> Dict[str, object]:
    out: Dict[str, object] = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def make_params(cfg: Dict[str, object], seed: int, dtype=jnp.bfloat16):
    """The weights, in ``dtype``, on the device in one jitted call."""
    lay = layout(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(lay))
        flat = {}
        for k, (path, (shape, kind)) in zip(keys, sorted(lay.items())):
            if kind == "normal":
                x = jax.random.normal(k, shape, F32) * std
            elif kind == "ones":
                x = jnp.ones(shape, F32)
            else:
                x = jnp.zeros(shape, F32)
            flat[path] = x.astype(dtype)
        return nest(flat)

    return build(jax.random.PRNGKey(seed % (2 ** 31)))


def tokens(seed: int, shard: int, offset: int, batch: int, seq_len: int,
           vocab: int) -> np.ndarray:
    """(batch, seq_len + 1) int32 tokens of one shard's microbatch."""
    ss = np.random.SeedSequence(entropy=seed, spawn_key=(shard, offset))
    rng = np.random.default_rng(ss)
    return rng.integers(0, vocab, size=(batch, seq_len + 1),
                        dtype=np.int64).astype(np.int32)


# ---------------------------------------------------------------------------
# Forward and loss
# ---------------------------------------------------------------------------
def _mm(eq, a, b, cast):
    if cast is not None:
        a = a.astype(cast).astype(F32)
        b = b.astype(cast).astype(F32)
    return jnp.einsum(eq, a, b)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * inv
    sin = jnp.sin(ang)[None, :, None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def loss_fn(cfg, params, toks, labels, cast=None):
    """Mean next-token cross entropy of one microbatch, float32."""
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    h_heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // h_heads
    p = jax.tree.map(lambda x: x.astype(F32), params)
    x = p["embed"][toks]
    s = toks.shape[1]
    pos = jnp.arange(s)
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def layer(h, lp):
        a = lp["mixer"]
        y = _rms(h, lp["ln1"]["scale"], eps)
        q = _mm("bsd,dhk->bshk", y, a["wq"], cast) + a["bq"]
        k = _mm("bsd,dhk->bshk", y, a["wk"], cast) + a["bk"]
        v = _mm("bsd,dhk->bshk", y, a["wv"], cast) + a["bv"]
        q = _rope(q, pos, theta)
        k = _rope(k, pos, theta)
        sc = _mm("bqhk,bthk->bhqt", q, k, cast) / np.sqrt(hd)
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        o = _mm("bhqt,bthk->bqhk", w, v, cast)
        h = h + _mm("bshk,hkd->bsd", o, a["wo"], cast)
        f = lp["ffn"]
        y = _rms(h, lp["ln2"]["scale"], eps)
        g = _mm("bsd,df->bsf", y, f["w_gate"], cast)
        u = _mm("bsd,df->bsf", y, f["w_up"], cast)
        h = h + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, f["w_down"], cast)
        return h, None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = _rms(x, p["final_norm"]["scale"], eps)
    logits = _mm("bsd,vd->bsv", x, p["embed"], cast)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items, cast):
    cfg = dict(cfg_items)

    def f(params, toks, labels):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda pp: loss_fn(cfg, pp, toks, labels, cast))(params)
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _adamw(lr, b1, b2, eps, wd, clip_norm, param_dtype):
    @jax.jit
    def f(params, m, v, count, grads):
        count = count + 1
        gsq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
        clip = jnp.minimum(1.0, clip_norm / (jnp.sqrt(gsq) + 1e-12))
        bc1 = 1.0 - b1 ** count.astype(F32)
        bc2 = 1.0 - b2 ** count.astype(F32)

        def upd(p, g, mm, vv):
            g = g * clip
            mm = b1 * mm + (1.0 - b1) * g
            vv = b2 * vv + (1.0 - b2) * g * g
            step = (mm / bc1) / (jnp.sqrt(vv / bc2) + eps)
            p = p - lr * (step + wd * p)
            return p.astype(param_dtype).astype(F32), mm, vv

        out = jax.tree.map(upd, params, grads, m, v)

        def pick(i):
            return jax.tree.map(lambda _p, o: o[i], params, out)
        return pick(0), pick(1), pick(2), count, clip
    return f


def train(cfg: Dict[str, object], trainer: Dict[str, object], params,
          batches: List[List[np.ndarray]], cast=None) -> Dict[str, object]:
    """Run ``len(batches)`` steps; ``batches[step][shard]`` is one
    microbatch of tokens ``(b, s + 1)``. Returns each step's loss, the
    per-leaf norms of the first step's clipped gradient, the per-leaf
    norms of the parameters' change over all steps, and the first clipped
    gradient itself (``first_grad``, host arrays by leaf path)."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    grad = _grad_fn(items, cast)
    opt = _adamw(float(trainer["learning_rate"]), float(trainer["b1"]),
                 float(trainer["b2"]), 1e-8, float(trainer["weight_decay"]),
                 float(trainer["grad_clip_norm"]),
                 jnp.dtype(trainer["param_dtype"]))
    p0 = jax.tree.map(lambda x: x.astype(F32), params)
    p = p0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    count = jnp.zeros((), jnp.int32)
    losses = []
    g1 = None
    for step, shards in enumerate(batches):
        acc = None
        step_loss = 0.0
        for tok in shards:
            tok = jnp.asarray(tok)
            loss, g = grad(p, tok[:, :-1], tok[:, 1:])
            step_loss += float(loss)
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
            del g
        acc = jax.tree.map(lambda x: x / len(shards), acc)
        losses.append(step_loss / len(shards))
        p, m, v, count, clip = opt(p, m, v, count, acc)
        if step == 0:
            first = jax.tree.map(lambda x: x * clip, acc)
            g1 = leaf_norms(first)
            first = host_leaves(first)
        del acc
    dp = leaf_norms(jax.tree.map(jnp.subtract, p, p0))
    return {"losses": losses, "grad_norms": g1, "update_norms": dp,
            "first_grad": first}


def host_leaves(tree) -> Dict[str, np.ndarray]:
    """Float32 host copies of every leaf, by path."""
    return {k: np.asarray(jax.device_get(x.astype(F32)))
            for k, x in flatten(tree).items()}


def diff_gaps(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              ref_norms: Dict[str, float], keep: List[str]
              ) -> Dict[str, float]:
    """Per leaf ‖got - ref‖ / max(‖ref‖, median ‖ref‖): how far the
    gradient itself, not only its size, lies from the reference's."""
    med = float(np.median([ref_norms[k] for k in keep]))
    return {k: float(np.linalg.norm((got[k] - ref[k]).ravel()))
            / max(ref_norms[k], med) for k in keep}


def leaf_norms(tree) -> Dict[str, float]:
    flat = flatten(tree)
    vals = jax.device_get({k: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32)))) for k, x in flat.items()})
    return {k: float(v) for k, v in vals.items()}


def leaf_gaps(got: Dict[str, float], ref: Dict[str, float],
              keep: Optional[List[str]] = None) -> Dict[str, float]:
    """Per leaf |‖got‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖)."""
    keys = sorted(keep if keep is not None else ref)
    med = float(np.median([ref[k] for k in keys]))
    return {k: abs(got[k] - ref[k]) / max(ref[k], med) for k in keys}


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[List[str]] = None) -> float:
    return max(leaf_gaps(got, ref, keep).values())


def moving_leaves(grad_norms: Dict[str, float], share: float = 1e-3
                  ) -> List[str]:
    """Leaves whose reference gradient is at least ``share`` of the
    median leaf's: the others (a key bias under softmax) move by
    round-off alone."""
    med = float(np.median(list(grad_norms.values())))
    return sorted(k for k, v in grad_norms.items() if v >= share * med)
