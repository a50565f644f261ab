"""Plain reference for StarCoder2 at long sequences, and local SGD on it.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``: no kernels, no batching over workers. It imports
nothing of the program and takes nothing the program made: the weights
come from :func:`init_weights` and the seed, as the program's do.

The architecture is the published ``config.json`` named in the
configuration file: LayerNorm with bias and the published epsilon,
biases on every linear layer, RoPE, grouped-query attention over a
causal sliding window, a non-gated tanh-GELU MLP, no embedding scale and
a tied head. The file's ``assumed`` block says what else runs (the
parameter dtype, norm gains stored as offsets from 1).

At 8k tokens the whole (S, S) score matrix and the whole (S, V) logits
do not fit next to the weights, so the reference computes the same
functions in pieces: attention query block by query block against only
the keys in that block's window, and the loss over sequence chunks, each
piece and each layer under ``jax.checkpoint``.

:func:`train_phase` runs the cell's first phase as the ``decoder``
reference does (the same momentum, rounding, averaging, planted faults
and norms); only the model differs.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.ref.decoder import (_arrays, _cast, _Frozen, _norms, _stats,
                               round_to, seed_key, unstack)

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024      # query positions per attention block
LOSS_CHUNK = 1024   # positions per loss chunk


def shape(c) -> dict:
    """The architecture as it runs. The dense keys ``bench.flops``
    reads (d, ff, layers, heads, kv_heads, head_dim, vocab, gated,
    window) hold the published values, depth as the file cuts it."""
    if c["model_type"] != "starcoder2" or c["norm_type"] != "layer_norm":
        raise ValueError("this reference computes StarCoder2 only")
    d, heads = c["hidden_size"], c["num_attention_heads"]
    return {
        "d": d, "ff": c["intermediate_size"],
        "layers": c["num_hidden_layers"], "heads": heads,
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim", d // heads),
        "vocab": c["vocab_size"], "act": "gelu_tanh", "gated": False,
        "norm": "layernorm", "bias": bool(c["use_bias"]),
        "rope_theta": float(c["rope_theta"]),
        "window": int(c.get("sliding_window") or 0),
        "tied": bool(c.get("tie_word_embeddings", True)),
        "eps": float(c["norm_epsilon"]),
        "param_dtype": c["assumed"]["param_dtype"],
    }


def weight_specs(c) -> dict:
    """Ordered ``name -> (shape, fan_in)``; fan_in None means zeros (norm
    gains, stored as offsets from 1, and every bias)."""
    s = shape(c)
    d, ff = s["d"], s["ff"]
    q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    specs = {"embed": ((s["vocab"], d), d)}
    if not s["tied"]:
        specs["head"] = ((d, s["vocab"]), d)
    norm = {"scale": ((d,), None), "bias": ((d,), None)}
    for i in range(s["layers"]):
        p = f"layers.{i}."
        specs.update({p + "norm1." + n: v for n, v in norm.items()})
        specs.update({p + "attn.wq": ((d, q), d), p + "attn.wk": ((d, kv), d),
                      p + "attn.wv": ((d, kv), d), p + "attn.wo": ((q, d), q)})
        if s["bias"]:
            specs.update({p + "attn.bq": ((q,), None),
                          p + "attn.bk": ((kv,), None),
                          p + "attn.bv": ((kv,), None),
                          p + "attn.bo": ((d,), None)})
        specs.update({p + "norm2." + n: v for n, v in norm.items()})
        specs[p + "mlp.w_in"] = ((d, ff), d)
        specs[p + "mlp.w_out"] = ((ff, d), ff)
        if s["bias"]:
            specs[p + "mlp.b_in"] = ((ff,), None)
            specs[p + "mlp.b_out"] = ((d,), None)
    specs.update({"final_norm." + n: v for n, v in norm.items()})
    return specs


def width(c) -> int:
    """P: every parameter, as one worker's row."""
    return sum(math.prod(shp) for shp, _ in weight_specs(c).values())


def init_weights(c, key, dtype):
    """Every weight from one key: normal(0, 1/sqrt(fan_in)), zeros for
    norm gains and biases, in ``dtype``. Traceable: wrap it in one jit
    to make the weights on the device."""
    out = {}
    for i, (name, (shp, fan_in)) in enumerate(weight_specs(c).items()):
        if fan_in is None:
            out[name] = jnp.zeros(shp, dtype)
        else:
            w = jax.random.normal(jax.random.fold_in(key, i), shp, jnp.float32)
            out[name] = (w / math.sqrt(fan_in)).astype(dtype)
    return out


# --------------------------------------------------------------------------
# forward pass and loss
# --------------------------------------------------------------------------

def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _lin(s, lw, name, x):
    y = _mm(x, lw[name])
    return y + lw[name.replace(".w", ".b", 1)] if s["bias"] else y


def _norm(s, w, name, x):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + s["eps"])
    return x * (1.0 + w[name + ".scale"]) + w[name + ".bias"]


def _rope(s, x, pos):
    half = s["head_dim"] // 2
    inv = 1.0 / (s["rope_theta"] ** (np.arange(half) / half))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attention(s, q, k, v):
    """Causal attention over the last ``window`` keys (all keys for no
    window), query block by query block: each block of ``Q_BLOCK``
    queries against the ``Q_BLOCK + window`` keys that end with it.
    q: (B, n, H, hd), k/v: (B, n, Hkv, hd) -> (B, n, H, hd)."""
    b, n, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    w = min(s["window"] or n, n)
    c = min(Q_BLOCK, n)
    if n % c:
        raise ValueError(f"sequence {n} is not a whole number of "
                         f"{c}-query blocks")
    pad = ((0, 0), (w, 0), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * c, c, 1)
        qi = qi.reshape(b, c, hkv, g, hd)
        ki = jax.lax.dynamic_slice_in_dim(kp, i * c, c + w, 1)
        vi = jax.lax.dynamic_slice_in_dim(vp, i * c, c + w, 1)
        qpos = i * c + jnp.arange(c)[:, None]
        kpos = i * c - w + jnp.arange(c + w)[None, :]
        allow = (kpos <= qpos) & (kpos > qpos - w) & (kpos >= 0)
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qi, ki,
                        precision=HI) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(allow, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", pr, vi, precision=HI)
        return o.reshape(b, c, h, hd)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(n // c))
    return out.swapaxes(0, 1).reshape(b, n, h, hd)


def _block(s, pos, x, lw):
    b, n, _ = x.shape
    h, hkv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    y = _norm(s, lw, "norm1", x)
    q = _lin(s, lw, "attn.wq", y).reshape(b, n, h, hd)
    k = _lin(s, lw, "attn.wk", y).reshape(b, n, hkv, hd)
    v = _lin(s, lw, "attn.wv", y).reshape(b, n, hkv, hd)
    o = attention(s, _rope(s, q, pos), _rope(s, k, pos), v)
    x = x + _lin(s, lw, "attn.wo", o.reshape(b, n, h * hd))
    y = _norm(s, lw, "norm2", x)
    u = _gelu(_lin(s, lw, "mlp.w_in", y))
    return x + _lin(s, lw, "mlp.w_out", u), None


def _nll_sum(head, x, targets):
    """Summed next-token NLL of one chunk; target -1 is no target."""
    logp = jax.nn.log_softmax(_mm(x, head), axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(targets, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(jnp.where(targets >= 0, nll, 0.0))


def loss(c, w, tokens):
    """Mean next-token cross-entropy of ``tokens`` (B, S) under stacked
    float32 weights ``w`` (:func:`stack`), the last position having no
    target."""
    s = shape(c)
    b, n = tokens.shape
    top = w["top"]
    pos = jnp.arange(n)
    x, _ = jax.lax.scan(jax.checkpoint(partial(_block, s, pos)),
                        top["embed"][tokens], w["layers"])
    x = _norm(s, top, "final_norm", x)
    head = top["embed"].T if s["tied"] else top["head"]
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
    ch = min(LOSS_CHUNK, n)
    if n % ch:
        raise ValueError(f"sequence {n} is not a whole number of "
                         f"{ch}-position loss chunks")
    xs = x.reshape(b, n // ch, ch, -1).swapaxes(0, 1)
    ts = targets.reshape(b, n // ch, ch).swapaxes(0, 1)
    one = jax.checkpoint(_nll_sum)

    def body(acc, xt):
        return acc + one(head, *xt), None
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
    return total / (b * (n - 1))


def stack(c, w):
    """Named weights as ``{"top": {...}, "layers": {part: (L, ...)}}``:
    each per-layer weight stacked over the layers, so that the loss scans
    one block instead of unrolling all of them."""
    n = shape(c)["layers"]
    parts = sorted({k.split(".", 2)[2] for k in w if k.startswith("layers.")})
    return {"top": {k: v for k, v in w.items() if not k.startswith("layers.")},
            "layers": {q: jnp.stack([w[f"layers.{i}.{q}"] for i in range(n)])
                       for q in parts}}


# --------------------------------------------------------------------------
# local SGD over the first phase
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4))
def _worker_step(c, pdtype, vdtype, theta, vel, tokens, lr, mu):
    """One worker's step on stacked weights: loss and gradient at
    ``theta``, momentum rounded to ``vdtype``, the update rounded to the
    parameter dtype. Returns (theta, vel, loss)."""
    dt = jnp.dtype(pdtype)
    w32 = jax.tree.map(lambda v: v.astype(jnp.float32), theta)
    l, grad = jax.value_and_grad(lambda w: loss(c, w, tokens))(w32)
    vel = jax.tree.map(lambda v, g: round_to(mu * v + round_to(g, dt),
                                             jnp.dtype(vdtype)), vel, grad)
    theta = jax.tree.map(lambda w, v: round_to(w - lr * v, dt).astype(dt),
                         w32, vel)
    return theta, vel, l


def _theta0(c, seed, pdt):
    dt0 = jnp.dtype(shape(c)["param_dtype"])
    return _cast(jax.jit(lambda key: stack(c, init_weights(c, key, dt0)))(
        seed_key(seed)), pdt)


def train_phase(c, traffic, seed, blocks, *, param_dtype=None,
                vel_dtype="float32", half_batch=False, groups=1,
                devices=None):
    """The reference's first phase of the cell: ``blocks`` is the
    (K, M, B, S) token block the program consumed. Workers sit on
    ``devices`` (worker w on ``devices[w * len(devices) // M]``).
    ``param_dtype`` overrides the stored precision (the control) and
    ``vel_dtype`` the momentum's (float32, as the configuration states);
    ``half_batch`` takes each worker's loss over half its rows (half of
    each sequence for a batch of one), and ``groups`` > 1 averages
    within that many contiguous worker groups only (two planted faults).
    Returns per-step ``loss`` and ``dispersion`` lists and per-worker,
    per-leaf ``change`` and ``velocity`` norm dicts. The seeded weights
    are made again for the norms rather than held through the phase,
    which keeps the reference's state within one chip at 8k tokens."""
    if traffic["optimizer"] != "momentum" or traffic["schedule"] != "periodic":
        raise ValueError("the reference runs periodic momentum SGD only")
    devices = devices or jax.devices()[:1]
    cf = _Frozen(c)
    k_steps, m = blocks.shape[0], blocks.shape[1]
    pdt = param_dtype or shape(c)["param_dtype"]
    period = traffic["phase_len"]
    lr = jnp.float32(traffic["lr"])
    mu = jnp.float32(traffic["momentum"])
    dev = [devices[w * len(devices) // m] for w in range(m)]
    theta0 = _theta0(c, seed, pdt)
    keys = _arrays(theta0)
    theta = [jax.device_put(theta0, d, may_alias=False) for d in dev]
    vel = [jax.device_put(jax.tree.map(
        lambda v: jnp.zeros(v.shape, jnp.float32), theta0), d) for d in dev]
    del theta0
    out = {"loss": [], "dispersion": []}
    b = blocks.shape[2]
    size = m // groups
    for t in range(1, k_steps + 1):
        ls = []
        for w in range(m):
            tok = blocks[t - 1, w]
            if half_batch:
                tok = tok[: b // 2] if b > 1 else tok[:, : tok.shape[1] // 2]
            theta[w], vel[w], l = _worker_step(
                cf, pdt, vel_dtype, theta[w], vel[w],
                jax.device_put(tok, dev[w]), lr, mu)
            ls.append(l)
        out["loss"].append(float(np.mean([float(v) for v in ls])))
        disp = 0.0
        for g, k in keys:
            mean, sq = _stats([jax.device_put(th[g][k], dev[0])
                               for th in theta])
            disp += float(sq)
            if t % period:
                continue
            for gi in range(groups):
                rows = range(gi * size, (gi + 1) * size)
                if groups > 1:
                    mean, _ = _stats([jax.device_put(theta[w][g][k], dev[0])
                                      for w in rows])
                avg = _cast(mean, pdt)
                for w in rows:
                    theta[w][g][k] = jax.device_put(avg, dev[w],
                                                    may_alias=False)
        out["dispersion"].append(disp / m)
    norms = []
    for w in range(m):
        theta0 = jax.device_put(_theta0(c, seed, pdt), dev[w])
        norms.append(jax.device_get(_norms(theta[w], theta0, vel[w])))
        del theta0
    out["change"] = [{k: float(v) for k, v in unstack(ch).items()}
                     for ch, _ in norms]
    out["velocity"] = [{k: float(v) for k, v in unstack(vn).items()}
                       for _, vn in norms]
    return out
