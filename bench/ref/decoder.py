"""Plain reference for decoder-only transformer LMs, and local SGD on it.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``: no kernels, no planes, no batching over workers.
It imports nothing of the program and takes nothing the program made:
the weights come from :func:`init_weights` and the seed, as the
program's do.

The architecture follows the published ``config.json`` named in the
configuration file, except where the file's ``assumed`` block says what
actually runs (norm epsilon, embedding scale, norm gains stored as
offsets from 1, no linear biases, the parameter dtype). Those are the
program's departures from the published models, written down there.

:func:`train_phase` runs the cell's first phase: M workers, each with
its own batch per step, heavy-ball momentum with the parameters rounded
to the parameter dtype after every update (the gradient too, since a
gradient has its parameter's dtype), and the periodic worker mean,
rounded, at every K-th step. ``param_dtype`` swaps the storage
precision (the control), ``vel_dtype`` the momentum's; ``half_batch``
and ``groups`` plant faults.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST

# what each model_type fixes beyond its config keys
_KINDS = {
    "llama": {"norm": "rmsnorm", "gated": True},
    "starcoder2": {"norm": "layernorm", "gated": False},
}
_ACTS = {"silu": "silu", "gelu_pytorch_tanh": "gelu_tanh"}


def shape(c) -> dict:
    """The architecture as it runs: published sizes, the kind's fixed
    choices, and the as-run departures from ``c["assumed"]``."""
    kind = _KINDS[c["model_type"]]
    a = c["assumed"]
    d, heads = c["hidden_size"], c["num_attention_heads"]
    return {
        "d": d, "ff": c["intermediate_size"],
        "layers": c["num_hidden_layers"], "heads": heads,
        "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim", d // heads),
        "vocab": c["vocab_size"], "act": _ACTS[c["hidden_act"]],
        "gated": kind["gated"], "norm": kind["norm"],
        "rope_theta": float(c["rope_theta"]),
        "window": int(c.get("sliding_window") or 0),
        "tied": bool(c.get("tie_word_embeddings", True)),
        "eps": float(a["norm_eps"]),
        "embed_scale": math.sqrt(d) if a["embed_scale_sqrt_d"] else 1.0,
        "param_dtype": a["param_dtype"],
    }


def weight_specs(c) -> dict:
    """Ordered ``name -> (shape, fan_in)``; fan_in None means zeros (norm
    gains, stored as offsets from 1, and norm biases)."""
    s = shape(c)
    d, ff = s["d"], s["ff"]
    q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    norm = ["scale"] + (["bias"] if s["norm"] == "layernorm" else [])
    specs = {"embed": ((s["vocab"], d), d)}
    if not s["tied"]:
        specs["head"] = ((d, s["vocab"]), d)
    for i in range(s["layers"]):
        p = f"layers.{i}."
        specs.update({p + "norm1." + n: ((d,), None) for n in norm})
        specs.update({p + "attn.wq": ((d, q), d), p + "attn.wk": ((d, kv), d),
                      p + "attn.wv": ((d, kv), d), p + "attn.wo": ((q, d), q)})
        specs.update({p + "norm2." + n: ((d,), None) for n in norm})
        specs[p + "mlp.w_in"] = ((d, ff), d)
        if s["gated"]:
            specs[p + "mlp.w_gate"] = ((d, ff), d)
        specs[p + "mlp.w_out"] = ((ff, d), ff)
    specs.update({"final_norm." + n: ((d,), None) for n in norm})
    return specs


def width(c) -> int:
    """P: every parameter, as one worker's row."""
    return sum(math.prod(shp) for shp, _ in weight_specs(c).values())


def seed_key(seed: int):
    """A PRNG key from any whole seed, also one past 32 bits."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


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

def _norm(s, w, name, x):
    if s["norm"] == "layernorm":
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + s["eps"])
    x = x * (1.0 + w[name + ".scale"])
    if s["norm"] == "layernorm":
        x = x + w[name + ".bias"]
    return x


def _rope(s, x, pos):
    half = s["head_dim"] // 2
    inv = 1.0 / (s["rope_theta"] ** (np.arange(half) / half))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(s, x):
    if s["act"] == "silu":
        return x * jax.nn.sigmoid(x)
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def stack(c, w):
    """Named weights as ``{"top": {...}, "layers": {part: (L, ...)}}``:
    each per-layer weight stacked over the layers, so that the loss scans
    one block instead of unrolling all of them."""
    n = shape(c)["layers"]
    parts = sorted({k.split(".", 2)[2] for k in w if k.startswith("layers.")})
    return {"top": {k: v for k, v in w.items() if not k.startswith("layers.")},
            "layers": {q: jnp.stack([w[f"layers.{i}.{q}"] for i in range(n)])
                       for q in parts}}


def unstack(tree) -> dict:
    """Inverse of :func:`stack` for trees of per-leaf values (norms)."""
    out = dict(tree["top"])
    for q, v in tree["layers"].items():
        out.update({f"layers.{i}.{q}": x for i, x in enumerate(v)})
    return out


def loss(c, w, tokens):
    """Mean next-token cross-entropy of ``tokens`` (B, S) under stacked
    float32 weights ``w`` (:func:`stack`), the last position having no
    target."""
    s = shape(c)
    b, n = tokens.shape
    h, g, hd = s["heads"], s["heads"] // s["kv_heads"], s["head_dim"]
    mm = partial(jnp.matmul, precision=HI)
    top = w["top"]
    pos = jnp.arange(n)
    allow = pos[None, :] <= pos[:, None]
    if s["window"]:
        allow &= pos[None, :] > pos[:, None] - s["window"]

    def block(x, lw):
        y = _norm(s, lw, "norm1", x)
        q = mm(y, lw["attn.wq"]).reshape(b, n, h, hd)
        k = mm(y, lw["attn.wk"]).reshape(b, n, h // g, hd)
        v = mm(y, lw["attn.wv"]).reshape(b, n, h // g, hd)
        q, k = _rope(s, q, pos), _rope(s, k, pos)
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(allow, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HI)
        x = x + mm(o.reshape(b, n, h * hd), lw["attn.wo"])
        y = _norm(s, lw, "norm2", x)
        u = mm(y, lw["mlp.w_in"])
        u = _act(s, mm(y, lw["mlp.w_gate"])) * u if s["gated"] else _act(s, u)
        return x + mm(u, lw["mlp.w_out"]), None

    x, _ = jax.lax.scan(block, top["embed"][tokens] * s["embed_scale"],
                        w["layers"])
    x = _norm(s, top, "final_norm", x)
    logits = mm(x, top["embed"].T if s["tied"] else top["head"])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return jnp.mean(nll)


# --------------------------------------------------------------------------
# local SGD over the first phase
# --------------------------------------------------------------------------

def round_to(x, dtype):
    """Round float32 ``x`` to ``dtype``'s precision, kept in float32
    (``reduce_precision``: an explicit rounding the compiler keeps)."""
    fi = jnp.finfo(dtype)
    if fi.bits == 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


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


@jax.jit
def _stats(xs):
    """Worker mean and sum of squared deviations of one array's copies."""
    x = jnp.stack([a.astype(jnp.float32) for a in xs])
    mean = jnp.mean(x, axis=0)
    return mean, jnp.sum(jnp.square(x - mean[None]))


@partial(jax.jit, static_argnums=1)
def _cast(x, dtype):
    return jax.tree.map(
        lambda v: round_to(v.astype(jnp.float32), jnp.dtype(dtype)).astype(
            dtype), x)


@jax.jit
def _norms(theta, theta0, vel):
    """Per-leaf norms (per layer for stacked arrays) of the change from
    theta0 and of the velocity."""
    def norm(x, stacked):
        axes = tuple(range(1 if stacked else 0, x.ndim))
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))

    def both(f):
        return {"top": {k: f(k, "top", False) for k in theta["top"]},
                "layers": {k: f(k, "layers", True) for k in theta["layers"]}}
    ch = both(lambda k, g, st: norm(theta[g][k].astype(jnp.float32)
                                    - theta0[g][k].astype(jnp.float32), st))
    return ch, both(lambda k, g, st: norm(vel[g][k], st))


class _Frozen(dict):
    """A hashable view of the configuration, for jit's static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))


def _arrays(tree):
    return [(g, k) for g in ("top", "layers") for k in tree[g]]


def train_phase(c, traffic, seed, blocks, *, param_dtype=None,
                vel_dtype="float32", half_batch=False, groups=1,
                devices=None):
    """The reference's first phase of the cell: ``blocks`` is the
    (K, M, B, S) token block the program consumed. Workers sit on
    ``devices`` (worker w on ``devices[w * len(devices) // M]``).
    ``param_dtype`` overrides the stored precision (the control) and
    ``vel_dtype`` the momentum's (float32, as the configuration states);
    ``half_batch`` takes each worker's loss over half its rows, and
    ``groups`` > 1 averages within that many contiguous worker groups
    only (two planted faults). Returns per-step ``loss`` and
    ``dispersion`` lists and per-worker, per-leaf ``change`` and
    ``velocity`` norm dicts."""
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
    dt0 = jnp.dtype(shape(c)["param_dtype"])
    theta0 = _cast(jax.jit(lambda key: stack(c, init_weights(c, key, dt0)))(
        seed_key(seed)), pdt)
    theta = [jax.device_put(theta0, d, may_alias=False) for d in dev]
    vel = [jax.device_put(jax.tree.map(
        lambda v: jnp.zeros(v.shape, jnp.float32), theta0), d) for d in dev]
    out = {"loss": [], "dispersion": []}
    b = blocks.shape[2]
    size = m // groups
    for t in range(1, k_steps + 1):
        ls = []
        for w in range(m):
            tok = blocks[t - 1, w, : b // 2] if half_batch else blocks[t - 1, w]
            theta[w], vel[w], l = _worker_step(
                cf, pdt, vel_dtype, theta[w], vel[w],
                jax.device_put(tok, dev[w]), lr, mu)
            ls.append(l)
        out["loss"].append(float(np.mean([float(v) for v in ls])))
        disp = 0.0
        for g, k in _arrays(theta0):
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
    norms = [jax.device_get(_norms(theta[w], jax.device_put(theta0, dev[w]),
                                   vel[w])) for w in range(m)]
    out["change"] = [{k: float(v) for k, v in unstack(ch).items()}
                     for ch, _ in norms]
    out["velocity"] = [{k: float(v) for k, v in unstack(vn).items()}
                       for _, vn in norms]
    return out
