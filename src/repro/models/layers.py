"""Shared building blocks: norms, activations, MLPs, embeddings, RoPE."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (what llama/gemma use in practice)."""
    fan_in = shape[in_axis] if in_axis is not None else 1
    std = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, d: int | None = None):
    d = d or cfg.d_model
    p = {"scale": jnp.zeros((d,), cdtype(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), cdtype(cfg))
    return p


def apply_norm(cfg: ModelConfig, p, x):
    """RMSNorm / LayerNorm with fp32 statistics, (1+scale) gemma-style."""
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + cfg.norm_eps)
    out = xf * (1.0 + p["scale"].astype(jnp.float32))
    if cfg.norm == "layernorm":
        out = out + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Activations / MLP
# --------------------------------------------------------------------------

def activate(cfg: ModelConfig, x):
    if cfg.act == "silu":
        return jax.nn.silu(x)
    if cfg.act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if cfg.act == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(cfg.act)


def linear(p, w: str, x):
    """``x @ p[w]``, plus the bias ``p["b" + w[1:]]`` (``wq`` -> ``bq``,
    ``w_in`` -> ``b_in``) where the layer has one
    (``ModelConfig.linear_bias``)."""
    y = x @ p[w]
    b = p.get("b" + w[1:])
    return y if b is None else y + b


def init_biases(cfg: ModelConfig, widths: dict) -> dict:
    """Zero biases ``name -> (width,)``, or none where the model has no
    linear biases."""
    if not cfg.linear_bias:
        return {}
    return {n: jnp.zeros((w,), cdtype(cfg)) for n, w in widths.items()}


def init_mlp(cfg: ModelConfig, key, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_in": dense_init(ks[0], (d, f), 0, cdtype(cfg)),
        "w_out": dense_init(ks[1], (f, d), 0, cdtype(cfg)),
    }
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(ks[2], (d, f), 0, cdtype(cfg))
    p.update(init_biases(cfg, {"b_in": f, "b_out": d,
                               **({"b_gate": f} if cfg.gated_mlp else {})}))
    return p


def apply_mlp(cfg: ModelConfig, p, x):
    h = linear(p, "w_in", x)
    if cfg.gated_mlp:
        h = activate(cfg, linear(p, "w_gate", x)) * h
    else:
        h = activate(cfg, h)
    return linear(p, "w_out", h)


# --------------------------------------------------------------------------
# Embedding / unembedding (padded vocab, see ModelConfig.padded_vocab)
# --------------------------------------------------------------------------

def init_embed(cfg: ModelConfig, key):
    ks = jax.random.split(key, 3)
    p = {"tok": dense_init(ks[0], (cfg.padded_vocab, cfg.d_model), 1, cdtype(cfg))}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.padded_vocab), 0, cdtype(cfg))
    if cfg.pos_emb == "learned":
        p["pos"] = dense_init(ks[2], (cfg.max_seq_len, cfg.d_model), 1, cdtype(cfg))
    return p


def embed(cfg: ModelConfig, p, tokens, pos_offset=0):
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.embed_scale and cfg.family != "ssm":  # gemma-style sqrt(d)
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    if cfg.pos_emb == "learned":
        s = tokens.shape[-1]
        idx = pos_offset + jnp.arange(s)
        x = x + jnp.take(p["pos"], idx, axis=0)
    return x


def unembed(cfg: ModelConfig, p, x):
    if cfg.tie_embeddings:
        logits = x @ p["tok"].T
    else:
        logits = x @ p["unembed"]
    logits = logits.astype(jnp.float32)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    # mask padded vocab rows so they can never win a softmax/argmax
    pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
    return jnp.where(pad_mask, -1e9, logits)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(cfg: ModelConfig, positions):
    """positions: (...,) int32 -> cos/sin of shape (..., head_dim//2)."""
    half = cfg.head_dim // 2
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, half) / half))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, hd); cos/sin: (B?, S, hd//2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    # broadcast (..., S, 1, half) over heads
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
