"""GQA attention: training/prefill (full or sliding-window causal),
single-token decode against a KV cache, and cross-attention.

Interchangeable compute paths for full-sequence self-attention:
  - "xla":    plain jnp einsums (used for dry-run/cost-analysis & CPU)
  - "pallas": repro.kernels flash attention (forward only; TPU target,
              interpret on CPU)
  - "splash": repro.kernels.window_attention, causal (windowed) attention
              with a backward pass that never writes the (S, S) scores to
              HBM: the training path at long sequences. Shapes it does
              not take fall back to "xla", its twin.

The attention core (scores, softmax, weighted sum) of every path runs
under the ``model.attention`` named scope.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.models.layers import (apply_rope, cdtype, dense_init,
                                 init_biases, linear, rope_freqs)


def init_attn(cfg: ModelConfig, key, cross: bool = False):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, cfg.q_dim), 0, cdtype(cfg)),
        "wk": dense_init(ks[1], (d, cfg.kv_dim), 0, cdtype(cfg)),
        "wv": dense_init(ks[2], (d, cfg.kv_dim), 0, cdtype(cfg)),
        "wo": dense_init(ks[3], (cfg.q_dim, d), 0, cdtype(cfg)),
        **init_biases(cfg, {"bq": cfg.q_dim, "bk": cfg.kv_dim,
                            "bv": cfg.kv_dim, "bo": d}),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _sdpa_xla(q, k, v, mask, scale, score_dtype=jnp.float32):
    """q: (B,Sq,H,hd)  k/v: (B,Sk,Hkv,hd)  mask: broadcastable (B,1,Sq,Sk).

    score_dtype: dtype of the materialized (Sq,Sk) score/prob traffic —
    the softmax statistics themselves are always fp32."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(score_dtype)
    scores = scores * jnp.asarray(scale, score_dtype)
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask,
                       scores, jnp.asarray(-1e30, score_dtype))
    m = jnp.max(scores.astype(jnp.float32), axis=-1, keepdims=True)
    p = jnp.exp(scores.astype(jnp.float32) - m).astype(score_dtype)
    denom = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
    p = (p.astype(jnp.float32) / jnp.maximum(denom, 1e-30)).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return out.reshape(b, sq, h, hd)


def _banded_attention(cfg, q, k, v, *, window, scale, score_dtype,
                      pos_offset=0):
    """Sliding-window attention computed band-wise: each q chunk of size
    c = window attends to a static k slice of 2c keys — score traffic is
    O(S·2w) instead of O(S²) (FLOPs likewise). Chunks are a static
    (unrolled) python loop so XLA cost analysis sees true FLOPs."""
    b, s, h, hd = q.shape
    c = min(window, s)
    s_pad = -(-s // c) * c
    qp = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    # pad keys by one chunk on the left so slice [i*c, i*c+2c) is static
    kp = jnp.pad(k, ((0, 0), (c, s_pad - s), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (c, s_pad - s), (0, 0), (0, 0)))
    outs = []
    for i in range(s_pad // c):
        qi = qp[:, i * c:(i + 1) * c]
        ki = kp[:, i * c:i * c + 2 * c]
        vi = vp[:, i * c:i * c + 2 * c]
        qpos = i * c + jnp.arange(c)[:, None]            # absolute q pos
        kpos = (i - 1) * c + jnp.arange(2 * c)[None, :]  # absolute k pos
        msk = (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0) & \
              (qpos < s)
        outs.append(_sdpa_xla(qi, ki, vi, msk[None, None], scale,
                              score_dtype))
    return jnp.concatenate(outs, axis=1)[:, :s]


def _core(cfg, q, k, v, *, impl, causal, window, scale, same, pos_offset):
    """Scores, softmax and weighted sum of one of the compute paths;
    ``same``: self-attention with as many keys as queries."""
    score_dt = jnp.dtype(cfg.score_dtype)
    sq, sk = q.shape[1], k.shape[1]
    if impl == "pallas" and same:
        from repro.kernels import ops
        return ops.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale)
    if impl == "splash" and same and causal and pos_offset == 0:
        from repro.kernels import window_attention as wa
        if wa.usable(sq, cfg.head_dim):
            return wa.window_attention(q, k, v, window=window, scale=scale)
    if (cfg.attn_banded and window > 0 and causal and same
            and pos_offset == 0):
        return _banded_attention(cfg, q, k, v, window=window, scale=scale,
                                 score_dtype=score_dt)
    mask = make_mask(sq, sk, causal=causal, window=window,
                     q_offset=pos_offset)[None, None]
    return _sdpa_xla(q, k, v, mask, scale, score_dt)


def make_mask(sq: int, sk: int, *, causal: bool, window: int = 0,
              q_offset: int = 0):
    """Boolean mask (sq, sk), True = attend. q position i maps to absolute
    position q_offset + i; k position j is absolute j."""
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    m = jnp.ones((sq, sk), bool)
    if causal:
        m &= kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m


def attention(cfg: ModelConfig, p, x, *, layer, kv_x=None, impl="xla",
              pos_offset=0, return_kv=False):
    """Full-sequence attention (training / prefill).

    kv_x: source for k/v (cross-attention memory); None => self-attention.
    Returns (B, S, d_model), or (out, (k, v)) with post-RoPE k/v when
    ``return_kv`` (prefill cache capture).
    """
    b, sq, _ = x.shape
    src = x if kv_x is None else kv_x
    sk = src.shape[1]
    q = _split_heads(linear(p, "wq", x), cfg.num_heads, cfg.head_dim)
    k = _split_heads(linear(p, "wk", src), cfg.num_kv_heads, cfg.head_dim)
    v = _split_heads(linear(p, "wv", src), cfg.num_kv_heads, cfg.head_dim)

    self_attn = kv_x is None
    if self_attn and cfg.pos_emb == "rope":
        cos_q, sin_q = rope_freqs(cfg, pos_offset + jnp.arange(sq))
        q = apply_rope(q, cos_q, sin_q)
        cos_k, sin_k = rope_freqs(cfg, jnp.arange(sk))
        k = apply_rope(k, cos_k, sin_k)

    causal = layer.causal and self_attn
    window = cfg.sliding_window if (layer.mixer == "attn_local" and self_attn) else 0
    scale = 1.0 / np.sqrt(cfg.head_dim)

    with jax.named_scope("model.attention"):
        out = _core(cfg, q, k, v, impl=impl, causal=causal, window=window,
                    scale=scale, same=self_attn and sq == sk,
                    pos_offset=pos_offset)
    out = linear(p, "wo", out.reshape(b, sq, cfg.q_dim))
    if return_kv:
        return out, (k, v)
    return out


# --------------------------------------------------------------------------
# Decode path (single token, KV cache)
# --------------------------------------------------------------------------

def init_attn_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    shape = (batch, seq_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attention(cfg: ModelConfig, p, x, cache, pos, *, layer):
    """x: (B, 1, d). cache: {"k","v"} (B, S, Hkv, hd). pos: scalar int32 —
    index at which the new token is written; attends to [0, pos].

    Sliding-window layers attend only to the last ``window`` positions via
    a static-size dynamic slice (O(window) instead of O(S))."""
    b = x.shape[0]
    s_cache = cache["k"].shape[1]
    q = _split_heads(linear(p, "wq", x), cfg.num_heads, cfg.head_dim)
    k = _split_heads(linear(p, "wk", x), cfg.num_kv_heads, cfg.head_dim)
    v = _split_heads(linear(p, "wv", x), cfg.num_kv_heads, cfg.head_dim)

    if cfg.pos_emb == "rope":
        cos, sin = rope_freqs(cfg, pos[None] if pos.ndim == 0 else pos)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), pos, 1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), pos, 1)
    new_cache = {"k": ck, "v": cv}

    window = cfg.sliding_window if layer.mixer == "attn_local" else 0
    scale = 1.0 / np.sqrt(cfg.head_dim)
    if window and window < s_cache:
        start = jnp.clip(pos - window + 1, 0, s_cache - window)
        ks = jax.lax.dynamic_slice_in_dim(ck, start, window, 1)
        vs = jax.lax.dynamic_slice_in_dim(cv, start, window, 1)
        kpos = start + jnp.arange(window)
    else:
        ks, vs = ck, cv
        kpos = jnp.arange(s_cache)
    mask = (kpos <= pos)[None, None, None, :]  # (1,1,1,Sk)
    out = _sdpa_xla(q, ks, vs, mask, scale)
    return linear(p, "wo", out.reshape(b, 1, cfg.q_dim)), new_cache


def decode_cross_attention(cfg: ModelConfig, p, x, cache):
    """Cross-attn at decode time: the memory K/V are precomputed at
    prefill and stored in ``cache`` as {"k","v"}: (B, Sm, Hkv, hd)."""
    b = x.shape[0]
    q = _split_heads(linear(p, "wq", x), cfg.num_heads, cfg.head_dim)
    sm = cache["k"].shape[1]
    mask = jnp.ones((1, 1, 1, sm), bool)
    out = _sdpa_xla(q, cache["k"], cache["v"], mask, 1.0 / np.sqrt(cfg.head_dim))
    return linear(p, "wo", out.reshape(b, 1, cfg.q_dim))


def cross_cache_from_memory(cfg: ModelConfig, p, memory):
    """Precompute cross-attention K/V from encoder/vision memory."""
    k = _split_heads(linear(p, "wk", memory), cfg.num_kv_heads, cfg.head_dim)
    v = _split_heads(linear(p, "wv", memory), cfg.num_kv_heads, cfg.head_dim)
    return {"k": k, "v": v}
