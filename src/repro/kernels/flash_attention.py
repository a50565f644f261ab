"""Flash attention Pallas TPU kernel (causal / sliding-window / GQA).

Online-softmax blocked attention: grid (batch, q_head, q_blocks,
k_blocks) with the k dimension innermost; running (max, sum, acc) live in
VMEM scratch and persist across the innermost grid steps. Block shapes
are MXU-aligned (q/k blocks of 128 rows, full head_dim lanes).

HBM->VMEM traffic per (q_block, k_block): q once per k sweep (cached by
the pipeline), k/v streamed — the S×S score matrix never exists in HBM,
which is precisely what removes the memory-roofline term the XLA path
pays (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale, causal, window, block_q, block_k, seq_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)           # (bk, hd)
    s = jnp.dot(q, k.T) * scale                   # (bq, bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    mask &= kpos < seq_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                           # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(p, v)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows -> 0
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None):
    """q: (B,S,H,hd), k/v: (B,S,Hkv,hd) -> (B,S,H,hd)."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    # pad sequence to block multiples (masked out via kpos < seq_len)
    s_pad = -(-s // max(block_q, block_k)) * max(block_q, block_k)
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    # (B,H,S,hd) layout for clean blocking
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    grid = (b, h, s_pad // block_q, s_pad // block_k)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_len=s)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bb, hh, qi, ki, g=g: (bb, hh // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bb, hh, qi, ki, g=g: (bb, hh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),        # running max
            pltpu.VMEM((block_q,), jnp.float32),        # running sum
            pltpu.VMEM((block_q, hd), jnp.float32),     # accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)[:, :s]
