"""RWKV6 (Finch) WKV recurrence Pallas TPU kernel.

Per (batch, head) with head_dim n and data-dependent per-channel decay:
  y_t = r_t · (S_{t-1} + (u ∘ k_t) v_tᵀ);   S_t = diag(w_t) S_{t-1} + k_t v_tᵀ

Grid (batch, heads, seq_blocks), seq innermost; the (n, n) fp32 state
matrix persists in VMEM scratch across sequence blocks. Each time step
is one rank-1 update + one vector-matrix product — n=64 keeps the state
a single (64, 64) VMEM tile; the v-products hit the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 128


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                block_s):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0]                   # (block_s, n) fp32
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    w = w_ref[0, 0]                   # decays, already exp()'d
    u = u_ref[0]                      # (n,)

    def step(t, S):
        kv = k[t][:, None] * v[t][None, :]            # (n, n) rank-1
        y = (r[t][None, :] @ (S + u[:, None] * kv))[0]
        o_ref[0, 0, t, :] = y
        return w[t][:, None] * S + kv

    s_ref[...] = jax.lax.fori_loop(0, block_s, step, s_ref[...])


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def rwkv6_scan(r, k, v, log_w, u, *, block_s: int = DEFAULT_BLOCK_S,
               interpret: bool | None = None):
    """r,k,v,log_w: (B,S,H,n); u: (H*n,) or (H,n). Returns (B,S,H,n) fp32."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bsz, s, h, n = r.shape
    block_s = min(block_s, s)
    s_pad = -(-s // block_s) * block_s
    u2 = jnp.asarray(u, jnp.float32).reshape(h, n)

    def prep(t, fill=0.0):
        t = t.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B,H,S,n)
        if s_pad != s:
            t = jnp.pad(t, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)),
                        constant_values=fill)
        return t

    rf, kf, vf = prep(r), prep(k), prep(v)
    wf = jnp.exp(prep(log_w, fill=0.0))  # pad decay=1 -> identity steps

    grid = (bsz, h, s_pad // block_s)
    blk = pl.BlockSpec((1, 1, block_s, n), lambda bb, hh, si: (bb, hh, si, 0))
    out = pl.pallas_call(
        functools.partial(_wkv_kernel, block_s=block_s),
        grid=grid,
        in_specs=[blk, blk, blk, blk,
                  pl.BlockSpec((1, n), lambda bb, hh, si: (hh, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((bsz, h, s_pad, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
        name="rwkv6_scan",
    )(rf, kf, vf, wf, u2)
    return out[:, :, :s].transpose(0, 2, 1, 3)
