"""RG-LRU linear recurrence Pallas TPU kernel:  h_t = a_t h_{t-1} + b_t.

Grid (batch, width_blocks, seq_blocks), seq innermost; the recurrent
state (one (block_w,) fp32 vector) lives in VMEM scratch and persists
across the sequence blocks. Within a block the recurrence is stepped
sequentially over rows with full-width VPU vector ops — the idiomatic
TPU shape for elementwise RNNs (channels on lanes, time sequential),
cf. RecurrentGemma's reference scan kernel.

Channel blocks of 512 lanes x fp32 keep (a, b, h, out) well under VMEM
while giving the VPU full 8x128 tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_W = 512
DEFAULT_BLOCK_S = 256


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, block_s):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]                      # (block_s, block_w) fp32
    b = b_ref[0]

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, t, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("block_w", "block_s",
                                             "interpret"))
def rglru_scan(a, b, *, block_w: int = DEFAULT_BLOCK_W,
               block_s: int = DEFAULT_BLOCK_S,
               interpret: bool | None = None):
    """a, b: (B, S, W) (any float dtype; computed in fp32) -> (B, S, W)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bsz, s, w = a.shape
    block_w = min(block_w, w)
    block_s = min(block_s, s)
    assert w % block_w == 0, (w, block_w)
    s_pad = -(-s // block_s) * block_s
    if s_pad != s:
        # pad with identity steps (a=1, b=0) — they do not disturb state
        a = jnp.pad(a, ((0, 0), (0, s_pad - s), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, s_pad - s), (0, 0)))

    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    grid = (bsz, w // block_w, s_pad // block_s)
    out = pl.pallas_call(
        functools.partial(_rglru_kernel, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_w),
                         lambda bb, wi, si: (bb, si, wi)),
            pl.BlockSpec((1, block_s, block_w),
                         lambda bb, wi, si: (bb, si, wi)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w),
                               lambda bb, wi, si: (bb, si, wi)),
        out_shape=jax.ShapeDtypeStruct((bsz, s_pad, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        interpret=interpret,
        name="rglru_scan",
    )(af, bf)
    return out[:, :s].astype(a.dtype)
