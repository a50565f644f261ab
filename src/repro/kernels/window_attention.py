"""Causal sliding-window GQA attention with a backward pass, for training.

The blockwise kernels are JAX's splash attention
(``jax.experimental.pallas.ops.tpu.splash_attention``): one MQA kernel
per KV head, whose group of query heads shares its keys and values,
vmapped over the KV heads and the batch. The mask is causal, cut to the
``window`` most recent keys (a query at position i sees keys
i - window < j <= i), or causal alone for ``window == 0``. The
forward, dq and dkv passes are three Pallas kernels that keep the
(S, S) scores in VMEM, one (block, block) tile at a time; the mask
info the kernel is built with lists, for each query block, only the key
blocks some query of it sees, so a key block wholly outside the window
is neither loaded nor computed.

In a compiled TPU program the passes are custom calls named
``splash_mqa_fwd_residuals`` (``..._no_residuals`` without a
gradient), ``splash_mqa_dq_no_residuals`` and
``splash_mqa_dkv_no_residuals``: the prefix ``splash_mqa_`` finds them
in a profile. On the CPU they run in Pallas interpret mode. The semantics
are those of ``repro.kernels.ref.flash_attention_ref`` and of
``repro.models.attention._sdpa_xla`` (``tests/test_starcoder2.py``
holds them to it, forward and backward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import splash_attention as splash

BLOCK = 512


def usable(seq: int, head_dim: int, block: int = BLOCK) -> bool:
    """Whether the kernel takes this shape: whole blocks of a multiple
    of 128 rows and a lane-aligned head."""
    b = min(block, seq)
    return b % 128 == 0 and seq % b == 0 and head_dim % 128 == 0


@functools.lru_cache(maxsize=16)
def _kernel(seq: int, group: int, window: int, block: int, interpret: bool):
    """The splash MQA kernel of one shape. Its mask info is made as
    concrete arrays even when the first call comes inside a trace, so
    the cached kernel holds no tracer."""
    left = window - 1 if window > 0 else None
    mask = splash.MultiHeadMask([splash.LocalMask((seq, seq), (left, 0), 0)
                                 for _ in range(group)])
    b = min(block, seq)
    sizes = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa(mask, block_sizes=sizes,
                                      head_shards=1, q_seq_shards=1,
                                      interpret=interpret)


def window_attention(q, k, v, *, window: int, scale: float | None = None,
                     block: int = BLOCK, interpret: bool | None = None):
    """q: (B, S, H, hd), k/v: (B, S, Hkv, hd) -> (B, S, H, hd), causal
    over the last ``window`` keys (0: causal over all). Query head h
    reads KV head h // (H // Hkv), as ``jnp.repeat`` lays them out."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    kern = _kernel(s, g, int(window), block, bool(interpret))
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qs.reshape(b, s, hkv, g, hd).transpose(0, 2, 3, 1, 4)
    kg, vg = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(kern))(qg, kg, vg)        # (B, Hkv, G, S, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
