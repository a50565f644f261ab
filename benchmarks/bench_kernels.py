"""Kernel micro-benchmarks: Pallas (interpret on CPU / Mosaic on TPU)
vs the XLA reference path, per shape. On this CPU container the timing
column is indicative only; the derived column reports max|err| vs the
oracle, which is the portable claim."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.ref import (flash_attention_ref, rglru_scan_ref,
                               rwkv6_scan_ref)
from repro.launch.cache import enable_compile_cache


def run():
    key = jax.random.PRNGKey(0)
    # flash attention
    for (b, s, h, hkv, hd) in [(1, 512, 8, 2, 64), (1, 1024, 4, 1, 128)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, hkv, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, hkv, hd), jnp.float32)
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        us, out = timeit(lambda: jax.block_until_ready(f(q, k, v)), reps=2)
        ref = flash_attention_ref(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(out - ref)))
        emit(f"kernel_flash_attn_b{b}_s{s}_h{h}kv{hkv}_d{hd}", us,
             f"maxerr_vs_oracle={err:.1e}")
    # rglru
    a = jax.random.uniform(key, (1, 1024, 1024), minval=0.5, maxval=0.999)
    bb = jax.random.normal(key, (1, 1024, 1024)) * 0.1
    us, out = timeit(lambda: jax.block_until_ready(rglru_scan(a, bb)), reps=2)
    err = float(jnp.max(jnp.abs(out - rglru_scan_ref(a, bb))))
    emit("kernel_rglru_scan_s1024_w1024", us, f"maxerr_vs_oracle={err:.1e}")
    # rwkv6
    ks = jax.random.split(key, 5)
    r, k2, v2 = (jax.random.normal(ks[i], (1, 256, 4, 64)) * 0.5
                 for i in range(3))
    lw = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (1, 256, 4, 64))),
                  -5.0, -1e-5)
    u = jax.random.normal(ks[4], (256,)) * 0.1
    us, out = timeit(lambda: jax.block_until_ready(
        rwkv6_scan(r, k2, v2, lw, u)), reps=2)
    err = float(jnp.max(jnp.abs(out - rwkv6_scan_ref(r, k2, v2, lw, u))))
    emit("kernel_rwkv6_scan_s256_h4_n64", us, f"maxerr_vs_oracle={err:.1e}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
