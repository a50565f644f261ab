"""Operations and bytes the algorithm needs, counted from shapes.

These counts are the benchmark's own: they come from the configuration
file (through ``bench.ref.<model>.shape``), never from the compiler's
cost analysis, so a change in how the program implements a step cannot
change them.
"""
from __future__ import annotations


def matmul_params(s) -> int:
    """Weights that take part in a matrix multiplication per token: the
    attention and MLP projections of every layer and the output head
    (the tied embedding counts once, as the head; a lookup is no
    matmul)."""
    d, q, kv = s["d"], s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    mlp = (3 if s["gated"] else 2) * d * s["ff"]
    head = s["vocab"] * d
    return s["layers"] * (d * (q + 2 * kv) + q * d + mlp) + head


def mean_context(seq: int, window: int) -> float:
    """Mean number of keys a causal query attends to over one sequence."""
    w = window or seq
    return sum(min(i, w) for i in range(1, seq + 1)) / seq


def train_flops_per_token(s, seq: int) -> float:
    """Forward and backward FLOPs per token: 6 per matmul weight, plus
    the causal attention scores and weighted sums (2 matmuls of the
    query width against the mean context, times 3 for fwd+bwd).
    Recomputation is not counted."""
    q = s["heads"] * s["head_dim"]
    attn = 6 * 2 * q * mean_context(seq, s["window"])
    return 6.0 * matmul_params(s) + s["layers"] * attn


def update_bytes(workers: int, width: int, state_planes: int) -> int:
    """HBM bytes of one local update over the f32 planes: the param plane
    and each optimizer-state plane read once and written once. The
    gradient plane is not counted: a fused update may never write it."""
    return 2 * (1 + state_planes) * workers * width * 4
