"""Operations the algorithm needs, counted from shapes.

These counts are the benchmark's own: they come from the configuration
file (through ``bench.ref.<model>.shape``), never from the compiler's
cost analysis, so a change in how the program implements a step cannot
change them.

The count is summed over the model's layers. :func:`layer_work` gives
them as groups of equal layers, ``[(count, layer), ...]``, where a layer
is a dict of

- ``weights``: matmul weights one token uses in the layer: the mixer's
  projections plus the FFN's active weights, a router included;
- ``qk``, ``v``: the query-key width and the value width, summed over
  the heads;
- ``window``: the keys a query sees; 0 is full causal attention.

A model kind whose layers differ (latent attention, sparse experts,
windowed and full layers mixed) puts its groups under ``"layer_work"``
in its ``shape()``, built with :func:`layer` from one mixer
(:func:`attention`, :func:`latent_attention`) and one FFN
(:func:`dense_ffn`, :func:`experts`). Otherwise the dense keys (d, ff,
layers, heads, kv_heads, head_dim, gated, window) give one group of
dense GQA blocks.
"""
from __future__ import annotations


def attention(d, heads, kv_heads, head_dim, window) -> dict:
    """GQA or MHA: q and o at heads x head_dim, k and v at kv_heads x
    head_dim."""
    q, kv = heads * head_dim, kv_heads * head_dim
    return {"weights": d * (q + 2 * kv) + q * d, "qk": q, "v": q,
            "window": window}


def latent_attention(d, heads, q_rank, kv_rank, nope, rope, v_dim,
                     window) -> dict:
    """Multi-head latent attention in its training form (the absorbed
    decode form is not counted): q straight from d, or through a
    ``q_rank`` latent; k and v through a ``kv_rank`` latent, beside the
    shared rope key; o from heads x ``v_dim``."""
    qk = heads * (nope + rope)
    q = d * q_rank + q_rank * qk if q_rank else d * qk
    kv = d * (kv_rank + rope) + kv_rank * heads * (nope + v_dim)
    return {"weights": q + kv + heads * v_dim * d, "qk": qk,
            "v": heads * v_dim, "window": window}


def dense_ffn(d, ff, gated) -> int:
    """Up (and gate) and down projections."""
    return (3 if gated else 2) * d * ff


def experts(d, expert_ff, n_experts, top_k, held, shared_ff,
            gated) -> float:
    """A sparse-expert FFN on a chip that holds ``held`` of its
    ``n_experts``: the router at the published count, the shared experts
    (``shared_ff`` wide in all), and under uniform routing over all the
    experts ``top_k x held / n_experts`` routed experts a token. Uneven
    routing changes the program's work, not this count; capacity padding
    is never counted."""
    mult = 3 if gated else 2
    routed = top_k * held * mult * d * expert_ff / n_experts
    return d * n_experts + mult * d * shared_ff + routed


def layer(mixer: dict, ffn) -> dict:
    """One layer: a mixer and the FFN's weights per token."""
    return dict(mixer, weights=mixer["weights"] + ffn)


def layer_work(s) -> list:
    """The groups of equal layers, ``[(count, layer), ...]``."""
    if "layer_work" in s:
        return s["layer_work"]
    mixer = attention(s["d"], s["heads"], s["kv_heads"], s["head_dim"],
                      s["window"])
    return [(s["layers"], layer(mixer, dense_ffn(s["d"], s["ff"],
                                                 s["gated"])))]


def matmul_params(s):
    """Weights that take part in a matrix multiplication per token: every
    layer's and the output head (the tied embedding counts once, as the
    head; a lookup is no matmul)."""
    return (sum(n * w["weights"] for n, w in layer_work(s))
            + s["vocab"] * s["d"])


def mean_context(seq: int, window: int) -> float:
    """Mean number of keys a causal query attends to over one sequence."""
    w = window or seq
    return sum(min(i, w) for i in range(1, seq + 1)) / seq


def attn_flops_per_token(s, seq: int) -> float:
    """The causal attention's scores and weighted sums per token, forward
    and backward: 2 FLOPs per query-key and per value width against the
    mean context, times 3. Recomputation is not counted."""
    return sum(n * (6 * (w["qk"] + w["v"]) * mean_context(seq, w["window"]))
               for n, w in layer_work(s))


def train_flops_per_token(s, seq: int) -> float:
    """Forward and backward FLOPs per token: 6 per matmul weight, plus
    the attention (:func:`attn_flops_per_token`). Recomputation is not
    counted."""
    return 6.0 * matmul_params(s) + attn_flops_per_token(s, seq)
