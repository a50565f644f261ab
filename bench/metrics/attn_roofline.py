"""attn_roofline: the windowed attention kernel's share of the bf16
peak. The work is :func:`attn_flops_per_step`, the attention term that
``bench.flops`` counts for the whole step (scores and weighted sums,
forward and backward, over each layer group's mean causal context;
recomputation is not counted), for one device's share of the tokens,
times the traced steps, over the kernel's device time (``attn_ms``) and
the peak. None where the trace holds no call of the kernel. Layer:
windowed attention (``models/attention.py``)."""
import importlib.util
import os

from bench import flops

_spec = importlib.util.spec_from_file_location(
    "bench_metric_attn_ms", os.path.join(os.path.dirname(__file__),
                                         "attn_ms.py"))
attn_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(attn_ms)


def attn_flops_per_step(shape, traffic) -> float:
    """Tokens per step x :func:`bench.flops.attn_flops_per_token`."""
    tokens = traffic["workers"] * traffic["batch"] * traffic["seq"]
    return tokens * flops.attn_flops_per_token(shape, traffic["seq"])


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    ns = attn_ms.device_ns(t)
    if not ns:
        return None
    work = attn_flops_per_step(ctx.shape, ctx.traffic) / ctx.chips * t.steps
    shares = [work / (x / 1e9) / ctx.peak["bf16_flops_per_s"] for x in ns]
    return 100.0 * sum(shares) / len(shares)
