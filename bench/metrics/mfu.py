"""mfu: the whole step's share of the chips' bf16 peak over the traced
window: forward and backward FLOPs per token (``bench.flops``) times
the tokens the traced phases completed, over the traced seconds and
chips x peak. Layer: the whole step (``PhaseEngine.run_phase``)."""
from bench import flops


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    f = flops.train_flops_per_token(ctx.shape, ctx.traffic["seq"])
    done = f * ctx.tokens_per_step * t.steps / t.window_s
    return 100.0 * done / (ctx.chips * ctx.peak["bf16_flops_per_s"])
