"""fwd_bwd_ms: device self time per step of the leaf ops under the
``engine.fwd_bwd`` scope: the workers' forward and backward passes
(``value_and_grad`` of the loss, vmapped over the worker rows;
``bench.spans``). Layer: local step."""
from bench import spans


def read(ctx):
    return spans.scoped_ms(ctx, "fwd_bwd_ms", ("engine.fwd_bwd",))
