"""avg_event_ms: device self time per step of the leaf ops under the
``engine.average`` scope: the averaging event and its switch (one
event every K steps, spread over the K; ``bench.spans``).
Layer: averaging event."""
from bench import spans


def read(ctx):
    return spans.scoped_ms(ctx, "avg_event_ms", ("engine.average",))
