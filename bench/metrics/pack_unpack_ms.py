"""pack_unpack_ms: device self time per step of the leaf ops the phase
program runs under the ``engine.unpack`` and ``engine.pack`` scopes:
``FlatSpec.unpack1``'s slices and casts of each worker row, and
``pack1``'s assembly of the gradient plane (``bench.spans``).
Layer: plane plumbing (``make_plane_step``)."""
from bench import spans


def read(ctx):
    return spans.scoped_ms(ctx, "pack_unpack_ms",
                           ("engine.unpack", "engine.pack"))
