"""host_bubble_ms: the host's share of each gap between two phases,
from the end of phase n's ``engine.fetch`` span to the end of phase
n+1's ``engine.dispatch`` span (``PhaseEngine.run``'s host loop), in
ms, the mean over the phase boundaries of the traced window
(``bench.spans``). Layer: host loop."""
import sys

from bench import spans


def read(ctx):
    t, sp = ctx.trace, spans.for_cell(ctx)
    if t is None or sp is None:
        return None
    b = spans.bubbles_ns(sp["spans"], t.t0, t.t1)
    if not b:
        return None
    print(f"[bench] host_bubble_ms over {len(b)} phase boundaries: "
          f"{[x / 1e6 for x in b]!r}", file=sys.stderr)
    return sum(b) / len(b) / 1e6
