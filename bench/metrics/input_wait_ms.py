"""input_wait_ms: per phase, how long the driving thread waited for its
next staged block (the ``engine.next_block`` span around the
``Prefetcher``), in ms, the mean over the phases of the traced window
(``bench.spans``). Layer: input staging."""
import sys

from bench import spans


def read(ctx):
    t, sp = ctx.trace, spans.for_cell(ctx)
    if t is None or sp is None:
        return None
    w = spans.waits_ns(sp["spans"], t.t0, t.t1)
    if not w:
        return None
    print(f"[bench] input_wait_ms over {len(w)} phases: "
          f"{[x / 1e6 for x in w]!r}", file=sys.stderr)
    return sum(w) / len(w) / 1e6
