"""collective_ms: device time per step of every all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute op (and their async
starts and ends), averaged over the cell's devices. Layer: the
averaging collective (``_flat_native_step_psum``, ``_psum_avg_event``)."""
from bench import trace as tr

OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
       "collective-permute")


def _is_collective(ev):
    kind, _, opcode = tr.op(ev)
    return any(x.startswith(k) for x in (kind, opcode) for k in OPS)


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    per = [tr.op_ns(evs, _is_collective, t.t0, t.t1)
           for evs in t.events.values()]
    if not any(per):
        return None
    return sum(per) / len(per) / 1e6 / t.steps
