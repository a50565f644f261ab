"""collective_ms: device time per step of every all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute op (and their async
starts and ends), averaged over the cell's devices. Layer: the
collectives of a mesh: the Eq. 4 dispersion's all-to-alls and scalar
psum (``engine.dispersion``, inside ``engine.update``) and the
all-mean event's all-gather (``engine.average``)."""
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
