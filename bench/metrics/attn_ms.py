"""attn_ms: device time per step of the windowed attention kernel, its
forward and backward calls together (the forward runs twice under
remat). The calls are the Mosaic custom calls whose HLO instruction is
named after the kernel (``repro.kernels.window_attention``: names that
start with ``splash_mqa_``); the mean over the cell's devices of their
time inside the traced window, over the traced steps. None where the
trace holds no such call. Layer: windowed attention
(``models/attention.py``)."""
from bench import trace as tr

PREFIX = "splash_mqa_"


def is_attn(ev) -> bool:
    m = tr._HEAD.match(ev[0])
    return bool(m) and m.group(1).startswith(PREFIX)


def device_ns(t) -> list:
    """Per device, the kernel's time inside the traced window (ns);
    devices that ran none are left out."""
    out = []
    for evs in t.events.values():
        ns = tr.op_ns(evs, is_attn, t.t0, t.t1)
        if ns:
            out.append(ns)
    return out


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    ns = device_ns(t)
    if not ns:
        return None
    return sum(ns) / len(ns) / t.steps / 1e6
