"""update_roofline: the fused update kernel's share of its HBM roofline.
The bytes it needs (``bench.flops.update_bytes``: the param and
momentum planes, this device's rows x P f32, read and written once per
step) over the HBM peak, against the device time of the kernel's
events. The kernel is the Mosaic custom call on this device's
(rows, P) plane; its ``pallas_call`` has no name yet.
Layer: fused update (``kernels/opt_step.py``)."""
from bench import flops
from bench import trace as tr


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    rows = ctx.traffic["workers"] // ctx.chips
    plane = f"f32[{rows},{ctx.width}]"

    def is_update(ev):
        return tr.op(ev)[0] == "tpu_custom_call" and plane in ev[0]
    need = t.steps * flops.update_bytes(rows, ctx.width, 1)
    shares = []
    for evs in t.events.values():
        ns = tr.op_ns(evs, is_update, t.t0, t.t1)
        if ns:
            shares.append(need / ctx.peak["hbm_bytes_per_s"] / (ns / 1e9))
    return 100.0 * sum(shares) / len(shares) if shares else None
