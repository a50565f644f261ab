"""Render the dry-run JSONL into the EXPERIMENTS.md roofline tables,
plus the analytic roofline of the fused avg_disp kernel (one averaging
event over the flat (M, P) plane)."""
from __future__ import annotations

import json
import os

from benchmarks.common import RESULTS_DIR
from repro.roofline import HW
from repro.launch.cache import enable_compile_cache

HDR = ("| arch | shape | mesh | avg | variant | flops/dev | bytes/dev | "
       "coll B/dev | compute s | memory s | coll s | bound | "
       "useful-FLOP frac |")
SEP = "|" + "---|" * 13


def fmt_row(r):
    def e(x):
        return f"{x:.2e}" if isinstance(x, (int, float)) else "-"
    if "skipped" in r:
        return (f"| {r.get('arch','?')} | {r.get('shape','?')} | "
                f"{r.get('mesh','-')} | - | - | SKIP | {r['skipped']} "
                f"| | | | | | |")
    return ("| {arch} | {shape} | {mesh} | {avg} | {var} | {f} | {b} | {c} "
            "| {cs} | {ms} | {cls} | **{bn}** | {uf} |").format(
        arch=r["arch"], shape=r["shape"], mesh=r["mesh"],
        avg=r.get("avg", "none"), var=r.get("variant", "baseline"),
        f=e(r.get("flops_per_device")), b=e(r.get("bytes_per_device")),
        c=e(r.get("collective_bytes_per_device")),
        cs=f"{r.get('compute_s', 0):.4f}", ms=f"{r.get('memory_s', 0):.4f}",
        cls=f"{r.get('collective_s', 0):.4f}", bn=r.get("bottleneck", "?"),
        uf=(f"{r['useful_flop_fraction']:.2f}"
            if r.get("useful_flop_fraction") else "-"))


def load(path=None):
    path = path or os.path.join(RESULTS_DIR, "dryrun.jsonl")
    rows, seen = [], set()
    if not os.path.exists(path):
        return rows
    for line in open(path):
        try:
            r = json.loads(line)
        except Exception:
            continue
        key = (r.get("arch"), r.get("shape"), r.get("mesh"),
               r.get("avg", "none"), r.get("variant", "baseline"))
        if key in seen:
            continue
        seen.add(key)
        rows.append(r)
    return rows


def render(rows=None):
    rows = rows if rows is not None else load()
    out = [HDR, SEP]
    order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}
    rows = sorted(rows, key=lambda r: (r.get("arch", ""),
                                       order.get(r.get("shape", ""), 9),
                                       r.get("mesh", ""),
                                       r.get("avg", "none")))
    for r in rows:
        out.append(fmt_row(r))
    return "\n".join(out)


def avg_disp_roofline(m: int, p: int, *, groups: int = 1,
                      outer: bool = False, hw: HW = HW()) -> dict:
    """Bytes / FLOPs of ONE fused averaging event on the (M, P) f32
    plane (repro.kernels.avg_disp), vs the tree path's 3-4 passes.

    Reads: the plane (M·P·4 B) once (+ prev_avg & velocity, 2·P·4 B,
    with the outer optimizer); writes: the broadcast plane (+ new
    avg/velocity). FLOPs: mean (M adds + 1 mul per column, + group
    means), dispersion (sub+mul+add per element), outer step (~5/col).
    The kernel is memory-bound at every realistic (M, P) — one averaging
    event costs two sweeps of the plane, where the tree path pays 3-4.
    """
    elems = m * p
    read_b = 4 * (elems + (2 * p if outer else 0))
    write_b = 4 * (elems + (2 * p if outer else 0))
    mean_f = elems + p + (elems + groups * p if groups > 1 else 0)
    disp_f = 3 * elems + p
    outer_f = 5 * p if outer else 0
    flops = mean_f + disp_f + outer_f
    bytes_total = read_b + write_b
    return {
        "kernel": "avg_disp" + ("_outer" if outer else ""),
        "m": m, "p": p, "groups": groups,
        "flops": flops, "bytes": bytes_total,
        "intensity_flop_per_byte": flops / bytes_total,
        "compute_s": flops / hw.peak_flops,
        "memory_s": bytes_total / hw.hbm_bw,
        "bound": "memory",  # intensity ~0.5 F/B << machine balance
        "tree_path_passes": 4 if outer else 3,
        "fused_passes": 2,
    }


def opt_step_roofline(m: int, p: int, *, kind: str = "momentum",
                      mode: str = "mean", wire: str = "f32",
                      hw: HW = HW()) -> dict:
    """Bytes / FLOPs of ONE fused opt_step pass (repro.kernels.opt_step):
    local optimizer update on the (M, P) plane + S state planes, plus
    the worker mean + Eq. 4 dispersion in EVERY mode (the always-on
    dispersion that drives the adaptive schedules and the per-step
    trace), and the broadcast on averaging steps (mode != "none").

    Reads: param plane + grad plane + S state planes; writes: param
    plane + S state planes (each M·P·4 B). FLOPs per element: sgd 2
    (fma), momentum 4, adamw ~12 (incl. div/sqrt), + ~4 for the
    mean/dispersion reduction (all modes — it rides the same sweep, so
    the always-on measurement adds no memory traffic). The un-fused
    path pays an extra read+write sweep of the plane for the optimizer
    update before the avg_disp pass (3 sweeps on averaging steps;
    tree-path optimizers additionally traverse every leaf).

    mode "mix" is the gossip-topology event (repro.topology): the
    (M, M) @ (M, P) mixing contraction adds 2M FLOPs per plane element
    and one M·M·4 B read of W — negligible traffic against the plane
    sweep (M is 4–64), so the mix stays memory-bound on the SAME
    single pass: the topology axis is free in bytes, paid only in
    (cheap) MXU flops.

    ``wire`` prices the event's BYTES ON THE WIRE (what a multi-host
    deployment ships between chips — M encoded rows per event,
    ``repro.core.compress.wire_row_bytes``) at that format. The
    compressed event's encode/decode/error-feedback adds ~6 FLOPs +
    one extra residual read+write sweep per element, but the wire
    payload shrinks by WIRE_BITS/32 — int8 moves ~4x fewer bytes over
    the links for an extra memory-bound plane sweep, which is exactly
    the trade a collective-bound step wants."""
    from repro.core.compress import wire_row_bytes
    s = {"sgd": 0, "momentum": 1, "adamw": 2}[kind]
    upd_f = {"sgd": 2, "momentum": 4, "adamw": 12}[kind]
    mix = mode == "mix"
    comp = wire != "f32"
    elems = m * p
    # compressed events read + write the (M, P) error-feedback residual
    # plane alongside the param plane
    read_b = 4 * (elems * (2 + s + (1 if comp else 0))
                  + (m * m if mix else 0))
    write_b = 4 * elems * (1 + s + (1 if comp else 0))
    # encode (scale + round) + decode + residual update: ~6 flops/elem
    flops = (upd_f * elems + 4 * elems + 2 * p
             + (2 * m * elems if mix else 0)
             + (6 * elems if comp else 0))
    bytes_total = read_b + write_b
    wire_b = m * wire_row_bytes(p, wire)
    return {
        "kernel": f"opt_step[{kind},{mode}"
                  + (f",{wire}]" if comp else "]"),
        "m": m, "p": p, "state_planes": s, "wire": wire,
        "flops": flops, "bytes": bytes_total,
        "intensity_flop_per_byte": flops / bytes_total,
        "compute_s": flops / hw.peak_flops,
        "memory_s": bytes_total / hw.hbm_bw,
        "wire_bytes_per_event": wire_b,
        "wire_reduction_vs_f32": (m * wire_row_bytes(p, "f32")) / wire_b,
        "bound": "memory",  # intensity << machine balance even at M=64
        "unfused_passes": 3 if mode != "none" else 2,
        "fused_passes": 1,
    }


AVG_DISP_HDR = ("| kernel | M | P | groups | FLOPs | bytes | F/B | "
                "memory s | passes (tree -> fused) |")
AVG_DISP_SEP = "|" + "---|" * 9

OPT_STEP_HDR = ("| kernel | M | P | S | FLOPs | bytes | F/B | memory s | "
                "wire B/event | wire vs f32 | passes (unfused -> fused) |")
OPT_STEP_SEP = "|" + "---|" * 11


def render_opt_step(cases=(("sgd", "none"), ("momentum", "none"),
                           ("momentum", "mean"), ("momentum", "mix"),
                           ("momentum", "mean", "int8"),
                           ("momentum", "mix", "int8"),
                           ("momentum", "mix", "one_bit"),
                           ("adamw", "mean")),
                    m: int = 16, p: int = 1 << 20) -> str:
    out = [OPT_STEP_HDR, OPT_STEP_SEP]
    for case in cases:
        kind, mode, wire = (*case, "f32")[:3]
        r = opt_step_roofline(m, p, kind=kind, mode=mode, wire=wire)
        out.append(
            f"| {r['kernel']} | {m} | {p} | {r['state_planes']} | "
            f"{r['flops']:.2e} | {r['bytes']:.2e} | "
            f"{r['intensity_flop_per_byte']:.2f} | {r['memory_s']:.2e} | "
            f"{r['wire_bytes_per_event']:.2e} | "
            f"{r['wire_reduction_vs_f32']:.2f}x | "
            f"{r['unfused_passes']} -> {r['fused_passes']} |")
    return "\n".join(out)


def render_avg_disp(cases=((16, 1 << 20, 1, False), (16, 1 << 20, 4, False),
                           (16, 1 << 20, 1, True),
                           (64, 1 << 24, 1, True))) -> str:
    out = [AVG_DISP_HDR, AVG_DISP_SEP]
    for m, p, groups, outer in cases:
        r = avg_disp_roofline(m, p, groups=groups, outer=outer)
        out.append(
            f"| {r['kernel']} | {m} | {p} | {groups} | {r['flops']:.2e} | "
            f"{r['bytes']:.2e} | {r['intensity_flop_per_byte']:.2f} | "
            f"{r['memory_s']:.2e} | {r['tree_path_passes']} -> "
            f"{r['fused_passes']} |")
    return "\n".join(out)


def run():
    rows = load()
    n_ok = sum(1 for r in rows if "skipped" not in r)
    n_skip = sum(1 for r in rows if "skipped" in r)
    r = avg_disp_roofline(16, 1 << 20)
    o = opt_step_roofline(16, 1 << 20, kind="momentum", mode="mean")
    print(f"roofline_table,0.0,combos_compiled={n_ok};skipped={n_skip};"
          f"avg_disp_fb={r['intensity_flop_per_byte']:.2f};"
          f"opt_step_fb={o['intensity_flop_per_byte']:.2f}")


if __name__ == "__main__":
    enable_compile_cache()
    print(render())
    print()
    print(render_avg_disp())
    print()
    print(render_opt_step())
