"""The paper's statistical/hardware-efficiency trade-off, quantified from
dry-run artifacts: averaging cost per phase, amortized per-step overhead
vs phase length K, and the break-even K where communication drops below
x% of step time.

Reads (arch, train_4k) rows from results/dryrun.jsonl: the `avg=none`
row gives the pure local step; the `avg=all` row adds the phase-end
model average. The difference in collective bytes is the cost of one
averaging operation (the paper's "communication cost of a phase").
"""
from __future__ import annotations

import json
import os

from benchmarks.common import RESULTS_DIR, emit, save
from repro.launch.cache import enable_compile_cache
from repro.roofline.analysis import HW


def load_pairs(path=None):
    path = path or os.path.join(RESULTS_DIR, "dryrun.jsonl")
    rows = {}
    for line in open(path):
        try:
            r = json.loads(line)
        except Exception:
            continue
        if r.get("shape") != "train_4k" or "skipped" in r:
            continue
        if r.get("variant", "baseline") != "baseline":
            continue
        key = (r["arch"], r["mesh"], r.get("avg", "none"))
        rows[key] = r
    return rows


def analyze(hw: HW = HW()):
    rows = load_pairs()
    out = []
    for (arch, mesh, avg), r in sorted(rows.items()):
        if avg == "none":
            continue
        base = rows.get((arch, mesh, "none"))
        if base is None:
            continue
        if r.get("phase_steps", 1) != base.get("phase_steps", 1):
            # rows from different dry-run generations / --phase-steps:
            # their collective-bytes deltas are not comparable
            continue
        d_coll = (r["collective_bytes_per_device"]
                  - base["collective_bytes_per_device"])
        # analytic cost of one model average: all-reduce of the per-chip
        # param shard (bf16, 16-way model sharding) ~ 2x payload on a ring.
        from repro.configs import get_config
        n_params = get_config(arch).num_params()
        analytic_bytes = 2.0 * n_params * 2 / 16
        # XLA CSEs the phase-end all-reduce into the step's existing
        # FSDP gathers when measured; report max(measured, analytic).
        avg_s = max(d_coll, analytic_bytes) / hw.ici_bw
        # train rows are whole compiled phases (phase_steps local steps);
        # normalize to per-step time for the amortization analysis
        k_phase = max(base.get("phase_steps", 1), 1)
        step_s = max(base["compute_s"], base["memory_s"],
                     base["collective_s"]) / k_phase
        ks = {}
        for frac in (0.01, 0.05, 0.25):
            ks[f"K_for_{int(frac*100)}pct"] = (
                max(1, round(avg_s / (step_s * frac))) if step_s else None)
        out.append({
            "arch": arch, "mesh": mesh, "avg": avg,
            "avg_bytes_per_device": max(d_coll, analytic_bytes),
            "measured_coll_delta_bytes": d_coll,
            "avg_seconds": avg_s,
            "local_step_seconds": step_s,
            "minibatch_overhead_pct": 100.0 * avg_s / step_s if step_s else None,
            **ks,
        })
    return out


def run():
    out = analyze()
    save("averaging_cost", {"rows": out})
    if out:
        emit("averaging_cost_amortization", 0.0,
             ";".join(f"{r['arch']}:avg={r['avg_seconds']:.3f}s,"
                      f"K1%={r['K_for_1pct']}" for r in out[:6]))
    else:
        emit("averaging_cost_amortization", 0.0, "no avg rows yet")


if __name__ == "__main__":
    enable_compile_cache()
    run()
