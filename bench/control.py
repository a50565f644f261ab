"""The readings a cell's limits are set from, on the chip at its size.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 --faulty 3

For every seed: the program's first phase (set-up of a benchmark run,
no window) against the reference's, as ``bench/run.py`` compares them.
For the first ``--faulty`` seeds also: the control (the reference with
its parameters held in float8 e4m3, the precision below the bf16 the
configuration states), the momentum stored in bfloat16 (the f32 plane
the configuration states, halved: the shortcut a later change to the
update would take) and the planted faults, each run by the reference in
the program's place: half of every batch left out, and, on a cell over
several chips, the averaging left inside each chip's own workers.
A state left unchanged reads 1 by construction and needs no run.

One process, one compile: each line of standard output is a JSON
object; the last one sums up, per compared number, the largest sound
reading and the smallest reading of each control and fault.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import check, run  # noqa: E402
from bench.feed import Feed, TokenBlocks  # noqa: E402


def readings(name, seeds, faulty, devices, root=ROOT, out=print):
    cell = run.build(name, root)
    cfg, tr, ref = cell.cfg, cell.tr, cell.ref
    used = devices[:cell.wl["chips"]]
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        feed = Feed(TokenBlocks(tr, ref.shape(cfg)["vocab"], seed))
        prog, state = run.first_phase(cell, seed, feed)
        del state
        block = feed.first_block
        base = ref.train_phase(cfg, tr, seed, block, devices=used)
        row = {"seed": seed, "program": _short(check.readings(prog, base))}
        if i < faulty:
            runs = {"control": dict(param_dtype="float8_e4m3fn"),
                    "momentum_bf16": dict(vel_dtype="bfloat16"),
                    "half_batch": dict(half_batch=True)}
            if len(used) > 1:
                runs["no_exchange"] = dict(groups=len(used))
            for what, kw in runs.items():
                other = ref.train_phase(cfg, tr, seed, block, devices=used,
                                        **kw)
                row[what] = _short(check.readings(other, base))
        row["seconds"] = time.perf_counter() - t0
        out(json.dumps(row))
        rows.append(row)
    summary = {"workload": name, "seeds": list(seeds),
               "program_max": {n: max(r["program"][n] for r in rows)
                               for n in check.NAMES}}
    for what in ("control", "momentum_bf16", "half_batch", "no_exchange"):
        have = [r[what] for r in rows if what in r]
        if have:
            summary[what + "_min"] = {n: min(h[n] for h in have)
                                      for n in check.NAMES}
    out(json.dumps(summary))
    return summary


def _short(read):
    return {k: v for k, v in read.items() if k in check.NAMES
            or k.startswith("worst")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--faulty", type=int, default=3,
                    help="seeds on which the control and faults also run")
    args = ap.parse_args(argv)
    from bench import spec
    devices = run.find_devices(spec.cell(args.workload)["workload"]["chips"])
    run.enable_cache()
    readings(args.workload, [int(s) for s in args.seeds.split(",")],
             args.faulty, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
