"""Benchmark driver — one benchmark per paper table/figure + kernel
micro-benches + roofline summary. Prints ``name,us_per_call,derived``
CSV lines (spec format) and saves full payloads under results/."""
from __future__ import annotations

import sys
import traceback

from repro.launch.cache import enable_compile_cache


def main() -> None:
    from benchmarks import (averaging_cost, bench_fig1_pca,
                            bench_fig2_convex, bench_fig3_cnn,
                            bench_kernels, bench_lemma1, bench_quartic,
                            bench_table1, roofline_table)
    benches = [
        ("lemma1 (paper §2.3)", bench_lemma1),
        ("table1 (paper Table 1)", bench_table1),
        ("fig2 convex (paper Fig 2)", bench_fig2_convex),
        ("fig1 pca (paper Fig 1)", bench_fig1_pca),
        ("quartic (paper §2.4)", bench_quartic),
        ("fig3 cnn (paper Fig 3 / §3.2)", bench_fig3_cnn),
        ("kernels", bench_kernels),
        ("averaging cost (paper's trade-off, from dry-run)", averaging_cost),
        ("roofline (EXPERIMENTS.md §Roofline)", roofline_table),
    ]
    print("name,us_per_call,derived")
    failed = []
    for label, mod in benches:
        try:
            mod.run()
        except Exception:
            failed.append(label)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
