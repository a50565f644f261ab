"""Paper §2.3 / Lemma 1: asymptotic variance of the worker average vs
averaging rate ζ — closed form against Monte-Carlo simulation."""
from __future__ import annotations


from benchmarks.common import emit, save, timeit
from repro.configs.paper import QuadraticConfig
from repro.core.theory import lemma1_asymptotic_variance, simulate_quadratic
from repro.launch.cache import enable_compile_cache


def run():
    cfg = QuadraticConfig()
    zetas = [0.0, 0.001, 0.005, 0.02, 0.1, 0.3, 1.0]
    rows = []
    us = 0.0
    for z in zetas:
        pred = lemma1_asymptotic_variance(cfg.alpha, cfg.c, cfg.beta2,
                                          cfg.sigma2, cfg.num_workers, z)
        dt, sim = timeit(simulate_quadratic, cfg.alpha, cfg.c, cfg.beta2,
                         cfg.sigma2, cfg.num_workers, z, 3000, reps=1)
        us += dt
        rows.append({"zeta": z, "lemma1": pred, "simulated": float(sim),
                     "rel_err": abs(float(sim) - pred) / pred})
    worst = max(r["rel_err"] for r in rows)
    ratio = rows[0]["lemma1"] / rows[-1]["lemma1"]
    save("bench_lemma1", {"rows": rows, "config": cfg.__dict__})
    emit("lemma1_asymptotic_variance", us,
         f"worst_rel_err={worst:.3f};oneshot/minibatch_var_ratio={ratio:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
