"""Paper Table 1: per-dataset gradient-variance measurements (σ², β², ρ)
via the §3.1 procedure, on the synthetic convex suite (regime analogues
of the paper's libsvm datasets — see DESIGN.md §6)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, save, timeit
from repro.configs.paper import CONVEX_SUITE
from repro.core.variance_model import empirical_variance_fn, measure_beta2, rho
from repro.data import convex_dataset
from repro.models.convex import solve_optimum as _w_star_impl
from repro.launch.cache import enable_compile_cache


def _w_star(kind, X, y):
    return _w_star_impl(kind, X, y)


def run():
    rows = []
    total_us = 0.0
    for c in CONVEX_SUITE:
        n = min(c.num_samples, 2048)
        d = min(c.num_dims, 256)
        X, y, _ = convex_dataset(c.model, n, d, sparsity=c.sparsity,
                                 noise=c.noise, seed=0)
        X, y = jnp.asarray(X), jnp.asarray(y)
        ws = _w_star(c.model, X, y)
        vfn = empirical_variance_fn(c.model, X, y)
        dt, (b2, s2) = timeit(
            lambda: measure_beta2(vfn, ws, key=jax.random.PRNGKey(0),
                                  num_lines=6), reps=1)
        total_us += dt
        r = rho(b2, s2, jnp.zeros(d), ws)
        rows.append({"dataset": c.name, "model": c.model, "n": n, "d": d,
                     "sigma2": s2, "beta2": b2, "rho": r})
    save("bench_table1", {"rows": rows})
    order = sorted(rows, key=lambda r: -r["rho"])
    emit("table1_variance_measurements", total_us,
         "rho_order=" + ">".join(r["dataset"].split("-")[1] for r in order))


if __name__ == "__main__":
    enable_compile_cache()
    run()
