"""Paper §2.4 quartic example: f(w) = (w²-1)², ∇f̃ = 4(w³-w+u).
The paper reports (24 workers, α=.025, 10000 steps): one-shot 0.922,
0.1%% averaging 0.274, 10%% averaging 0.011."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, save, timeit
from repro.configs.paper import QuarticConfig
from repro.launch.cache import enable_compile_cache


def run_quartic(cfg: QuarticConfig, avg_fracs, seed=0):
    key = jax.random.PRNGKey(seed)
    u = jax.random.normal(key, (cfg.num_steps, cfg.num_workers))
    rows = []
    for frac in avg_fracs:
        k = 0 if frac == 0 else max(1, int(round(1.0 / frac)))
        do_avg = (jnp.arange(1, cfg.num_steps + 1) % k == 0) if k else \
            jnp.zeros(cfg.num_steps, bool)

        @jax.jit
        def go():
            def body(w, inp):
                ut, at = inp
                g = 4.0 * (w ** 3 - w + ut)
                w = w - cfg.alpha * g
                w = jnp.where(at, jnp.full_like(w, jnp.mean(w)), w)
                return w, None
            w, _ = jax.lax.scan(body, jnp.zeros(cfg.num_workers),
                                (u, do_avg))
            return jnp.mean(w)

        wbar = float(go())
        obj = (wbar ** 2 - 1.0) ** 2
        rows.append({"avg_frac": frac, "objective": float(obj)})
    return rows


def run():
    cfg = QuarticConfig()
    dt, rows = timeit(lambda: run_quartic(cfg, [0.0, 0.001, 0.01, 0.1]),
                      reps=1)
    save("bench_quartic", {"rows": rows,
                           "paper": {"oneshot": 0.922, "0.001": 0.274,
                                     "0.1": 0.011}})
    d = {r["avg_frac"]: r["objective"] for r in rows}
    emit("quartic_nonconvex", dt,
         f"oneshot={d[0.0]:.3f};avg0.1%={d[0.001]:.3f};avg10%={d[0.1]:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
