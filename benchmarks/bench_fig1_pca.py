"""Paper Figure 1: PCA (Oja's rule) principal-component error vs total
number of averaging steps — one-shot (leftmost point) through frequent."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, save, timeit
from repro.configs.paper import PCAConfig
from repro.launch.cache import enable_compile_cache


def pca_error_vs_avg_steps(cfg: PCAConfig, phase_lens, seed=0):
    spec = np.full(cfg.dim, cfg.tail_eig)
    spec[0] = cfg.top_eig
    v1 = np.eye(cfg.dim)[0]
    rows = []
    for k in phase_lens:
        rng = np.random.default_rng(seed)
        w = rng.standard_normal((cfg.num_workers, cfg.dim))
        w /= np.linalg.norm(w, axis=1, keepdims=True)
        rs = np.random.default_rng(1234)
        n_avg = 0
        for t in range(cfg.num_samples):
            x = rs.standard_normal((cfg.num_workers, cfg.dim)) * np.sqrt(spec)
            wx = np.einsum("md,md->m", w, x)
            w = w + cfg.alpha * wx[:, None] * x
            w /= np.maximum(np.linalg.norm(w, axis=1, keepdims=True), 1e-9)
            if k and (t + 1) % k == 0:
                w = np.broadcast_to(w.mean(0), w.shape).copy()
                w /= np.maximum(np.linalg.norm(w, axis=1, keepdims=True), 1e-9)
                n_avg += 1
        wbar = w.mean(0)
        err = 1.0 - abs(wbar @ v1) / (np.linalg.norm(wbar) + 1e-12)
        rows.append({"phase_len": k, "num_avg_steps": n_avg + 1,
                     "pc_error": float(err)})
    return rows


def run():
    cfg = PCAConfig(num_workers=24, num_samples=4000, alpha=0.02)
    dt, rows = timeit(
        lambda: pca_error_vs_avg_steps(cfg, [0, 2000, 500, 100, 25, 5]),
        reps=1)
    save("bench_fig1_pca", {"rows": rows, "config": cfg.__dict__})
    one = rows[0]["pc_error"]
    best = min(r["pc_error"] for r in rows[1:])
    emit("fig1_pca_oja", dt, f"oneshot_err={one:.3f};best_periodic_err={best:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
