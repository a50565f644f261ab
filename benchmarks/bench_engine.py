"""Phase-engine benchmark on reduced convex workloads.

Runtimes, same periodic(K) schedule on identical sample draws:

  host          — PhaseEngine.run_host: one jit dispatch per step,
                  averaging decided on host (the seed runtime).
  tree          — PR 1 engine: compiled phase scans, params-pytree carry.
  flat_staged   — flat (M, P) plane + fused averaging, host-staged from
                  an in-memory list (sync).
  flat_prefetch — same list source with prefetch=True: run() now detects
                  the materialized source and skips the prefetch thread,
                  so this column ≈ flat_staged (the PR 2 regression —
                  speedup_prefetch_vs_stack < 1 on every row — is gone).
  stream_sync / stream_prefetch — a TRUE stream source (host indexing +
                  device transfer per step): the double-buffered
                  Prefetcher only ever engages here.
  flat_indexed  — PR 2 engine: flat plane + on-device index blocks, but
                  per-step spec.unpack/spec.pack round-trips around the
                  tree-mapped optimizer (fused_opt=False).
  flat_fusedopt — PR 3 flat-NATIVE engine: optimizer state as (M, P)
                  planes in the scan carry, fused opt_step update —
                  zero per-step pack/unpack.
  flat_sharded  — the same engine under shard_map over the available
                  devices, where it carries the leaves and psums its
                  worker means; needs >= 2 devices (CI runs it under
                  XLA_FLAGS=--xla_force_host_platform_device_count=8).

Two Momentum workloads: ``ls`` (single-leaf least squares — PR 1/2
continuity; pytree overhead is negligible at one leaf) and ``deep`` (a
36-leaf narrow tanh MLP — the regime the fused optimizer planes target;
the acceptance column ``speedup_fusedopt_vs_flat`` is flat_indexed /
flat_fusedopt). Deep rows sweep scan_unroll: rolled scans let XLA elide
much of the tree path's per-step pack/unpack, unrolled scans (the
CPU-recommended setting for compute-heavy bodies) expose it — the
flat-native carry is robust to both.

Also times the WorkerSharder batched replacement draw. An ``adaptive``
row compares the dispersion-driven schedules (adaptive_threshold with
the trip level self-tuned to 0.7x the periodic run's mean event
dispersion; adaptive_budget with half the periodic communication
budget) against the periodic-8 baseline on identical draws: final
consensus loss vs averaging-event count — the paper's question, answered by following the measured variance envelope
instead of a fixed clock. A ``topology`` sweep (``repro.topology``)
asks the same question along the mixing-matrix axis: each sparse
topology (ring / torus / hypercube / gossip pairs) runs at the event
period matching periodic-8 full averaging's per-worker communication
budget, recording final loss + dispersion envelope vs spectral gap vs
comm volume — and the ``full``-topology run is checked bit-identical
to the plain mean path (``full_topology_bitexact``, gated in CI; the
``--tiny`` smoke keeps full+ring+gossip).
A ``compressed`` row (``repro.core.compress``) runs the wire-precision
axis at matched BYTE budgets: int8 + error feedback at the event period
whose realized bytes-on-the-wire fit within 25% of full-f32 periodic-8's
(per ``repro.topology.comm_bytes``), recording final losses and bytes —
plus a ``bf16`` arm at the same period as the baseline (half the
bytes for free). The ``f32`` wire format must lower to the
uncompressed path BIT-exactly (params + full history) — recorded as
``compressed_matches_f32`` and gated like ``full_topology_bitexact``.
A ``faults`` row (``repro.faults``) runs the robustness axis: a
scripted crash + warm-started rejoin with stochastic stragglers must
recover the no-fault final loss within 5% (``dropout_recovers``,
gated in CI), and an IID-vs-dirichlet(0.05) shard comparison records
the non-IID dispersion gap against the variance model's predicted
averaging benefit (``noniid_benefit_agrees``).
An ``elastic`` row (``repro.elastic``) runs the membership axis: a
fixed-M periodic-8 baseline vs the same recipe shrinking to 3M/4 a
quarter of the way in and growing back (4-step rejoin curriculum) at
three quarters — the resized run must recover the fixed-M final loss
within 5% (``elastic_recovers``, gated in CI), and the K-weighted
drift budget (``predict_post_resize_dispersion``, arXiv 1807.06629)
calibrated on dirichlet(0.05) shards must predict the measured
post-resize dispersion within 2x (``envelope_calibrated``).
Topology-sweep rows carry a ``bytes_per_worker`` column pricing their
realized events at every wire format, so matched-budget comparisons
read in bytes, not messages.

Emits JSON via benchmarks/common.py
(results/bench_engine.json). ``--tiny`` runs CI-smoke shapes (no host
baseline; pass ``--save`` to still write JSON for the CI artifact).
"""
from __future__ import annotations

import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS_DIR, emit, save
from repro.core import AveragingSchedule, PhaseEngine
from repro.data import convex_dataset
from repro.data.pipeline import DeviceDataset, WorkerSharder
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_worker_mesh
from repro.optim import SGD, Momentum
from repro.telemetry import JsonlSink, run_meta_record
from repro.telemetry.timing import time_run, timed

DIM, SAMPLES, STEPS = 64, 1024, 512
PHASE_LENS = (1, 4, 8, 64, 512)
DEEP_PHASE_LENS = (1, 8, 64)
WORKER_COUNTS = (4, 16)
AVG_HEAVY_K = 8  # minibatch / periodic K<=8: the averaging-heavy regime
DEEP_LAYERS, DEEP_WIDTH = 16, 32


def ls_mean_loss(params, batch, rng):
    r = batch["x"] @ params["w"] - batch["y"]
    return 0.5 * jnp.mean(r * r), {}


def deep_params(dim):
    ks = jax.random.split(jax.random.PRNGKey(0), DEEP_LAYERS + 1)
    h = DEEP_WIDTH
    p = {"in": {"w": jax.random.normal(ks[0], (dim, h)) * 0.3,
                "b": jnp.zeros(h)}}
    for i in range(DEEP_LAYERS):
        p[f"h{i:02d}"] = {"w": jax.random.normal(ks[i + 1], (h, h)) * 0.3,
                          "b": jnp.zeros(h)}
    p["out"] = {"w": jnp.zeros((h, 1)), "b": jnp.zeros(1)}
    return p


def deep_loss(params, batch, rng):
    h = jnp.tanh(batch["x"] @ params["in"]["w"] + params["in"]["b"])
    for i in range(DEEP_LAYERS):
        h = jnp.tanh(h @ params[f"h{i:02d}"]["w"] + params[f"h{i:02d}"]["b"])
    out = (h @ params["out"]["w"] + params["out"]["b"])[:, 0]
    return 0.5 * jnp.mean(jnp.square(out - batch["y"])), {}


def schedule(phase_len: int) -> AveragingSchedule:
    return (AveragingSchedule("minibatch") if phase_len == 1
            else AveragingSchedule("periodic", phase_len))


def make_engine(loss_fn, phase_len: int, *, flat: bool = True,
                fused: bool = True, unroll: int = 1, mesh=None):
    return PhaseEngine(loss_fn, Momentum(lr=0.01, mu=0.9),
                       schedule(phase_len), flat=flat, fused_opt=fused,
                       scan_unroll=unroll, mesh=mesh)


def worker_mesh(workers: int):
    """The production worker mesh when enough devices are visible to
    actually shard, else None (sharded columns skipped)."""
    mesh = make_worker_mesh(workers)
    return mesh if mesh.shape["data"] >= 2 else None


def bench_sharder(workers: int, steps: int, batch: int = 8,
                  reps: int = 5) -> dict:
    """Replacement-mode index generation: batched single draw vs the
    PR 1 per-worker python loop."""
    def loop_draw():  # the old implementation, for comparison
        rngs = [np.random.default_rng(10_007 + i) for i in range(workers)]
        out = np.empty((steps, workers, batch), np.int64)
        for t in range(steps):
            for i in range(workers):
                out[t, i] = rngs[i].integers(0, SAMPLES, batch)
        return out

    def block_draw():
        sh = WorkerSharder(SAMPLES, workers, seed=1, mode="replacement")
        return sh.next_index_block(steps, batch)

    out = {}
    for name, fn in (("loop", loop_draw), ("block", block_draw)):
        fn()
        best = min(timed(fn) for _ in range(reps))
        out[f"sharder_{name}_us"] = best * 1e6
    out["sharder_speedup"] = out["sharder_loop_us"] / out["sharder_block_us"]
    return out


def bench_adaptive(arrays, idx, workers, steps) -> dict:
    """Adaptive dispersion-driven schedules vs the periodic-8 baseline
    on identical sample draws: how much averaging does the measured
    dispersion envelope actually need? Returns one row with final
    consensus losses (full-dataset objective) and averaging-event
    counts. The threshold is self-tuned to 0.7x the periodic run's mean
    event dispersion — just under the level a periodic phase typically
    builds, so averaging triggers as the envelope approaches it — and
    the budget is half the periodic run's events (the tuning recorded
    in the row as ``disp_threshold`` / ``comm_budget``)."""
    Xn, yn = np.asarray(arrays["x"]), np.asarray(arrays["y"])

    def full_loss(f):
        r = Xn @ np.asarray(f["w"]) - yn
        return 0.5 * float(np.mean(r * r))

    def run(sch):
        eng = PhaseEngine(ls_mean_loss, Momentum(lr=0.01, mu=0.9), sch)
        f, h = eng.run({"w": jnp.zeros(Xn.shape[1])},
                       DeviceDataset(arrays, workers, indices=idx),
                       num_workers=workers, seed=3, record_every=1)
        return full_loss(f), h

    loss_p, h_p = run(AveragingSchedule("periodic", 8))
    thr = 0.7 * float(np.mean([v for _, v in h_p["dispersion"]]))
    loss_t, h_t = run(AveragingSchedule(
        "adaptive_threshold", disp_threshold=thr, disp_ema_beta=0.5))
    budget = max(1, h_p["averages"] // 2)
    loss_b, h_b = run(AveragingSchedule(
        "adaptive_budget", comm_budget=budget, budget_horizon=steps))
    row = {
        "workload": "adaptive", "workers": workers, "steps": steps,
        "periodic_final_loss": loss_p,
        "periodic_events": h_p["averages"],
        "disp_threshold": thr,
        "adaptive_threshold_final_loss": loss_t,
        "adaptive_threshold_events": h_t["averages"],
        "comm_budget": budget,
        "adaptive_budget_final_loss": loss_b,
        "adaptive_budget_events": h_b["averages"],
        # the acceptance claim: periodic-K's final loss (3% slack — the
        # convex objective's step-to-step noise band) with fewer events
        "adaptive_reaches_periodic": bool(
            loss_t <= loss_p * 1.03
            and h_t["averages"] < h_p["averages"]),
    }
    emit("engine_adaptive_vs_periodic", row["adaptive_threshold_events"],
         f"periodic8_loss={loss_p:.5f}@{h_p['averages']}ev;"
         f"thresh_loss={loss_t:.5f}@{h_t['averages']}ev;"
         f"budget_loss={loss_b:.5f}@{h_b['averages']}ev;"
         f"reaches_periodic={row['adaptive_reaches_periodic']}")
    return row


def bench_topology(arrays, idx, workers, steps, tiny: bool = False) -> dict:
    """Mixing-topology sweep at matched communication budgets — the
    paper's question along the new ``repro.topology`` axis: at equal
    communication, is FREQUENT SPARSE mixing better than INFREQUENT
    FULL averaging?

    Baseline: periodic-8 full averaging, i.e. (M-1)/8 row-exchanges
    per worker per step (one full-mean event costs M-1 messages per
    worker, a ring event 2, a gossip pairing 1). Every sparse topology
    runs at the event period that matches the baseline's per-step
    budget as closely as its degree allows, on identical sample draws.
    Rows record final consensus loss, the dispersion envelope (mean
    over the last quarter of steps — the Eq. 4 diagnostic the spectral
    gap governs), the spectral gap, and the realized comm volume.

    Also verifies the subsystem's bit-identity anchor: an engine with
    ``Topology.full`` must reproduce the plain mean path EXACTLY
    (params + full history) — recorded as ``full_topology_bitexact``
    and gated in CI."""
    from repro.topology import Topology, comm_bytes
    Xn, yn = np.asarray(arrays["x"]), np.asarray(arrays["y"])

    def full_loss(f):
        r = Xn @ np.asarray(f["w"]) - yn
        return 0.5 * float(np.mean(r * r))

    def run(sch, topo):
        eng = PhaseEngine(ls_mean_loss, Momentum(lr=0.01, mu=0.9), sch,
                          topology=topo)
        f, h = eng.run({"w": jnp.zeros(Xn.shape[1])},
                       DeviceDataset(arrays, workers, indices=idx),
                       num_workers=workers, seed=5, record_every=1)
        return f, full_loss(f), h

    base_period = 8
    base_sch = AveragingSchedule("periodic", base_period)
    f_plain, loss_plain, h_plain = run(base_sch, None)
    f_full, loss_full, h_full = run(base_sch, Topology.full(workers))
    bitexact = bool(
        (np.asarray(f_plain["w"]) == np.asarray(f_full["w"])).all()
        and h_plain == h_full)

    budget = (workers - 1) / base_period  # msgs/worker/step, baseline
    rows = []
    kinds = ["full", "ring", "gossip_pairs"]
    if not tiny:
        kinds += ["torus", "hypercube", "disconnected"]

    dim = Xn.shape[1]

    def row_of(topo, period, loss, hist):
        tail = [v for t, v in hist["disp_trace"] if t > steps * 3 // 4]
        return {
            "workload": "topology", "topology": topo.kind,
            "workers": workers, "steps": steps,
            "spectral_gap": topo.spectral_gap,
            "comm_degree": topo.comm_degree, "period": period,
            "events": hist["averages"],
            "comm_per_worker": hist["averages"] * topo.comm_degree,
            # the realized events priced at each wire format
            # (repro.topology.comm_bytes): matched-budget comparisons
            # in bytes, the currency the adaptive_bytes schedule spends
            "bytes_per_worker": {
                w: comm_bytes(topo, hist["averages"], dim, w)
                for w in ("f32", "bf16", "int8")},
            "final_loss": loss,
            "disp_tail_mean": float(np.mean(tail)) if tail else 0.0,
        }

    for kind in kinds:
        try:
            topo = Topology.build(kind, workers)
        except ValueError as e:  # e.g. prime M for torus in a sweep
            rows.append({"workload": "topology", "topology": kind,
                         "workers": workers, "skipped": str(e)})
            continue
        if kind == "full":
            period, (loss, h) = base_period, (loss_full, h_full)
        else:
            period = (max(1, round(topo.comm_degree / budget))
                      if topo.comm_degree > 0 else base_period)
            _, loss, h = run(AveragingSchedule("periodic", period), topo)
        rows.append(row_of(topo, period, loss, h))

    by_kind = {r["topology"]: r for r in rows if "skipped" not in r}
    ring, full = by_kind.get("ring"), by_kind["full"]
    headline = ""
    if ring:
        headline = (f"ring@K{ring['period']}_loss={ring['final_loss']:.5f}"
                    f"({ring['comm_per_worker']:.0f}msg);"
                    f"full@K{full['period']}_loss={full['final_loss']:.5f}"
                    f"({full['comm_per_worker']:.0f}msg)")
    emit("engine_topology_sweep", 0.0 if bitexact else 1.0,
         f"full_topology_bitexact={bitexact};{headline}")
    if not bitexact:
        # a regression in the full-topology bit-identity must fail the
        # PR, not just flip a field in the JSON artifact
        raise SystemExit(
            "Topology.full engine run is NOT bit-identical to the mean "
            "path")
    return {"full_topology_bitexact": bitexact,
            "baseline_period": base_period,
            "budget_msgs_per_worker_step": budget, "rows": rows}


def bench_compressed(arrays, idx, workers, steps) -> dict:
    """Wire-precision sweep at matched BYTE budgets — the paper's
    communication question in the currency production actually pays:
    can int8 rows + error feedback reach full-f32 periodic-8's final
    loss at <= 25% of the bytes-on-the-wire?

    Baseline: uncompressed periodic-8 full averaging. The int8 arm
    runs at the smallest event period whose realized wire bytes
    (``repro.topology.comm_bytes`` — events x (M-1) messages, each one
    encoded row of ``wire_row_bytes``) fit the 25% budget; int8 rows
    cost ~26.6% of f32 rows at these widths, so a slightly longer
    period buys the rest. A ``bf16`` arm rides the baseline period
    (50% of the bytes with no shared randomness). All arms run on
    identical sample draws.

    Also verifies the axis's bit-identity anchor: an engine with
    ``Compression("f32")`` must reproduce the uncompressed path
    EXACTLY (params + full history) — recorded as
    ``compressed_matches_f32`` and gated in CI like
    ``full_topology_bitexact``."""
    from repro.core import Compression
    from repro.topology import Topology, comm_bytes
    Xn, yn = np.asarray(arrays["x"]), np.asarray(arrays["y"])
    dim = Xn.shape[1]
    topo = Topology.full(workers)

    def full_loss(f):
        r = Xn @ np.asarray(f["w"]) - yn
        return 0.5 * float(np.mean(r * r))

    def run(period, comp):
        eng = PhaseEngine(ls_mean_loss, Momentum(lr=0.01, mu=0.9),
                          AveragingSchedule("periodic", period),
                          compression=comp)
        f, h = eng.run({"w": jnp.zeros(dim)},
                       DeviceDataset(arrays, workers, indices=idx),
                       num_workers=workers, seed=7, record_every=1)
        return f, full_loss(f), h

    base_period = 8
    f_plain, loss_f32, h_plain = run(base_period, None)
    f_id, loss_id, h_id = run(base_period, Compression("f32"))
    matches = bool(
        (np.asarray(f_plain["w"]) == np.asarray(f_id["w"])).all()
        and h_plain == h_id)

    bytes_f32 = comm_bytes(topo, h_plain["averages"], dim, "f32")
    budget = bytes_f32 // 4  # the 25%-of-the-bytes acceptance budget

    # smallest int8 period whose expected events fit the byte budget:
    # more frequent averaging is strictly better, so spend it all
    period_i8 = base_period
    while comm_bytes(topo, steps // period_i8, dim, "int8") > budget:
        period_i8 += 1
    _, loss_i8, h_i8 = run(period_i8, Compression("int8"))
    bytes_i8 = comm_bytes(topo, h_i8["averages"], dim, "int8")

    _, loss_bf16, h_bf16 = run(base_period, Compression("bf16"))
    bytes_bf16 = comm_bytes(topo, h_bf16["averages"], dim, "bf16")

    row = {
        "workload": "compressed", "workers": workers, "steps": steps,
        "f32_period": base_period, "f32_events": h_plain["averages"],
        "f32_bytes_per_worker": bytes_f32, "f32_final_loss": loss_f32,
        "bf16_period": base_period, "bf16_events": h_bf16["averages"],
        "bf16_bytes_per_worker": bytes_bf16,
        "bf16_final_loss": loss_bf16,
        "int8_period": period_i8, "int8_events": h_i8["averages"],
        "int8_bytes_per_worker": bytes_i8, "int8_final_loss": loss_i8,
        "int8_bytes_fraction": bytes_i8 / bytes_f32,
        # the acceptance claim: full-f32 periodic-8's final loss (3%
        # slack — the convex objective's step-to-step noise band) at
        # <= 25% of the bytes on the wire
        "int8_reaches_f32": bool(loss_i8 <= loss_f32 * 1.03
                                 and bytes_i8 * 4 <= bytes_f32),
        "compressed_matches_f32": matches,
    }
    emit("engine_compressed_vs_f32", 0.0 if matches else 1.0,
         f"compressed_matches_f32={matches};"
         f"f32_loss={loss_f32:.5f}@{bytes_f32}B;"
         f"int8_loss={loss_i8:.5f}@{bytes_i8}B"
         f"({row['int8_bytes_fraction']:.0%});"
         f"int8_reaches_f32={row['int8_reaches_f32']}")
    if not matches:
        # same CI contract as full_topology_bitexact: a regression in
        # the f32-wire bit-identity must fail the PR, not just flip a
        # field in the JSON artifact
        raise SystemExit(
            "Compression('f32') engine run is NOT bit-identical to the "
            "uncompressed path")
    return row


def bench_faults(arrays, idx, workers, steps) -> dict:
    """Robustness sweep along the fault + heterogeneity axes.

    Crash/rejoin recovery: a no-fault periodic-8 Momentum baseline vs
    the same engine under a scripted fault plan — one worker crashes a
    quarter of the way in, rejoins (warm-started from the alive
    average) at three quarters, with 5% stochastic stragglers
    throughout — on identical sample draws. The acceptance claim is
    ``dropout_recovers``: the faulted run's final consensus loss lands
    within 5% of the no-fault run's (the rejoined worker re-converges
    instead of dragging the consensus), gated in CI like
    ``compressed_matches_f32``.

    Heterogeneity: an IID (replacement) vs non-IID (per-class
    dirichlet(0.05) label skew over target-quantile pseudo-classes)
    sampled run at the same schedule. Non-IID shards hold worker
    iterates apart between events, so the recorded mean event
    dispersion gap ``noniid_disp_gap`` must be positive — and the
    variance model must agree (``noniid_benefit_agrees``). Label skew
    SHRINKS within-pool gradient variance (a near-single-class pool is
    more homogeneous than the full dataset); what widens the envelope
    is the coherent drift of pool-mean gradients, which accumulates
    linearly in iterate space over the K local steps between events
    (vs sqrt(K) for noise) and so enters the per-event variance budget
    with weight K. ``predict_averaging_benefit`` on that drift-aware
    budget must predict a larger averaging benefit for the skewed
    shards than the IID budget predicts."""
    from repro.core import FaultPlan, predict_averaging_benefit
    Xn, yn = np.asarray(arrays["x"]), np.asarray(arrays["y"])
    dim = Xn.shape[1]

    def full_loss(f):
        r = Xn @ np.asarray(f["w"]) - yn
        return 0.5 * float(np.mean(r * r))

    def run(data, faults=None, run_steps=None):
        eng = PhaseEngine(ls_mean_loss, Momentum(lr=0.01, mu=0.9),
                          AveragingSchedule("periodic", 8), faults=faults)
        f, h = eng.run({"w": jnp.zeros(dim)}, data, num_workers=workers,
                       seed=7, record_every=1, steps=run_steps)
        return full_loss(f), h

    loss_clean, h_clean = run(DeviceDataset(arrays, workers, indices=idx))
    t_crash, t_rejoin = max(1, steps // 4), max(2, 3 * steps // 4)
    plan = FaultPlan.parse(
        f"crash:m=1@t={t_crash},rejoin:m=1@t={t_rejoin}", workers,
        straggle_prob=0.05)
    loss_fault, h_fault = run(DeviceDataset(arrays, workers, indices=idx),
                              faults=plan)
    recovers = bool(loss_fault <= loss_clean * 1.05)

    # pseudo-classes for label skew: quartiles of the regression target
    labels = np.digitize(yn, np.quantile(yn, [0.25, 0.5, 0.75]))

    def sampled(mode, alpha):
        return run(DeviceDataset(arrays, workers, batch_size=8, seed=11,
                                 mode=mode, labels=labels, alpha=alpha),
                   run_steps=steps)

    loss_iid, h_iid = sampled("replacement", 0.5)
    loss_ni, h_ni = sampled("dirichlet", 0.05)
    disp_iid = float(np.mean([v for _, v in h_iid["dispersion"]]))
    disp_ni = float(np.mean([v for _, v in h_ni["dispersion"]]))

    # per-pool gradient statistics at w0 = 0 (per-sample grad =
    # -x_i y_i): noise = variance around the pool's own mean, drift =
    # the pool mean's offset from the global mean. Noise accumulates
    # as sqrt(K) over the K steps between events, drift coherently as
    # K — so the per-event variance budget weights drift by K
    sh = WorkerSharder(len(yn), workers, seed=11, mode="dirichlet",
                       labels=labels, alpha=0.05)
    grads = -Xn * yn[:, None]
    gbar = grads.mean(0)

    def pool_noise(pool):
        g = grads[pool]
        return float(np.mean(np.sum((g - g.mean(0)) ** 2, axis=1)))

    def pool_drift(pool):
        return float(np.sum((grads[pool].mean(0) - gbar) ** 2))

    period = 8
    s2_ni = [pool_noise(p) + period * pool_drift(p) for p in sh._pools]
    s2_iid = [pool_noise(np.arange(len(yn)))] * workers
    drift = float(np.mean([pool_drift(p) for p in sh._pools]))
    pred_ni = predict_averaging_benefit(s2_ni)
    pred_iid = predict_averaging_benefit(s2_iid)
    alive = np.ones(workers)
    alive[1] = 0.0
    pred_degraded = predict_averaging_benefit(s2_iid, alive=alive)

    row = {
        "workload": "faults", "workers": workers, "steps": steps,
        "fault_plan": f"crash:m=1@t={t_crash},rejoin:m=1@t={t_rejoin}",
        "straggle_prob": 0.05,
        "clean_final_loss": loss_clean, "clean_events": h_clean["averages"],
        "faulted_final_loss": loss_fault,
        "faulted_events": h_fault["averages"],
        "dropout_recovers": recovers,
        "iid_final_loss": loss_iid, "iid_mean_event_disp": disp_iid,
        "noniid_final_loss": loss_ni, "noniid_mean_event_disp": disp_ni,
        "noniid_disp_gap": disp_ni - disp_iid,
        "noniid_grad_drift": drift,
        "noniid_sigma2_bar": pred_ni["sigma2_bar"],
        "iid_sigma2_bar": pred_iid["sigma2_bar"],
        "noniid_predicted_benefit": pred_ni["benefit"],
        "iid_predicted_benefit": pred_iid["benefit"],
        "noniid_benefit_agrees": bool(
            disp_ni > disp_iid
            and pred_ni["benefit"] > pred_iid["benefit"]),
        "degraded_variance_reduction": pred_degraded["variance_reduction"],
    }
    emit("engine_faults_recovery", 0.0 if recovers else 1.0,
         f"clean_loss={loss_clean:.5f};fault_loss={loss_fault:.5f};"
         f"dropout_recovers={recovers};"
         f"noniid_disp_gap={row['noniid_disp_gap']:.4f};"
         f"benefit_agrees={row['noniid_benefit_agrees']}")
    if not recovers:
        # same CI contract as compressed_matches_f32: losing the
        # crash+rejoin recovery property must fail the PR, not just
        # flip a field in the JSON artifact
        raise SystemExit(
            f"faulted run does NOT recover: final loss {loss_fault:.6f} "
            f"vs no-fault {loss_clean:.6f} (budget 5%)")
    return row


def bench_elastic(arrays, idx, workers, steps, labels) -> dict:
    """Elastic-membership sweep (``repro.elastic``).

    Recovery: a fixed-M periodic-8 Momentum baseline vs the same recipe
    losing a quarter of its workers a quarter of the way in
    (shrink M -> 3M/4 at steps/4) and getting them back at three
    quarters (grow back, 4-step rejoin curriculum), on identical sample
    draws — at the default shapes that is the ISSUE's 16 -> 12 at
    t=128, back to 16 at t=384. The acceptance claim is
    ``elastic_recovers``: the resized run's final consensus loss lands
    within 5% of the fixed-M run's (the noise band the other recovery
    gates use), gated in CI like ``dropout_recovers``.

    Calibration: an SGD run on dirichlet(0.05) label-skewed shards
    exercises ``predict_post_resize_dispersion`` — the K-weighted
    drift budget of Parallel Restarted SGD (arXiv 1807.06629) — as a
    MAGNITUDE predictor, not just a direction: per-pool gradient noise
    (sigma^2 / batch), pool-mean drift and the pool-curvature
    contraction rate along it are measured at the consensus reached by
    the averaging event at the grow-back step, and the predicted
    K=8-step dispersion must land within 2x of the dispersion the
    engine actually records one period later
    (``envelope_calibrated``, gated the same way)."""
    from repro.core import predict_post_resize_dispersion
    from repro.elastic import ElasticPlan, run_elastic
    Xn, yn = np.asarray(arrays["x"]), np.asarray(arrays["y"])
    dim = Xn.shape[1]

    def full_loss(f):
        r = Xn @ np.asarray(f["w"]) - yn
        return 0.5 * float(np.mean(r * r))

    t1, t2 = max(2, steps // 4), 3 * steps // 4
    m1 = max(1, 3 * workers // 4)
    plan = ElasticPlan.parse(workers, shrink_at=[f"{t1}:{m1}"],
                             grow_at=[f"{t2}:{workers}"], curriculum=4)

    def factory(m, t0, k):
        return DeviceDataset(arrays, m,
                             indices=idx[t0 - 1:t0 - 1 + k, :m])

    def run_fixed():
        eng = PhaseEngine(ls_mean_loss, Momentum(lr=0.01, mu=0.9),
                          AveragingSchedule("periodic", 8))
        f, h = eng.run({"w": jnp.zeros(dim)},
                       DeviceDataset(arrays, workers, indices=idx),
                       num_workers=workers, seed=6, record_every=1)
        return full_loss(f), h

    loss_fixed, h_fixed = run_fixed()
    eng = PhaseEngine(ls_mean_loss, Momentum(lr=0.01, mu=0.9),
                      AveragingSchedule("periodic", 8))
    f_el, h_el = run_elastic(eng, {"w": jnp.zeros(dim)}, factory, plan,
                             steps=steps, seed=6, record_every=1)
    loss_el = full_loss(f_el)
    recovers = bool(loss_el <= loss_fixed * 1.05)

    # ---- calibration: predicted vs measured post-resize dispersion ----
    # dirichlet(0.05) shards, SGD (the K-window weights c_j = lr exactly;
    # momentum's velocity carry-over from BEFORE the window would break
    # the from-consensus assumption), curriculum 0 so the grown rows
    # enter the mix — and the model's n — immediately
    lr, period = 0.01, 8
    sh = WorkerSharder(len(yn), workers, seed=13, mode="dirichlet",
                       labels=labels, alpha=0.05)
    cal_steps = t2 + period
    block = sh.next_index_block(cal_steps, 8)

    def cal_factory(m, t0, k):
        return DeviceDataset(arrays, m,
                             indices=block[t0 - 1:t0 - 1 + k, :m])

    cal_plan = ElasticPlan.parse(workers, shrink_at=[f"{t1}:{m1}"],
                                 grow_at=[f"{t2}:{workers}"])
    cal_eng = PhaseEngine(ls_mean_loss, SGD(lr=lr),
                          AveragingSchedule("periodic", period))
    # stop at the averaging event DURING step t2 (t2 % 8 == 0): every
    # row — survivors and grown alike — leaves it at the consensus w_c,
    # so the next period is exactly the model's from-consensus K-window
    w_c, _, st = run_elastic(cal_eng, {"w": jnp.zeros(dim)}, cal_factory,
                             cal_plan, steps=t2, seed=6,
                             return_state=True)
    _, h_cal = run_elastic(cal_eng, {"w": jnp.zeros(dim)}, cal_factory,
                           cal_plan, steps=cal_steps, seed=6,
                           record_every=1, state=st)
    measured = float(dict(h_cal["dispersion"])[cal_steps])

    # per-pool gradient statistics AT w_c: per-sample grad of the
    # 0.5*mean(r^2) objective is x_i r_i; a B-sample batch mean has
    # sigma^2_pool / B of it. Pool-mean drifts are centered on the
    # ACROSS-POOL mean (dispersion is measured against the worker
    # mean, which tracks it, not the full-data gradient), and the
    # contraction rate each drift decays at is the pool Hessian's
    # Rayleigh quotient along it, weighted by drift mass
    wc = np.asarray(w_c["w"])
    g = Xn * (Xn @ wc - yn)[:, None]
    means = np.stack([g[p].mean(0) for p in sh._pools])
    s2 = [float(np.mean(np.sum((g[p] - g[p].mean(0)) ** 2, axis=1))) / 8
          for p in sh._pools]
    drift2 = float(np.mean(np.sum((means - means.mean(0)) ** 2, axis=1)))
    lams = np.array([float(d @ (Xn[p].T @ Xn[p] / len(p)) @ d / (d @ d))
                     for p, d in zip(sh._pools, means)])
    w2 = np.sum(means ** 2, axis=1)
    curvature = float(np.sum(w2 * lams) / np.sum(w2))
    pred = predict_post_resize_dispersion(s2, lr=lr, steps=period,
                                          drift2=drift2,
                                          curvature=curvature)
    predicted = pred["predicted_dispersion"]
    ratio = measured / predicted if predicted > 0 else float("inf")
    calibrated = bool(0.5 <= ratio <= 2.0)

    row = {
        "workload": "elastic", "workers": workers, "steps": steps,
        "plan": f"shrink@{t1}:{m1},grow@{t2}:{workers}",
        "curriculum": 4,
        "fixed_final_loss": loss_fixed,
        "fixed_events": h_fixed["averages"],
        "elastic_final_loss": loss_el,
        "elastic_events": h_el["averages"],
        "resizes": h_el["resizes"],
        "elastic_recovers": recovers,
        "calib_measured_disp": measured,
        "calib_predicted_disp": predicted,
        "calib_drift2": drift2,
        "calib_curvature": curvature,
        "calib_noise_disp": pred["noise_dispersion"],
        "calib_drift_disp": pred["drift_dispersion"],
        "calib_ratio": ratio,
        "envelope_calibrated": calibrated,
    }
    emit("engine_elastic_recovery", 0.0 if recovers else 1.0,
         f"fixed_loss={loss_fixed:.5f};elastic_loss={loss_el:.5f};"
         f"elastic_recovers={recovers};"
         f"disp_pred={predicted:.5g};disp_meas={measured:.5g}"
         f"({ratio:.2f}x);envelope_calibrated={calibrated}")
    if not recovers:
        # same CI contract as dropout_recovers: losing the resize
        # recovery property must fail the PR, not just flip a field in
        # the JSON artifact
        raise SystemExit(
            f"elastic run does NOT recover: final loss {loss_el:.6f} "
            f"vs fixed-M {loss_fixed:.6f} (budget 5%)")
    if not calibrated:
        raise SystemExit(
            f"post-resize dispersion prediction is OFF: predicted "
            f"{predicted:.6g} vs measured {measured:.6g} "
            f"({ratio:.2f}x, budget [0.5, 2.0])")
    return row


def run(tiny: bool = False, workers_override: int | None = None,
        save_json: bool | None = None):
    steps = 64 if tiny else STEPS
    phase_lens = (1, 8) if tiny else PHASE_LENS
    deep_phase_lens = (8,) if tiny else DEEP_PHASE_LENS
    worker_counts = (4,) if tiny else WORKER_COUNTS
    if workers_override:
        worker_counts = (workers_override,)
    dim, samples = (16, 256) if tiny else (DIM, SAMPLES)
    reps = 1 if tiny else 3
    if save_json is None:
        save_json = not tiny

    X, y, _ = convex_dataset("ls", samples, dim, sparsity=0.2, noise=0.1,
                             seed=0)
    Xn, yn = np.asarray(X), np.asarray(y)
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    w0 = {"w": jnp.zeros(dim)}

    results = []
    for workers in worker_counts:
        mesh = worker_mesh(workers)
        rng = np.random.default_rng(0)
        idx = rng.integers(0, samples, size=(steps, workers, 8))
        batches = [{"x": Xj[idx[t]], "y": yj[idx[t]]} for t in range(steps)]

        def stream():
            for t in range(steps):
                yield {"x": jnp.asarray(Xn[idx[t]]),
                       "y": jnp.asarray(yn[idx[t]])}

        for k in phase_lens:
            # small-K schedules still scan big blocks: averaging decisions
            # are per-step and on-device, so one compiled block may span
            # many averaging periods
            block = max(k, 64)
            tree_eng = make_engine(ls_mean_loss, k, flat=False)
            pr2_eng = make_engine(ls_mean_loss, k, fused=False)
            fused_eng = make_engine(ls_mean_loss, k)

            def staged(eng, data_fn, prefetch):
                # data_fn: factory — generators are consumed per run
                return lambda: eng.run(w0, data_fn(), num_workers=workers,
                                       seed=0, phase_len=block,
                                       prefetch=prefetch)

            def indexed(eng):
                return lambda: eng.run(
                    w0, DeviceDataset({"x": Xj, "y": yj}, workers,
                                      indices=idx),
                    num_workers=workers, seed=0, phase_len=block)

            row = {"workload": "ls", "workers": workers, "phase_len": k,
                   "steps": steps, "scan_unroll": 1}
            if not tiny:
                row["host_ms_per_step"] = time_run(
                    lambda: tree_eng.run_host(w0, batches,
                                              num_workers=workers, seed=0),
                    steps, reps=reps)
            row["tree_ms_per_step"] = time_run(
                staged(tree_eng, lambda: batches, False), steps, reps=reps)
            row["flat_staged_ms_per_step"] = time_run(
                staged(fused_eng, lambda: batches, False), steps, reps=reps)
            row["flat_prefetch_ms_per_step"] = time_run(
                staged(fused_eng, lambda: batches, True), steps, reps=reps)
            row["stream_sync_ms_per_step"] = time_run(
                staged(fused_eng, stream, False), steps, reps=reps)
            row["stream_prefetch_ms_per_step"] = time_run(
                staged(fused_eng, stream, True), steps, reps=reps)
            row["flat_indexed_ms_per_step"] = time_run(
                indexed(pr2_eng), steps, reps=reps)
            row["flat_fusedopt_ms_per_step"] = time_run(
                indexed(fused_eng), steps, reps=reps)
            if mesh is not None:
                sharded_eng = make_engine(ls_mean_loss, k, mesh=mesh)
                row["flat_sharded_ms_per_step"] = time_run(
                    indexed(sharded_eng), steps, reps=reps)
            row["speedup_flat_vs_tree"] = (row["tree_ms_per_step"] /
                                           row["flat_fusedopt_ms_per_step"])
            row["speedup_fusedopt_vs_flat"] = (
                row["flat_indexed_ms_per_step"] /
                row["flat_fusedopt_ms_per_step"])
            row["speedup_prefetch_vs_stack"] = (
                row["flat_staged_ms_per_step"] /
                row["flat_prefetch_ms_per_step"])
            row["speedup_stream_prefetch"] = (
                row["stream_sync_ms_per_step"] /
                row["stream_prefetch_ms_per_step"])
            if not tiny:
                row["speedup_vs_host"] = (row["host_ms_per_step"] /
                                          row["flat_fusedopt_ms_per_step"])
            results.append(row)
            emit(f"engine_ls_K{k}_M{workers}",
                 row["flat_fusedopt_ms_per_step"] * 1e3,
                 f"tree_ms/step={row['tree_ms_per_step']:.3f};"
                 f"fusedopt_ms/step="
                 f"{row['flat_fusedopt_ms_per_step']:.3f};"
                 f"flat_vs_tree={row['speedup_flat_vs_tree']:.2f}x;"
                 f"fusedopt_vs_flat="
                 f"{row['speedup_fusedopt_vs_flat']:.2f}x;"
                 f"prefetch_vs_stack="
                 f"{row['speedup_prefetch_vs_stack']:.2f}x")

        # deep multi-leaf Momentum workload: the fused-optimizer target
        dp = deep_params(dim)
        for k in deep_phase_lens:
            for unroll in ((4,) if tiny else (1, 4)):
                block = 256 if not tiny else 64
                pr2_eng = make_engine(deep_loss, k, fused=False,
                                      unroll=unroll)
                fused_eng = make_engine(deep_loss, k, unroll=unroll)

                def indexed_deep(eng):
                    return lambda: eng.run(
                        dp, DeviceDataset({"x": Xj, "y": yj}, workers,
                                          indices=idx),
                        num_workers=workers, seed=0, phase_len=block)

                row = {"workload": "deep", "workers": workers,
                       "phase_len": k, "steps": steps,
                       "scan_unroll": unroll,
                       "num_leaves": len(jax.tree.leaves(dp))}
                row["flat_indexed_ms_per_step"] = time_run(
                    indexed_deep(pr2_eng), steps, reps=reps)
                row["flat_fusedopt_ms_per_step"] = time_run(
                    indexed_deep(fused_eng), steps, reps=reps)
                if mesh is not None:
                    row["flat_sharded_ms_per_step"] = time_run(
                        indexed_deep(make_engine(deep_loss, k,
                                                 unroll=unroll, mesh=mesh)),
                        steps, reps=reps)
                row["speedup_fusedopt_vs_flat"] = (
                    row["flat_indexed_ms_per_step"] /
                    row["flat_fusedopt_ms_per_step"])
                results.append(row)
                emit(f"engine_deep_K{k}_M{workers}_u{unroll}",
                     row["flat_fusedopt_ms_per_step"] * 1e3,
                     f"indexed_ms/step="
                     f"{row['flat_indexed_ms_per_step']:.3f};"
                     f"fusedopt_ms/step="
                     f"{row['flat_fusedopt_ms_per_step']:.3f};"
                     f"fusedopt_vs_flat="
                     f"{row['speedup_fusedopt_vs_flat']:.2f}x")

    m_adapt = max(worker_counts)
    rng = np.random.default_rng(2)
    aidx = rng.integers(0, samples, size=(steps, m_adapt, 8))
    adaptive_row = bench_adaptive({"x": Xj, "y": yj}, aidx, m_adapt, steps)
    results.append(adaptive_row)

    rng = np.random.default_rng(3)
    tidx = rng.integers(0, samples, size=(steps, m_adapt, 8))
    topology_sweep = bench_topology({"x": Xj, "y": yj}, tidx, m_adapt,
                                    steps, tiny=tiny)
    results.extend(topology_sweep["rows"])

    rng = np.random.default_rng(4)
    xidx = rng.integers(0, samples, size=(steps, m_adapt, 8))
    compressed_row = bench_compressed({"x": Xj, "y": yj}, xidx, m_adapt,
                                      steps)
    results.append(compressed_row)

    rng = np.random.default_rng(5)
    fidx = rng.integers(0, samples, size=(steps, m_adapt, 8))
    faults_row = bench_faults({"x": Xj, "y": yj}, fidx, m_adapt, steps)
    results.append(faults_row)

    rng = np.random.default_rng(6)
    eidx = rng.integers(0, samples, size=(steps, m_adapt, 8))
    labels = np.digitize(yn, np.quantile(yn, [0.25, 0.5, 0.75]))
    elastic_row = bench_elastic({"x": Xj, "y": yj}, eidx, m_adapt, steps,
                                labels)
    results.append(elastic_row)

    sharder = bench_sharder(max(worker_counts), steps)
    emit("sharder_replacement", sharder["sharder_block_us"],
         f"loop_us={sharder['sharder_loop_us']:.0f};"
         f"block_us={sharder['sharder_block_us']:.0f};"
         f"speedup={sharder['sharder_speedup']:.1f}x")

    fused = [r["speedup_fusedopt_vs_flat"] for r in results
             if r["workload"] == "deep"]
    heavy = [r["speedup_flat_vs_tree"] for r in results
             if r["workload"] == "ls" and r["phase_len"] <= AVG_HEAVY_K]
    if heavy:
        print(f"min flat-vs-tree speedup at K<={AVG_HEAVY_K}: "
              f"{min(heavy):.2f}x")
    if fused:
        print(f"max fusedopt-vs-PR2-flat speedup (deep workload): "
              f"{max(fused):.2f}x")
    if save_json:
        # a small telemetry-enabled run next to the timing JSON: the CI
        # artifact a reader can render with python -m repro.telemetry.report
        tele_path = os.path.join(RESULTS_DIR, "bench_engine_telemetry.jsonl")
        tele_workers = worker_counts[0]
        rng = np.random.default_rng(7)
        tidx = rng.integers(0, samples, size=(steps, tele_workers, 8))
        tele_eng = dataclasses.replace(
            make_engine(ls_mean_loss, 8), telemetry=True)
        with JsonlSink(tele_path) as sink:
            sink.emit(run_meta_record(config={
                "workload": "ls", "workers": tele_workers,
                "steps": steps, "avg": "periodic", "phase_len": 8,
                "lr": 0.01, "momentum": 0.9, "optimizer": "momentum"}))
            tele_eng.run(w0, DeviceDataset({"x": Xj, "y": yj},
                                           tele_workers, indices=tidx),
                         num_workers=tele_workers, seed=0, phase_len=64,
                         sink=sink)
        print(f"telemetry log -> {tele_path}")
        save("bench_engine", {
            "run_meta": run_meta_record(),
            "workload": {"dim": dim, "samples": samples, "steps": steps,
                         "kind": "ls+deep", "optimizer": "momentum",
                         "deep_layers": DEEP_LAYERS,
                         "deep_width": DEEP_WIDTH},
            "devices": len(jax.devices()),
            "adaptive": adaptive_row,
            "topology": topology_sweep,
            "compressed": compressed_row,
            "faults": faults_row,
            "elastic": elastic_row,
            "rows": results, "sharder": sharder})
    return results


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--save", action="store_true",
                    help="write results/bench_engine.json even with --tiny")
    ap.add_argument("--workers", type=int, default=None,
                    help="override the worker-count sweep (CI smoke runs "
                         "--workers 8 under forced host device count to "
                         "exercise the sharded path)")
    args = ap.parse_args()
    run(tiny=args.tiny, workers_override=args.workers,
        save_json=args.save or not args.tiny)
