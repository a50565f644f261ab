"""Training CLI: local-SGD training of any assigned architecture.

On a CPU use ``--reduced``. On one TPU v5e chip the full published
width runs as is (``chip_smoke.py`` drives it: smollm-360m, 2 workers);
``--shard`` splits the worker axis over every local chip.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --reduced --steps 100 --workers 4 --avg periodic --phase-len 10

Returns (consensus params, history, final EngineState).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (load_engine_state, save_checkpoint,
                              save_engine_state)
from repro.configs import ARCHS, get_config
from repro.core import (AveragingSchedule, Compression, OuterOptimizer,
                        PhaseEngine, WIRE_FORMATS)
from repro.topology import KINDS as TOPOLOGY_KINDS
from repro.topology import Topology
from repro.data import token_stream
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_worker_mesh
from repro.models import init_params, lm_loss
from repro.optim import AdamW, Momentum
from repro.telemetry import (JsonlSink, make_record, profile_trace,
                             run_meta_record)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--avg", default="periodic",
                    choices=["oneshot", "minibatch", "periodic",
                             "stochastic", "hierarchical",
                             "adaptive_threshold", "adaptive_budget",
                             "adaptive_bytes"])
    ap.add_argument("--phase-len", type=int, default=10)
    ap.add_argument("--zeta", type=float, default=0.01)
    ap.add_argument("--disp-threshold", type=float, default=0.0,
                    help="adaptive_threshold: average when the running "
                         "EMA of the Eq. 4 worker dispersion crosses "
                         "this level (required > 0)")
    ap.add_argument("--disp-ema-beta", type=float, default=0.9,
                    help="adaptive schedules: dispersion EMA decay "
                         "(0 <= beta < 1)")
    ap.add_argument("--comm-budget", type=int, default=0,
                    help="adaptive_budget: max averaging events over "
                         "the budget horizon (required >= 1)")
    ap.add_argument("--budget-horizon", type=int, default=0,
                    help="adaptive_budget / adaptive_bytes: steps the "
                         "budget spans (default 0 -> --steps)")
    ap.add_argument("--comm-dtype", default="f32",
                    choices=list(WIRE_FORMATS),
                    help="wire precision of averaging/mixing events "
                         "(repro.core.compress): f32 ships the rows "
                         "uncompressed (bit-identical to no "
                         "compression); bf16/int8/one_bit quantize "
                         "them, int8/one_bit with an error-feedback "
                         "residual plane")
    ap.add_argument("--byte-budget", type=int, default=0,
                    help="adaptive_bytes: max bytes ONE worker puts on "
                         "the wire over the budget horizon (required "
                         ">= the cost of one event at the chosen "
                         "topology x --comm-dtype)")
    ap.add_argument("--error-feedback", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="carry the error-feedback residual plane "
                         "(required for int8/one_bit wire formats; "
                         "--no-error-feedback is only valid for bf16)")
    ap.add_argument("--topology", default=None,
                    choices=list(TOPOLOGY_KINDS),
                    help="mixing topology for the averaging events "
                         "(repro.topology): every event becomes one "
                         "doubly-stochastic W @ plane mix over this "
                         "communication graph; 'full' is bit-identical "
                         "to the default mean, 'groups' to the "
                         "inner-groups block mean")
    ap.add_argument("--topology-groups", type=int, default=2,
                    help="--topology groups: number of block-diagonal "
                         "worker groups (must divide --workers)")
    ap.add_argument("--inner-groups", type=int, default=2,
                    help="hierarchical averaging: number of inner worker "
                         "groups (must divide --workers)")
    ap.add_argument("--outer-phase-len", type=int, default=0,
                    help="hierarchical averaging: all-worker period "
                         "(default 0 -> 8 x --phase-len)")
    ap.add_argument("--optimizer", default="momentum",
                    choices=["momentum", "adamw"])
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--outer-momentum", type=float, default=0.0,
                    help=">0 enables the beyond-paper DiLoCo-style outer "
                         "optimizer at averaging steps")
    ap.add_argument("--scan-unroll", type=int, default=1,
                    help="lax.scan unroll for the phase engine (0 = full "
                         "unroll; speeds up compute-heavy bodies on CPU)")
    ap.add_argument("--tree-engine", action="store_true",
                    help="carry the params pytree through the phase scan "
                         "instead of the default flat (M, P) plane "
                         "(PR 1 baseline path)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="stage phase blocks synchronously instead of via "
                         "the double-buffered prefetch thread")
    ap.add_argument("--no-fused-opt", action="store_true",
                    help="disable the flat-native fused optimizer planes "
                         "(PR 2 behavior: per-step pack/unpack around the "
                         "tree-mapped optimizer)")
    ap.add_argument("--shard", action="store_true",
                    help="shard the workers (each leaf's worker axis) "
                         "over the available devices via shard_map, "
                         "worker means psum'd across them (on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first)")
    ap.add_argument("--faults", default=None,
                    help="deterministic fault script (repro.faults): "
                         "comma-separated kind:m=<row>@t=<step> events, "
                         "e.g. 'crash:m=3@t=100,rejoin:m=3@t=200' — "
                         "crashed rows drop out of every update and "
                         "averaging event, rejoining rows warm-start "
                         "from the alive consensus")
    ap.add_argument("--straggle-prob", type=float, default=0.0,
                    help="per-worker per-step probability of skipping "
                         "the local update (still receives the mix); "
                         "drawn from the deterministic fold_in stream, "
                         "so every engine path replays the identical "
                         "straggler pattern")
    ap.add_argument("--rejoin", type=int, default=0,
                    help="auto-rejoin every scripted crash N steps "
                         "later (crashes with a later scripted event "
                         "for the same worker are left alone)")
    ap.add_argument("--shrink-at", action="append", default=[],
                    metavar="STEP:M'",
                    help="elastic membership (repro.elastic): shrink "
                         "the live worker plane to M' rows before STEP "
                         "runs — the dropped rows' memory, compute and "
                         "collective bandwidth are actually freed "
                         "(repeatable; composes with --grow-at)")
    ap.add_argument("--grow-at", action="append", default=[],
                    metavar="STEP:M'",
                    help="elastic membership: grow the live worker "
                         "plane to M' rows before STEP runs; new rows "
                         "warm-start from the mixing-cohort consensus "
                         "with optimizer planes zeroed (repeatable)")
    ap.add_argument("--rejoin-curriculum", type=int, default=0,
                    help="solo steps a rejoined or grown worker trains "
                         "before its iterate re-enters averaging (it "
                         "updates locally but is masked out of every "
                         "mix, the loss and the dispersion)")
    ap.add_argument("--straggle-aware", action="store_true",
                    help="adaptive schedules only: discount the "
                         "measured dispersion by the fraction of the "
                         "mixing cohort that actually updated, so "
                         "straggler-widened dispersion does not "
                         "trigger spurious averaging events")
    ap.add_argument("--non-iid-alpha", type=float, default=0.0,
                    help="> 0 enables Dirichlet(alpha) label-skewed "
                         "(non-IID) worker shards for dataset-backed "
                         "runs; the synthetic token stream has no "
                         "labels, so this CLI only validates and "
                         "records the setting")
    ap.add_argument("--impl", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write structured run telemetry to this JSONL "
                         "file (repro.telemetry): a run_meta header, "
                         "one phase_metrics record per compiled phase "
                         "(flushed from the on-device accumulator with "
                         "the phase's single trace fetch), plus "
                         "averaging/fault/resize/checkpoint events — "
                         "render with python -m repro.telemetry.report")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the run into "
                         "this directory (TensorBoard-loadable)")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--resume", default=None,
                    help="path of a full-EngineState checkpoint "
                         "(--checkpoint writes <path>.state) to resume "
                         "from; --steps counts additional steps")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.avg == "hierarchical":
        if args.inner_groups < 1 or args.workers % args.inner_groups:
            ap.error(f"--workers ({args.workers}) must be divisible by "
                     f"--inner-groups ({args.inner_groups})")
        outer_len = args.outer_phase_len or args.phase_len * 8
        if args.phase_len >= outer_len:
            # every multiple of the outer period wins the decision, so an
            # inner period >= the outer one silently never (or only
            # degenerately) inner-averages — refuse at parse time
            ap.error(f"--avg hierarchical needs the inner period "
                     f"(--phase-len, {args.phase_len}) < the outer period "
                     f"(--outer-phase-len, {outer_len}); as given it "
                     "would never inner-average")
    if args.avg == "stochastic" and not 0.0 < args.zeta <= 1.0:
        ap.error(f"--avg stochastic needs 0 < --zeta <= 1, got "
                 f"{args.zeta} (other schedules ignore --zeta)")
    if args.avg == "adaptive_threshold" and args.disp_threshold <= 0.0:
        ap.error("--avg adaptive_threshold needs --disp-threshold > 0 "
                 "(the Eq. 4 dispersion level that triggers averaging)")
    if args.avg == "adaptive_budget":
        horizon = args.budget_horizon or args.steps
        if args.comm_budget < 1:
            ap.error("--avg adaptive_budget needs --comm-budget >= 1")
        if args.comm_budget > horizon:
            ap.error(f"--comm-budget ({args.comm_budget}) cannot exceed "
                     f"the budget horizon ({horizon} steps): at most one "
                     "averaging event per step")
    if args.avg == "adaptive_bytes" and args.byte_budget < 1:
        ap.error("--avg adaptive_bytes needs --byte-budget >= 1 (bytes "
                 "one worker may put on the wire over the horizon)")
    try:
        # int8/one_bit without the error-feedback residual diverge —
        # Compression refuses the combination; surface its message at
        # parse time instead of deep inside engine setup
        compression = Compression(args.comm_dtype,
                                  error_feedback=args.error_feedback)
    except ValueError as e:
        ap.error(f"--comm-dtype {args.comm_dtype}: {e}")
    if args.outer_momentum > 0 and args.comm_dtype != "f32":
        ap.error(f"--outer-momentum steps on the exact consensus mean, "
                 f"which a {args.comm_dtype} wire never forms — use "
                 "--comm-dtype f32 or drop the outer optimizer")
    faults = None
    if args.faults or args.straggle_prob > 0:
        from repro.faults import FaultPlan
        if not 0.0 <= args.straggle_prob <= 1.0:
            ap.error(f"--straggle-prob must be in [0, 1], got "
                     f"{args.straggle_prob}")
        if args.rejoin < 0:
            ap.error(f"--rejoin must be >= 0, got {args.rejoin}")
        try:
            # FaultPlan validates eagerly: rows in [0, workers), steps
            # >= 1, crash/rejoin alternation per worker (a rejoin
            # needs a prior crash), never-all-dead — surface its
            # message at parse time instead of deep inside a trace
            faults = FaultPlan.parse(
                args.faults or "", args.workers,
                straggle_prob=args.straggle_prob,
                rejoin_after=args.rejoin,
                rejoin_curriculum=max(args.rejoin_curriculum, 0))
        except ValueError as e:
            ap.error(f"--faults: {e}")
        if args.outer_momentum > 0:
            ap.error("--outer-momentum steps on the full-membership "
                     "consensus mean, which a faulty run never forms — "
                     "drop --faults/--straggle-prob or the outer "
                     "optimizer")
    elif args.rejoin:
        ap.error("--rejoin without --faults has no crash to rejoin "
                 "from")
    if args.rejoin_curriculum < 0:
        ap.error(f"--rejoin-curriculum must be >= 0, got "
                 f"{args.rejoin_curriculum}")
    if args.straggle_aware:
        if args.avg not in ("adaptive_threshold", "adaptive_budget",
                            "adaptive_bytes"):
            ap.error(f"--straggle-aware discounts the dispersion fed to "
                     f"the adaptive schedules; --avg {args.avg} never "
                     "consumes dispersion — use an adaptive_* schedule "
                     "or drop the flag")
        if args.straggle_prob <= 0.0:
            ap.error("--straggle-aware needs --straggle-prob > 0 — "
                     "with no stragglers there is nothing to discount")
    elastic = None
    if args.shrink_at or args.grow_at:
        from repro.elastic import ElasticPlan
        try:
            # ElasticPlan.parse validates eagerly: step:M' syntax,
            # strictly increasing steps >= 2, shrinks shrink and grows
            # grow relative to the running membership
            elastic = ElasticPlan.parse(
                args.workers, shrink_at=args.shrink_at,
                grow_at=args.grow_at,
                curriculum=args.rejoin_curriculum)
        except ValueError as e:
            ap.error(f"--shrink-at/--grow-at: {e}")
        if args.outer_momentum > 0:
            ap.error("--outer-momentum steps on a fixed-membership "
                     "consensus mean, which an elastic run never keeps "
                     "— drop --shrink-at/--grow-at or the outer "
                     "optimizer")
        for m in elastic.sizes():
            # every membership the run passes through must satisfy the
            # same topology / inner-groups constraints as the initial M
            if args.avg == "hierarchical" and m % args.inner_groups:
                ap.error(f"resize target M'={m} is not divisible by "
                         f"--inner-groups ({args.inner_groups}) — "
                         "hierarchical averaging needs every membership "
                         "the run passes through to split evenly")
            if args.topology and m != args.workers:
                try:
                    Topology.build(args.topology, m,
                                   groups=args.topology_groups)
                except ValueError as e:
                    ap.error(f"resize target M'={m} is incompatible "
                             f"with --topology {args.topology}: {e}")
    elif args.rejoin_curriculum and not (faults and faults.has_rejoin):
        ap.error("--rejoin-curriculum without --grow-at or a rejoin "
                 "fault event has no worker to run a curriculum for")
    if args.non_iid_alpha < 0:
        ap.error(f"--non-iid-alpha must be >= 0, got "
                 f"{args.non_iid_alpha}")
    topology = None
    if args.topology:
        # invalid topology/worker-count combinations (ring needs M >= 3,
        # torus a composite M, gossip_pairs an even M, ...) surface here
        # at parse time with the builders' actionable messages instead
        # of deep inside a trace
        try:
            topology = Topology.build(args.topology, args.workers,
                                      groups=args.topology_groups)
        except ValueError as e:
            ap.error(f"--topology {args.topology}: {e}")
        if args.outer_momentum > 0 and args.topology != "full":
            ap.error(f"--outer-momentum steps on the consensus mean, "
                     f"which --topology {args.topology} never forms — "
                     "use --topology full or drop the outer optimizer")

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.reduced:
        cfg = dataclasses.replace(cfg, dtype="float32")
    print(f"[train] {cfg.name}: {cfg.num_params()/1e6:.1f}M params, "
          f"{args.workers} workers, avg={args.avg}")

    if args.avg == "adaptive_bytes":
        # one event's wire cost at this topology x precision: a budget
        # below it silently never averages — refuse up front
        from repro.topology import comm_bytes
        event_cost = comm_bytes(topology or Topology.full(args.workers),
                                1, int(cfg.num_params()), args.comm_dtype)
        if args.byte_budget < event_cost:
            ap.error(f"--byte-budget ({args.byte_budget}) is below the "
                     f"cost of ONE averaging event at this configuration "
                     f"({event_cost} B/worker: "
                     f"{args.topology or 'full'} topology, "
                     f"{args.comm_dtype} wire, "
                     f"{int(cfg.num_params())} params) — the schedule "
                     "would never fire")

    # after every argument check: a refused command line compiles nothing
    enable_compile_cache()
    params = init_params(cfg, jax.random.PRNGKey(args.seed))

    def loss_fn(p, batch, rng):
        return lm_loss(cfg, p, batch, impl=args.impl)

    opt = (Momentum(lr=args.lr, mu=0.9) if args.optimizer == "momentum"
           else AdamW(lr=args.lr))
    sch = AveragingSchedule(
        kind=args.avg, phase_len=args.phase_len, zeta=args.zeta,
        inner_phase_len=args.phase_len,
        outer_phase_len=args.outer_phase_len or args.phase_len * 8,
        # only hierarchical consumes inner groups, but the lax.switch
        # traces the inner branch for every kind — a non-dividing
        # (dead) group count would still fail the reshape under trace
        inner_groups=(args.inner_groups if args.avg == "hierarchical"
                      else 1),
        disp_threshold=args.disp_threshold,
        disp_ema_beta=args.disp_ema_beta,
        comm_budget=args.comm_budget,
        byte_budget=args.byte_budget,
        budget_horizon=args.budget_horizon or args.steps,
        straggle_aware=args.straggle_aware)
    outer = (OuterOptimizer(lr=1.0, momentum=args.outer_momentum)
             if args.outer_momentum > 0 else None)
    mesh = None
    if args.shard:
        mesh = make_worker_mesh(args.workers)
        shards = mesh.shape["data"]
        print(f"[train] sharding {args.workers} workers over {shards} "
              f"devices ({args.workers // shards} rows/shard)")
    sink = None
    if args.telemetry:
        sink = JsonlSink(args.telemetry)
        sink.emit(run_meta_record(config={
            "arch": args.arch, "workers": args.workers,
            "steps": args.steps, "avg": args.avg,
            "phase_len": args.phase_len, "lr": args.lr,
            "optimizer": args.optimizer,
            "momentum": 0.9 if args.optimizer == "momentum" else 0.0,
            "topology": args.topology,
            "spectral_gap": (topology.spectral_gap
                             if topology is not None else None),
            "comm_dtype": args.comm_dtype, "seed": args.seed}))
        print(f"[train] telemetry -> {args.telemetry}")
    engine = PhaseEngine(loss_fn, opt, sch, outer=outer,
                         scan_unroll=args.scan_unroll or True,
                         flat=not args.tree_engine,
                         fused_opt=not args.no_fused_opt,
                         mesh=mesh,
                         topology=topology, compression=compression,
                         faults=faults, telemetry=sink is not None)
    if faults is not None and not faults.is_trivial:
        crashes = sum(ev.kind == "crash" for ev in faults.events)
        rejoins = sum(ev.kind == "rejoin" for ev in faults.events)
        print(f"[train] faults: {crashes} crash / {rejoins} rejoin "
              f"events, straggle_prob={faults.straggle_prob}")
    if topology is not None:
        print(f"[train] topology={topology.kind} "
              f"(spectral gap {topology.spectral_gap:.3f}, "
              f"{topology.comm_degree:.1f} msgs/worker/event)")
    if not compression.is_identity:
        print(f"[train] wire={compression.wire} "
              f"(error_feedback={compression.error_feedback})")

    # per-worker independent data streams (paper §3.2: distinct
    # shuffles); under an elastic plan a row keeps its stream across
    # resizes (row indices are stable identities), so a re-grown worker
    # continues where it left off instead of replaying data
    streams = {}

    def stream(i):
        if i not in streams:
            streams[i] = token_stream(cfg.vocab_size, args.batch,
                                      args.seq, seed=args.seed * 131 + i)
        return streams[i]

    def batches(m, k):
        for _ in range(k):
            toks = np.stack([next(stream(i)) for i in range(m)])
            yield {"tokens": jnp.asarray(toks)}

    resume_state = None
    at = 0
    if args.resume:
        if elastic is not None:
            import json
            with open(args.resume + ".json") as f:
                meta = json.load(f)
            at = int(meta["step"])
            saved_m = (meta.get("extra") or {}).get("num_workers")
            # a save at an exact resize boundary may hold either the
            # pre- or post-resize plane; the recorded row count picks
            # the matching segment's like-state
            from repro.elastic import segment_engine
            seg_eng, m = segment_engine(engine, elastic, at,
                                        at + args.steps)
            if saved_m is not None and int(saved_m) != m:
                seg_eng, m = segment_engine(engine, elastic, at + 1,
                                            at + args.steps)
            like = seg_eng.init(params, m, args.seed)
        else:
            like = engine.init(params, args.workers, args.seed)
        resume_state, at = load_engine_state(args.resume, like)
        print(f"[train] resuming from {args.resume} at step {at}")

    # a run shorter than the record period still records its last step
    record_every = min(10, args.steps)
    t0 = time.time()
    with profile_trace(args.profile_dir):
        if elastic is not None:
            from repro.elastic import run_elastic
            final, hist, state = run_elastic(
                engine, params, lambda m, t_start, k: batches(m, k),
                elastic, steps=at + args.steps, seed=args.seed,
                record_every=record_every, state=resume_state,
                return_state=True,
                sink=sink)
            for t, old_m, new_m in hist["resizes"]:
                kind = "shrink" if new_m < old_m else "grow"
                print(f"[train] {kind} {old_m} -> {new_m} workers "
                      f"before step {t}")
        else:
            final, hist, state = engine.run(
                params, batches(args.workers, args.steps),
                num_workers=args.workers, seed=args.seed,
                record_every=record_every, prefetch=not args.no_prefetch,
                state=resume_state, return_state=True, sink=sink)
    dt = time.time() - t0
    if args.profile_dir:
        print(f"[train] profiler trace -> {args.profile_dir}")
    losses = hist["loss"]
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({dt / args.steps * 1e3:.0f} ms/step), "
          f"{hist['averages']} averaging ops")
    if losses:
        print(f"[train] loss {losses[0][1]:.4f} -> {losses[-1][1]:.4f}")
    if hist["dispersion"]:
        print(f"[train] final pre-average worker dispersion: "
              f"{hist['dispersion'][-1][1]:.3e}")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, final, step=int(state.step))
        save_engine_state(args.checkpoint + ".state", state,
                          elastic=elastic is not None)
        print(f"[train] saved consensus model to {args.checkpoint} "
              f"(+ resumable EngineState at {args.checkpoint}.state)")
        if sink is not None:
            from repro.checkpoint.io import ENGINE_STATE_VERSION
            sink.emit(make_record(
                "checkpoint_event", step=int(state.step),
                path=args.checkpoint + ".state",
                layout_version=ENGINE_STATE_VERSION))
    if sink is not None:
        sink.close()
    return final, hist, state


if __name__ == "__main__":
    main()
