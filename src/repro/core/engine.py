"""Compiled phase engine: K local steps + averaging as ONE jitted program.

The paper's algorithm is phase-structured — M workers each take K
independent SGD steps (Eq. 3), then their models are averaged — yet a
naive runtime dispatches one jitted call per step, decides averaging on
the host, and blocks on ``float()`` metric reads. This module compiles
the whole phase instead:

    run_phase(state, batches)          # ONE dispatch per phase
      └─ jax.lax.scan over K steps     # batches gathered on-device from
           └─ vmap over M workers      #   index blocks, or prefetched as
           └─ schedule.decision_state  #   a staged (K, M, ...) block
                none / inner / all averaging (+ outer optimizer)
      └─ loss + dispersion traces accumulated on-device, fetched once

All engine state (worker params, optimizer state, outer-optimizer state,
PRNG keys, step counter) lives in an :class:`EngineState` pytree that is
buffer-donated to ``run_phase``, so a phase updates parameters in place.
Averaging decisions — including the stochastic schedule's Bernoulli
draws — are pure functions of a single PRNG key and the step counter
(``fold_in(key, step)``), so runs are bitwise reproducible and resumable
from a checkpointed ``EngineState``.

Two device-residency layers sit on top of the PR 1 scan:

- **Flat parameter plane** (one device off the TPU, :func:`carry_for`):
  inside a phase the scan carries the workers as one contiguous
  ``(M, P)`` float32 plane (:class:`repro.core.flat.FlatSpec`; bit-exact
  pack/unpack), so every averaging event is a single fused pass — worker
  mean (global or per-group), Eq. 4 dispersion, broadcast, and the
  outer-optimizer momentum step — instead of 3–4 params-pytree
  traversals (``repro.kernels.avg_disp`` on TPU, its jnp twin on CPU).
  A TPU phase and every shard of a mesh carry the leaves instead: on a
  TPU the unpack and pack around every step cost more than the plane
  saves, and a mesh runs one phase body for every shard.
  Trees with dtypes that have no exact float32 image take the leaf
  (tree) path too.
  :meth:`PhaseEngine.run` packs the state into that plane form once
  (:meth:`PhaseEngine.start_state`) and carries the planes from phase
  to phase, so a full-width model never holds a tree copy of its
  params beside the planes during a phase.
- **On-device data plane**: :meth:`run` accepts a
  :class:`repro.data.pipeline.DeviceDataset` — the dataset lives on
  device, the driver ships (K, M, B) int32 index blocks, and the scan
  body gathers batches with ``jnp.take`` — zero per-phase host staging.
  Streaming iterables are staged by a double-buffered
  :class:`repro.data.pipeline.Prefetcher` thread instead.

Schedules lower to on-device control flow as follows:

  - oneshot     : statically no averaging branch at all
  - minibatch   : the all-average is unconditionally fused into each step
  - periodic(K) : ``step % K == 0`` predicate under ``lax.switch``
  - stochastic  : ``bernoulli(fold_in(key, step), ζ)`` under ``lax.switch``
  - hierarchical: two modulo predicates select none / inner / all
  - adaptive_threshold / adaptive_budget: the fused step passes emit the
    Eq. 4 dispersion EVERY step; ``AveragingSchedule.decision_state`` —
    a pure transition on the :class:`repro.core.averaging.SchedState`
    carried in the scan and in :class:`EngineState` — turns it into the
    none / all decision under the same ``lax.switch``

Because the fused passes always measure the dispersion, the per-step
``dispersion`` trace is the true Eq. 4 diagnostic on EVERY step (it used
to read 0.0 between averaging events), in all four paths: flat-native,
flat, tree, and the host loop — and on a mesh, where an all-to-all of
each leaf's rows gives every shard 1/S of the columns to reduce, and
one scalar psum sums their squared deviations, once per step.

A :class:`repro.topology.Topology` generalizes the "all"-scope event
from the full mean to one doubly-stochastic mixing-matrix application
``plane <- W @ plane`` (ring / torus / hypercube / random gossip pairs /
disconnected), fused into the same passes; ``full`` and ``groups``
topologies lower to the existing mean / block-mean code bit-exactly.

Names on the profiler's clock (docs/TELEMETRY.md): each part of the
phase step runs under one ``jax.named_scope`` — ``engine.batch`` (step
counter, rng splits, batch fetch, fault transition), ``engine.unpack``,
``engine.fwd_bwd``, ``engine.pack``, ``engine.update`` (optimizer
update, dispersion, schedule decision) and ``engine.average`` (the
event) — so every op of the compiled phase carries at most one of them
in its ``op_name`` (on a mesh the dispersion's exchange and reduction
run under ``engine.dispersion``, nested in ``engine.update``).
:meth:`PhaseEngine.run` marks its host loop with
``jax.profiler.TraceAnnotation`` spans ``engine.next_block``,
``engine.dispatch``, ``engine.fetch`` and ``engine.record`` (and
``engine.stage`` on the staging thread), each with ``step=`` the
phase's first step. Both are inert unless a profiler trace is running.

:meth:`PhaseEngine.run` is the production driver (one compiled dispatch
per phase); :meth:`PhaseEngine.run_host` keeps the legacy per-step
host-driven loop — same numerics, same decision stream — as the baseline
for `benchmarks/bench_engine.py` and the equivalence tests.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from functools import cached_property, partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.averaging import (AveragingSchedule, OuterOptimizer,
                                  SchedState, average_inner,
                                  worker_dispersion)
from repro.core.compress import Compression, encode_decode, row_uniforms
from repro.core.flat import FlatOptSpec, FlatSpec
from repro.data.pipeline import DeviceDataset, Prefetcher
from repro import faults as faults_mod
from repro.faults import FaultPlan, FaultState
from repro.kernels.avg_disp import (avg_disp, avg_disp_outer,
                                    compressed_mix, mix_disp)
from repro.kernels.opt_step import opt_step
from repro.telemetry import metrics as tele_metrics
from repro.telemetry.events import init_history, make_record
from repro.kernels.ref import (avg_disp_outer_ref, avg_disp_ref,
                               compressed_avg_ref, compressed_mix_ref,
                               mix_disp_ref, opt_step_ref,
                               plane_average_ref, round_to_codes, widen)
from repro.topology import MIX_KINDS, Topology, comm_bytes, mix_tree

# a host span on the profiler's clock; inert while no trace is recorded
span = jax.profiler.TraceAnnotation


# --------------------------------------------------------------------------
# Worker-axis utilities (leading axis = worker index on every leaf)
# --------------------------------------------------------------------------

def replicate(tree, num_workers: int):
    """Give every leaf a leading worker axis (all workers start at w_0,
    as the paper prescribes)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_workers,) + x.shape), tree)


def _as_leaves(mean, tree):
    """An f32 worker-mean tree rounded to ``tree``'s leaf dtypes."""
    return jax.tree.map(lambda g, x: g.astype(x.dtype), mean, tree)


def _column_view(x, shards: int):
    """A worker-rows-first leaf as the shards split its columns: along
    its first axis after the rows where ``shards`` divides it (the leaf
    keeps its layout), else flattened and padded with zero columns,
    which deviate by nothing."""
    if x.ndim > 1 and x.shape[1] % shards == 0:
        return x
    x = x.reshape(x.shape[0], -1)
    return jnp.pad(x, ((0, 0), (0, -x.shape[1] % shards)))


def unreplicate(tree):
    return jax.tree.map(lambda x: x[0], tree)


def consensus(tree):
    """The paper's final estimate: the average of the workers."""
    return jax.tree.map(lambda x: jnp.mean(x, axis=0), tree)


def tree_stack(trees):
    """Stack a list of per-step batches into one (K, ...) device block."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def make_worker_step(loss_fn: Callable, optimizer) -> Callable:
    """The ONE vmapped local-SGD step (paper Eq. 3) every runtime path
    shares: LocalSGD, the phase engine's scan body, and the launch/dryrun
    train steps.

    loss_fn(params, batch, rng) -> (loss, aux); optimizer is an
    init/apply pair from repro.optim. Returns
    step_fn(worker_params, opt_state, batch, step, rngs=None)
    -> (worker_params, opt_state, per-worker losses, aux).
    """
    def one(params, ostate, batch, rng, step):
        with jax.named_scope("engine.fwd_bwd"):
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, rng)
        with jax.named_scope("engine.update"):
            params, ostate = optimizer.apply(params, grads, ostate, step)
        return params, ostate, loss, aux

    def step_fn(worker_params, opt_state, batch, step, rngs=None):
        if rngs is None:  # rng-free losses (launch/dryrun abstract paths)
            return jax.vmap(lambda p, s, b: one(p, s, b, None, step))(
                worker_params, opt_state, batch)
        return jax.vmap(lambda p, s, b, r: one(p, s, b, r, step))(
            worker_params, opt_state, batch, rngs)

    return step_fn


def carry_for(platform: str, sharded: bool) -> str:
    """The carry a phase takes: ``"plane"`` (the (M, P) f32 plane) where
    unpacking a plane row into the leaves is free, else ``"leaf"`` (the
    leaves in their own dtypes).

    On a TPU a flat row and a leaf are tiled differently, so a step on
    the plane pays a relayout and a cast each way — every row unpacked
    for the forward pass, the gradients packed for the update — which
    outweighs the fused update and event it buys. On other platforms
    reshaping a row is a bitcast, so one device keeps the plane there.
    A mesh carries leaves on every platform: each shard runs the one
    phase body, stepping its own rows."""
    return "leaf" if sharded or platform == "tpu" else "plane"


def make_plane_step(loss_fn: Callable, spec: FlatSpec) -> Callable:
    """The flat-native local step: losses and gradients straight on the
    (M, P) plane. Each worker row is unpacked to a params *view*
    (``FlatSpec.unpack1``) only inside the traced loss — the plane is
    the only carried representation — and the per-leaf gradients come
    back as one plane row via a single ``pack1`` concatenation (the
    efficient transpose of the unpack: differentiating through the row
    slices instead would build each leaf's cotangent as a full-width
    pad-and-add).

    Returns grads_fn(plane, batch, rngs) -> (losses (M,), aux,
    grad plane (M, P) f32). ``rngs=None`` supports rng-free losses
    (launch/dryrun abstract paths)."""
    def one(row, batch, rng):
        with jax.named_scope("engine.unpack"):
            params = spec.unpack1(row)
        with jax.named_scope("engine.fwd_bwd"):
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, rng)
        with jax.named_scope("engine.pack"):
            gplane = spec.pack1(grads)
        return loss, aux, gplane

    def grads_fn(plane, batch, rngs=None):
        if rngs is None:
            return jax.vmap(lambda r, b: one(r, b, None))(plane, batch)
        return jax.vmap(one)(plane, batch, rngs)

    return grads_fn


class EngineState(NamedTuple):
    """Everything a phase consumes and produces; donated to run_phase."""
    worker_params: Any   # leaves (M, ...)
    opt_state: Any       # leaves (M, ...)
    outer_state: Any     # (prev_avg, velocity) trees, or () without outer
    key: Any             # data-rng key, split once per step
    dec_key: Any         # schedule-decision root key (constant)
    step: Any            # int32 scalar, steps completed
    sched: Any = ()      # SchedState (adaptive-schedule carry), or ()
    resid: Any = ()      # (M, P) f32 error-feedback residual plane
    #                    # (compressed communication), or ()
    fault: Any = ()      # FaultState (alive/staleness rows, fault
    #                    # injection — repro.faults), or ()


@dataclass(frozen=True, eq=False)  # eq=False: hash by identity for jit
class PhaseEngine:
    """loss_fn(params, batch, rng) -> (loss, aux); optimizer from
    repro.optim (init/apply pair).

    ``scan_unroll`` is forwarded to ``lax.scan``: XLA:CPU runs while-loop
    bodies with reduced intra-op threading, so compute-heavy losses (e.g.
    convolutions) on CPU backends benefit from ``scan_unroll=True`` (full
    unroll: longer compiles, per-step speed of eager dispatch). On real
    accelerator meshes leave the default rolled scan.

    ``flat`` (default) lets a phase carry the (M, P) flat plane where
    that is free (:func:`carry_for`): on one device off a TPU. A TPU
    phase, every mesh, and every tree FlatSpec cannot embed carry the
    leaves instead — params in their dtypes, optimizer state as its own
    tree, the tree optimizer per leaf, the event by ``_tree_average``.
    ``flat=False`` carries leaves on one device too. With
    ``fused_opt`` (default) and an optimizer that speaks the plane
    protocol (SGD/Momentum/AdamW: ``plane_kind``/``plane_hypers``/
    ``plane_scalars`` + a ``FlatOptSpec``-alignable state), the scan is
    *flat-native*: optimizer state rides as extra (M, P) planes, grads
    come from one vjp through the unpacked view, and every step is one
    fused ``opt_step`` pass (update + optional average + Eq. 4
    dispersion + broadcast) — zero per-step pack/unpack.
    ``kernel_impl`` picks the fused implementation: "auto" (jnp
    reference on CPU; the Pallas kernels, compiled by Mosaic, on a TPU
    — ``chip_smoke.py`` checks that a v5e phase holds them), "ref", or
    "pallas".

    ``mesh`` shards the phase over a device mesh via ``shard_map``: the
    worker axis M of every leaf is split over the mesh's worker axes
    (``shard_axes``; defaults to ("pod","data") ∩ mesh axes), each
    shard runs :meth:`_phase`'s one body on its own rows, carrying the
    leaves; the per-step dispersion is reduced by columns after
    all-to-alls of the rows (:meth:`_sharded_dispersion`), and the
    all-mean event gathers that mean. ``collective`` names the mesh's
    worker reduction and takes only ``"psum"``.

    ``topology`` (a :class:`repro.topology.Topology`) generalizes the
    "all"-scope averaging event from the full worker mean to one
    application of the topology's doubly-stochastic mixing matrix,
    ``plane <- W @ plane`` — each worker keeps its own mixed row.
    ``full`` and ``groups`` lower to the existing fused mean /
    group-mean paths (bit-identical to running without a topology /
    to the ``inner_groups`` block mean); the sparse kinds (ring,
    torus, hypercube, gossip_pairs, disconnected) run the fused mix
    pass in every engine path, ``gossip_pairs`` sampling a fresh
    random matching per event as a pure function of (dec_key, step)
    — reproducible and checkpoint/resume-safe with no extra state.
    The outer optimizer steps on the consensus mean, which partial
    mixing never forms, so it requires ``full`` (or no) topology.

    ``compression`` (a :class:`repro.core.compress.Compression`) sets
    the wire precision of every averaging/mixing event: the event
    operator acts on the quantized image ``q`` of the post-update
    plane, with an error-feedback residual carried as one more (M, P)
    plane in ``EngineState.resid`` (checkpoint layout v3). ``f32`` is
    the identity and lowers to the uncompressed paths bit-exactly; the
    quantizing formats require params FlatSpec can embed (every engine
    path encodes on the flat plane) and exclude the outer optimizer,
    whose consensus step needs the exact mean.

    ``faults`` (a :class:`repro.faults.FaultPlan`) makes worker
    failure a scenario axis: a :class:`repro.faults.FaultState`
    ``(alive, staleness)`` carry rides the scan like ``SchedState``
    (checkpoint layout v4), scripted crashes/rejoins are pure
    functions of the step and stochastic straggles of
    ``fold_in(dec_key, salt, step, row)``, so every path, shard and
    resume replays identical fault streams. Dead rows are masked out
    of every event (``faults.degraded_matrix`` renormalizes mixing
    matrices over the alive rows), stragglers skip their local update
    but still receive the event, rejoiners warm-start from the alive
    average with optimizer planes and residual rows zeroed, and the
    final estimate is the alive-worker consensus. A trivial plan (no
    events, zero straggle probability) lowers to the no-fault paths
    bit-exactly; the outer optimizer is excluded (its consensus step
    assumes a fixed membership).

    ``telemetry`` adds the on-device metrics plane
    (:mod:`repro.telemetry.metrics`): a fixed-layout f32 accumulator
    rides the scan carry — per-phase loss/dispersion sums and maxes,
    event counts, nominal ``topology.comm_bytes`` wire bytes, and
    alive/straggle occupancy from the fault streams — and is flushed
    to the host ONCE per phase with the existing trace fetch. The
    accumulator is created inside the phase (never part of
    ``EngineState`` or the checkpoint layout), it only READS values
    the step already computes, and the trained state never consumes
    it, so telemetry on vs off is bit-identical in every path.
    :meth:`run` flushes it into structured records when handed a
    ``sink`` (:class:`repro.telemetry.events.TelemetrySink`)."""
    loss_fn: Callable
    optimizer: Any
    schedule: AveragingSchedule
    outer: OuterOptimizer | None = None
    scan_unroll: int | bool = 1
    flat: bool = True
    kernel_impl: str = "auto"
    fused_opt: bool = True
    mesh: Any = None
    shard_axes: tuple = ()
    collective: str = "psum"
    topology: Topology | None = None
    compression: Compression | None = None
    faults: FaultPlan | None = None
    telemetry: bool = False

    def __post_init__(self):
        if self.collective != "psum":
            raise ValueError(
                f"collective={self.collective!r}: a mesh reduces its "
                "worker means by 'psum', the only collective; 'gather', "
                "which reproduced the one-device plane bit for bit, is "
                "gone")

    @cached_property
    def worker_step(self):
        return make_worker_step(self.loss_fn, self.optimizer)

    # ---- state -----------------------------------------------------------
    def _check_workers(self, num_workers: int):
        """``average_inner`` reshapes the worker axis into inner_groups
        contiguous groups; a non-dividing group count would surface
        mid-trace as an opaque reshape error — fail eagerly here, where
        M is first known."""
        g = self.schedule.inner_groups
        if self.schedule.kind == "hierarchical" and num_workers % g:
            raise ValueError(
                f"hierarchical inner averaging splits the worker axis "
                f"into inner_groups={g} contiguous groups, but "
                f"num_workers={num_workers} is not divisible by it — "
                "pick inner_groups dividing the worker count")
        t = self.topology
        if t is not None:
            if t.num_workers != num_workers:
                raise ValueError(
                    f"topology '{t.kind}' was built for "
                    f"{t.num_workers} workers but the engine runs "
                    f"{num_workers} — build the Topology with the run's "
                    "worker count")
            if self.outer is not None and t.kind != "full":
                raise ValueError(
                    f"the outer optimizer steps on the consensus mean, "
                    f"which topology '{t.kind}' never forms (partial "
                    "mixing keeps per-worker rows) — use topology "
                    "'full', or drop the outer optimizer")
        if self._comp() is not None and self.outer is not None:
            raise ValueError(
                "the outer optimizer steps on the exact consensus mean, "
                f"which the '{self.compression.wire}' wire format never "
                "ships — use the f32 wire, or drop the outer optimizer")
        fp = self.faults
        if fp is not None:
            if fp.num_workers != num_workers:
                raise ValueError(
                    f"FaultPlan was built for {fp.num_workers} workers "
                    f"but the engine runs {num_workers} — build the plan "
                    "with the run's worker count")
            if self._faults() is not None and self.outer is not None:
                raise ValueError(
                    "the outer optimizer steps on the full-membership "
                    "consensus mean, which a fault plan (crashes / "
                    "stragglers changing the alive set) never preserves "
                    "— drop the outer optimizer, or run without faults")

    def _faults(self) -> FaultPlan | None:
        """The active (non-trivial) fault plan, or None. A plan with no
        events and zero straggle probability IS the no-fault engine —
        lowering it here keeps that configuration bit-exact by
        construction (mirrors ``_comp``'s f32 lowering)."""
        fp = self.faults
        if fp is None or fp.is_trivial:
            return None
        return fp

    def _comp(self) -> Compression | None:
        """The active (non-identity) compression, or None. The ``f32``
        wire IS the existing uncompressed path — lowering it here keeps
        that configuration bit-exact by construction."""
        c = self.compression
        if c is None or c.is_identity:
            return None
        return c

    def _check_compressible(self, worker_params):
        if self._comp() is not None and not FlatSpec.supports(worker_params):
            raise ValueError(
                "compressed communication encodes averaging events on "
                "the flat (M, P) plane, but this params tree has leaves "
                "FlatSpec cannot embed in float32 — use the f32 wire "
                "for such trees")

    def _mix_topology(self) -> Topology | None:
        """The topology whose events need the generic ``W @ plane``
        mix, or None when events lower to the existing fused mean /
        group-mean paths (no topology, ``full``, or ``groups`` — the
        block-diagonal W is exactly the ``inner_groups`` block mean)."""
        t = self.topology
        if t is None or t.kind not in MIX_KINDS:
            return None
        return t

    def _all_groups(self) -> int:
        """Group count of an "all"-scope mean event: 1 (global mean)
        unless the ``groups`` topology narrows it to its block mean."""
        t = self.topology
        if t is not None and t.kind == "groups":
            return t.groups
        return 1

    def _event_W(self, step, dec_key):
        """This event's mixing matrix (f32 (M, M)), or None when events
        take the mean path. Deterministic topologies embed W as a trace
        constant; ``gossip_pairs`` samples the per-event matching from
        ``fold_in`` on (dec_key, step) — the same pure-function recipe
        as the stochastic schedule, so every engine path, phase
        blocking, shard and checkpoint/resume replays identical
        matchings."""
        t = self._mix_topology()
        if t is None:
            return None
        return t.mixing_matrix(step, dec_key)

    def init(self, params, num_workers: int, seed: int = 0) -> EngineState:
        self._check_workers(num_workers)
        wp = replicate(params, num_workers)
        self._check_compressible(wp)
        opt_state = jax.vmap(self.optimizer.init)(wp)
        outer_state = ()
        if self.outer is not None:
            avg = consensus(wp)
            outer_state = (avg, self.outer.init(avg))
        resid = ()
        if self._comp() is not None:
            resid = jnp.zeros((num_workers, FlatSpec.of(wp).width),
                              jnp.float32)
        fault = ()
        if self._faults() is not None:
            fault = faults_mod.init_fault_state(num_workers)
        key, dec_key = jax.random.split(jax.random.PRNGKey(seed))
        return EngineState(wp, opt_state, outer_state, key, dec_key,
                           jnp.zeros((), jnp.int32),
                           self.schedule.init_sched_state(), resid, fault)

    def _sched_event_cost(self, p: int, num_workers: int):
        """The per-event bytes-per-worker cost the ``adaptive_bytes``
        schedule spends its budget in: comm_degree messages of one
        (P,) row at the wire precision. None for every other kind."""
        if self.schedule.kind != "adaptive_bytes":
            return None
        topo = self.topology or Topology.full(num_workers)
        wire = self.compression.wire if self.compression else "f32"
        return float(comm_bytes(topo, 1, p, wire))

    def _event_bytes(self, p: int, num_workers: int):
        """Telemetry pricing of one averaging event: (all-scope, inner)
        nominal wire bytes ONE worker ships — the same
        ``topology.comm_bytes`` currency the ``adaptive_bytes`` budget
        spends; inner (group-mean) events ship within-group traffic."""
        from repro.core.compress import wire_row_bytes
        topo = self.topology or Topology.full(num_workers)
        wire = self.compression.wire if self.compression else "f32"
        eb_all = float(comm_bytes(topo, 1, p, wire))
        g = max(self.schedule.inner_groups, 1)
        eb_inner = float(
            max(num_workers // g - 1, 0) * wire_row_bytes(p, wire))
        return eb_all, eb_inner

    def _tele_occupancy(self, fp, step, dec_key, num_workers: int):
        """Per-step (n_alive, n_straggle) for the metrics accumulator —
        pure full-plane functions of the scripted fault streams, so
        every path and every shard computes the identical scalars with
        no extra collective (constants without a fault plan)."""
        if fp is None:
            return jnp.float32(num_workers), jnp.float32(0.0)
        a_full = fp.alive_at(step)
        s_full = fp.straggle_mask(
            dec_key, step, jnp.arange(fp.num_workers, dtype=jnp.int32))
        return jnp.sum(a_full), jnp.sum(a_full * s_full)

    # ---- fused flat averaging -------------------------------------------
    def _use_pallas(self) -> bool:
        if self.kernel_impl == "pallas":
            return True
        if self.kernel_impl == "ref":
            return False
        return jax.default_backend() != "cpu"

    def _flat_average(self, plane, outer_c, scope: str, W=None,
                      alive=None):
        """ONE fused pass over the (M, P) plane: mean (global or
        per-group), Eq. 4 dispersion, broadcast, and — for the all-scope
        with an outer optimizer — the outer momentum step. With a
        mixing topology the all-scope event is the fused
        ``W @ plane`` gossip mix instead (no broadcast). ``alive``
        ((M,) f32, fault mode) masks every variant over the alive
        rows; the outer optimizer is excluded under faults."""
        pallas = self._use_pallas()
        if scope == "inner":
            groups = max(self.schedule.inner_groups, 1)
            if pallas:
                plane, disp = avg_disp(plane, groups=groups, alive=alive)
            else:
                plane, disp = avg_disp_ref(plane, groups=groups,
                                           alive=alive)
            return plane, outer_c, disp
        if W is not None:
            mix = mix_disp if pallas else mix_disp_ref
            plane, disp = mix(plane, W, alive=alive)
            return plane, outer_c, disp
        if self.outer is not None and outer_c != ():
            prev, vel = outer_c
            fused = avg_disp_outer if pallas else avg_disp_outer_ref
            plane, prev, vel, disp = fused(
                plane, prev, vel, lr=self.outer.lr,
                momentum=self.outer.momentum, nesterov=self.outer.nesterov)
            return plane, (prev, vel), disp
        groups = self._all_groups()
        if pallas:
            plane, disp = avg_disp(plane, groups=groups, alive=alive)
        else:
            plane, disp = avg_disp_ref(plane, groups=groups, alive=alive)
        return plane, outer_c, disp

    # ---- flat-native fused step (+ averaging) ---------------------------
    def _opt_spec(self, spec: FlatSpec, opt_state) -> FlatOptSpec | None:
        """The FlatOptSpec for flat-native scans, or None when the
        optimizer or its state can't ride the plane."""
        if not self.fused_opt or getattr(self.optimizer, "plane_kind",
                                         None) is None:
            return None
        return FlatOptSpec.of(spec, opt_state)

    def _event_uniforms(self, spec, m, step, dec_key):
        """The int8 stochastic-rounding uniforms for the ``m`` rows of
        a one-device event, or None for the deterministic formats (a
        shard keys its own by global row:
        :meth:`_psum_compressed_event`)."""
        comp = self._comp()
        if comp is None or not comp.stochastic:
            return None
        return row_uniforms(dec_key, step, jnp.arange(m, dtype=jnp.int32),
                            spec.width)

    def _compressed_plane_event(self, spec, plane, resid, scope: str,
                                step, dec_key, W=None, alive=None):
        """One compressed averaging/mixing event on the (M, P) plane:
        error-feedback encode of the post-update plane, the event
        operator (mean / group mean / ``W @``) on the decoded ``q``,
        residual update — fused (``kernels.avg_disp.compressed_mix``)
        on accelerators, the jnp twins on CPU. ``alive`` masks the
        event over the alive rows (dead rows ship no bytes and keep
        their stale residual). Returns (plane, residual, dispersion)."""
        comp = self._comp()
        codes = spec.rounding_codes()
        u = self._event_uniforms(spec, plane.shape[0], step, dec_key)
        kw = dict(wire=comp.wire, u=u, codes=codes,
                  error_feedback=comp.error_feedback, alive=alive)
        groups = (max(self.schedule.inner_groups, 1) if scope == "inner"
                  else self._all_groups())
        if self._use_pallas():
            return compressed_mix(
                plane, resid, mode=("mix" if W is not None else
                                    "group" if groups > 1 else "mean"),
                groups=groups, W=W, **kw)
        if W is not None:
            return compressed_mix_ref(plane, resid, W, **kw)
        return compressed_avg_ref(plane, resid, groups=groups, **kw)

    def _fused_step_average(self, spec, plane, gplane, planes, outer_c,
                            scalars, scope: str, W=None, resid=(),
                            step=None, dec_key=None, alive=None,
                            umask=None):
        """ONE fused pass: local optimizer update on the plane (+ state
        planes) and, per ``scope``, the averaging event — mean (global
        or per-group), Eq. 4 dispersion, broadcast, or (with a mixing
        topology) the ``W @ plane`` gossip mix. The all-scope with an
        outer optimizer chains the fused update into the fused
        avg+outer-momentum kernel (two passes total on those rare
        steps). With active compression the event acts on the encoded
        ``q`` of the post-update plane and the error-feedback
        ``resid`` plane updates in the same pass. Returns
        (plane, planes, outer_c, resid, disp)."""
        codes = spec.rounding_codes()
        kw = dict(kind=self.optimizer.plane_kind, codes=codes,
                  **self.optimizer.plane_hypers())
        if alive is not None:
            kw.update(alive=alive, umask=umask)
        fused = opt_step if self._use_pallas() else opt_step_ref
        comp = self._comp()
        if comp is not None and scope != "none":
            u = self._event_uniforms(spec, plane.shape[0], step, dec_key)
            groups = self._all_groups()
            mode = ("mix" if W is not None
                    else "group" if groups > 1 else "mean")
            plane, planes, resid, disp = fused(
                plane, gplane, planes, scalars, mode=mode, W=W,
                groups=groups, wire=comp.wire, resid=resid, u=u,
                error_feedback=comp.error_feedback, **kw)
            return plane, planes, outer_c, resid, disp
        if scope == "none":
            plane, planes, disp = fused(plane, gplane, planes, scalars,
                                        mode="none", **kw)
            return plane, planes, outer_c, resid, disp
        if W is not None:
            plane, planes, disp = fused(plane, gplane, planes, scalars,
                                        mode="mix", W=W, **kw)
            return plane, planes, outer_c, resid, disp
        if self.outer is not None and outer_c != ():
            plane, planes, _ = fused(plane, gplane, planes, scalars,
                                     mode="none", **kw)
            prev, vel = outer_c
            # mixed-dtype trees need the ref twin: the Pallas outer
            # kernel has no rounding-codes path
            if codes is None and self._use_pallas():
                of = avg_disp_outer
            else:
                of = partial(avg_disp_outer_ref, codes=codes)
            plane, prev, vel, disp = of(
                plane, prev, vel, lr=self.outer.lr,
                momentum=self.outer.momentum, nesterov=self.outer.nesterov)
            return plane, planes, (prev, vel), resid, disp
        groups = self._all_groups()
        plane, planes, disp = fused(plane, gplane, planes, scalars,
                                    mode="group" if groups > 1 else "mean",
                                    groups=groups, **kw)
        return plane, planes, outer_c, resid, disp

    def _plane_avg_event(self, spec, plane, outer_c, scope: str, W=None,
                         alive=None):
        """Averaging event alone (no optimizer update) on the plane —
        used by the switch branches of rare-averaging schedules, where
        the update is hoisted before the switch so XLA can fuse it with
        the gradient computation. Mixed-dtype trees round the broadcast
        mean / mixed rows (and the outer-optimizer's gradient target
        and update) through the leaf dtypes (``rounding_codes``),
        matching the tree operators' ``.astype``. ``alive`` masks the
        event over the alive rows (fault mode)."""
        codes = spec.rounding_codes()
        if codes is None:
            return self._flat_average(plane, outer_c, scope, W=W,
                                      alive=alive)
        if scope == "all" and W is not None:
            plane, disp = mix_disp_ref(plane, W, codes=codes, alive=alive)
            return plane, outer_c, disp
        if scope == "all" and self.outer is not None and outer_c != ():
            prev, vel = outer_c
            plane, prev, vel, disp = avg_disp_outer_ref(
                plane, prev, vel, lr=self.outer.lr,
                momentum=self.outer.momentum,
                nesterov=self.outer.nesterov, codes=codes)
            return plane, (prev, vel), disp
        groups = (max(self.schedule.inner_groups, 1)
                  if scope == "inner" else self._all_groups())
        plane, disp = plane_average_ref(plane, groups=groups, codes=codes,
                                        alive=alive)
        return plane, outer_c, disp

    def _flat_native_step(self, spec, plane, gplane, planes, outer_c,
                          scalars, step, sst, dec_key, resid=(),
                          fmask=None, dscale=None):
        """One flat-native step: fused update(+average) for the
        every-step schedules, update-then-switched-average for the rare
        ones. The fused update always emits the Eq. 4 dispersion of the
        post-update plane, which feeds the stateful schedule decision
        (``AveragingSchedule.decision_state``) and the per-step trace.
        With active compression the error-feedback ``resid`` plane
        threads through the event (untouched on non-event steps).
        ``fmask`` (fault mode) is the ``(mix, umask)`` pair for this
        step: rows outside ``umask`` skip the update, events and the
        dispersion mask over the mixing cohort ``mix`` (alive rows not
        inside a solo window). ``dscale`` is the straggle-aware
        dispersion discount forwarded to the schedule decision. Returns
        (plane, state planes, outer_c, resid, sched state, dispersion,
        decision code)."""
        sched = self.schedule
        alive, umask = fmask if fmask is not None else (None, None)
        ec = self._sched_event_cost(spec.width, plane.shape[0])
        if sched.kind == "minibatch":
            # the all-average is unconditional — fuse it into the update
            # pass (so its time is engine.update's); the (static)
            # decision still advances the sched state
            with jax.named_scope("engine.update"):
                plane, planes, outer_c, resid, disp = \
                    self._fused_step_average(
                        spec, plane, gplane, planes, outer_c, scalars,
                        "all", W=self._event_W(step, dec_key), resid=resid,
                        step=step, dec_key=dec_key, alive=alive,
                        umask=umask)
                code, sst = sched.decision_state(step, sst, disp, dec_key,
                                                 event_cost=ec,
                                                 disp_scale=dscale)
            return plane, planes, outer_c, resid, sst, disp, code
        with jax.named_scope("engine.update"):
            plane, planes, outer_c, resid, disp = self._fused_step_average(
                spec, plane, gplane, planes, outer_c, scalars, "none",
                resid=resid, alive=alive, umask=umask)
            code, sst = sched.decision_state(step, sst, disp, dec_key,
                                             event_cost=ec,
                                             disp_scale=dscale)
        if sched.kind == "oneshot":
            return plane, planes, outer_c, resid, sst, disp, code
        comp = self._comp()

        def none_branch(args):
            return args[0], args[1], args[2]

        def inner_branch(args):
            if comp is not None:
                pl_, r_, _ = self._compressed_plane_event(
                    spec, args[0], args[2], "inner", step, dec_key,
                    alive=alive)
                return pl_, args[1], r_
            return self._plane_avg_event(spec, args[0], args[1],
                                         "inner",
                                         alive=alive)[:2] + (args[2],)

        def all_branch(args):
            W = self._event_W(step, dec_key)
            if comp is not None:
                pl_, r_, _ = self._compressed_plane_event(
                    spec, args[0], args[2], "all", step, dec_key, W=W,
                    alive=alive)
                return pl_, args[1], r_
            return self._plane_avg_event(spec, args[0], args[1], "all",
                                         W=W,
                                         alive=alive)[:2] + (args[2],)

        with jax.named_scope("engine.average"):
            plane, outer_c, resid = jax.lax.switch(
                code, [none_branch, inner_branch, all_branch],
                (plane, outer_c, resid))
        return plane, planes, outer_c, resid, sst, disp, code

    # ---- tree-path averaging (flat=False, and FlatSpec fallback) ---------
    def _apply_all_average(self, wp, outer_state, num_workers):
        avg = consensus(wp)
        if self.outer is not None:
            prev_avg, vel = outer_state
            avg, vel = self.outer.apply(prev_avg, avg, vel)
            outer_state = (avg, vel)
        return replicate(avg, num_workers), outer_state

    def _tree_average(self, wp, outer_c, scope: str, num_workers: int,
                      W=None, alive=None):
        if alive is not None:
            disp = faults_mod.masked_dispersion_tree(
                wp, alive).astype(jnp.float32)
            if scope == "inner":
                wp = faults_mod.masked_average_all_tree(
                    wp, alive, groups=max(self.schedule.inner_groups, 1))
                return wp, outer_c, disp
            if W is not None:
                return faults_mod.masked_mix_tree(wp, W, alive), \
                    outer_c, disp
            g = self._all_groups()
            wp = faults_mod.masked_average_all_tree(wp, alive,
                                                    groups=max(g, 1))
            return wp, outer_c, disp
        disp = worker_dispersion(wp).astype(jnp.float32)
        if scope == "inner":
            return (average_inner(wp, max(self.schedule.inner_groups, 1)),
                    outer_c, disp)
        if W is not None:
            return mix_tree(wp, W), outer_c, disp
        g = self._all_groups()
        if g > 1:
            return average_inner(wp, g), outer_c, disp
        wp, outer_c = self._apply_all_average(wp, outer_c, num_workers)
        return wp, outer_c, disp

    # ---- the compiled phase ---------------------------------------------
    def _phase(self, state: EngineState, xs, fetch, layout=None,
               m_global: int | None = None):
        """Trace the whole phase: scan the K entries of ``xs``
        (pre-staged batches, or index blocks that ``fetch`` gathers
        on-device), averaging fused per the schedule. Returns the new
        state and per-step traces {loss, dispersion, avg_code} — the only
        host transfer a phase needs.

        ``m_global`` runs the body on ONE shard of a mesh holding
        ``m_global`` workers (under shard_map; a mesh carries leaves):
        the shard steps its own rows, and the Eq. 4 dispersion is
        reduced by columns: an all-to-all of each leaf's rows hands
        every shard all M workers' values of 1/S of the columns, and
        one scalar psum sums the squared deviations
        (:meth:`_sharded_dispersion`); the all-mean event all-gathers
        the shards' columns of that mean (:meth:`_psum_tree_average`).

        Three carries, picked per :meth:`carry` and optimizer support:
          flat-native — the state in plane form (:meth:`to_planes`,
            ``layout`` from :meth:`plane_layout`; one device only):
            params AND optimizer state as (M, P) planes, grads via one
            vjp through the unpacked view, every step one fused
            opt_step pass; the phase takes and returns planes, so no
            tree copy of the params sits beside them;
          flat        — params plane packed on entry, with per-step
            pack/unpack around the tree-mapped optimizer (optimizers
            without plane support; one device only);
          tree        — the leaf carry: params pytree in its dtypes
            (``carry`` "leaf": dtypes FlatSpec can't embed, a TPU, a
            mesh, ``flat=False``)."""
        shard = m_global is not None
        ml = jax.tree.leaves(state.worker_params)[0].shape[0]
        num_workers = m_global if shard else ml
        self._check_workers(num_workers)
        self._check_compressible(state.worker_params)
        sched = self.schedule
        comp = self._comp()
        flat_native = layout is not None
        if flat_native:
            spec = layout[0]
            use_flat = True
        else:
            assert self.plane_layout(state) is None, \
                "a flat-native state runs in plane form: pass " \
                "start_state()'s state and layout"
            use_flat = self.carry(state) == "plane"
            # compressed events encode on the plane even in the tree
            # carry (pack/unpack around the event only — events are rare)
            spec = (FlatSpec.of(state.worker_params)
                    if use_flat or comp is not None else None)
        p_width = (spec.width if spec is not None else
                   sum(x.size // ml
                       for x in jax.tree.leaves(state.worker_params)))
        ec = self._sched_event_cost(p_width, num_workers)
        tm = tele_metrics if self.telemetry else None
        eb_all, eb_inner = (self._event_bytes(p_width, num_workers)
                            if tm is not None else (0.0, 0.0))

        if flat_native:
            carry_p, carry_s = state.worker_params, state.opt_state
            carry_o = state.outer_state
            average = self._flat_average
        elif use_flat:
            carry_p = spec.pack(state.worker_params)
            carry_s = state.opt_state
            carry_o = ()
            if self.outer is not None and state.outer_state != ():
                prev_avg, vel = state.outer_state
                carry_o = (spec.pack1(prev_avg), spec.pack1(vel))
            average = self._flat_average
        else:
            carry_p = state.worker_params
            carry_s = state.opt_state
            carry_o = state.outer_state
            average = partial(self._tree_average, num_workers=num_workers)
        grads_fn = (make_plane_step(self.loss_fn, spec) if flat_native
                    else None)
        fp = self._faults()
        ax = self._worker_axes() if shard else None
        i0 = self._shard_index() * ml if shard else 0
        # a shard's fault transitions and cohorts cover its own rows
        rows = dict(row0=i0, num_rows=ml) if shard else {}

        def comp_event(wp_c, resid, scope, step, W=None, alive=None,
                       alive_full=None):
            # encode -> event -> decode on the plane; tree carries pack
            # around the (rare) event only
            plane = wp_c if use_flat else spec.pack(wp_c)
            if shard:
                plane, resid = self._psum_compressed_event(
                    spec, plane, resid, scope, step, state.dec_key, ml,
                    num_workers, W=W, alive=alive, alive_full=alive_full)
            else:
                plane, resid, _ = self._compressed_plane_event(
                    spec, plane, resid, scope, step, state.dec_key, W=W,
                    alive=alive)
            return (plane if use_flat else spec.unpack(plane)), resid

        def warm_start(wp_c, opt_c, resid, alive_prev, rejoined):
            # rejoining rows take the current alive average, with
            # optimizer state and error-feedback residual zeroed —
            # static under fp.has_rejoin, so crash-only plans trace
            # nothing extra
            if shard:
                glob = self._psum_mean(wp_c, alive_prev)
                wp_c = faults_mod.select_rows_tree(
                    replicate(_as_leaves(glob, wp_c), ml), wp_c, rejoined)
            elif use_flat:
                glob = faults_mod.masked_mean(wp_c, alive_prev)
                codes = spec.rounding_codes()
                if codes is not None:
                    glob = round_to_codes(glob, codes)
                wp_c = faults_mod.select_rows(
                    jnp.broadcast_to(glob[None], wp_c.shape), wp_c,
                    rejoined)
            else:
                wp_c = faults_mod.warm_start_tree(wp_c, alive_prev,
                                                  rejoined)
            if flat_native:
                opt_c = tuple(faults_mod.zero_rows(s, rejoined)
                              for s in opt_c)
            else:
                opt_c = faults_mod.zero_rows_tree(opt_c, rejoined)
            if comp is not None:
                resid = faults_mod.zero_rows(resid, rejoined)
            return wp_c, opt_c, resid

        def body(carry, xs_t):
            wp_c, opt_c, outer_c, key, step, sst, resid, fst, acc = carry
            alive = alive_full = umask = dscale = own = None
            with jax.named_scope("engine.batch"):
                step = step + 1
                key, sub = jax.random.split(key)
                rngs = jax.random.split(sub, num_workers)
                if shard:
                    # the global M's streams, this shard's rows of them
                    rngs = jax.lax.dynamic_slice_in_dim(rngs, i0, ml, 0)
                batch = fetch(xs_t)
                if fp is not None:
                    alive_prev = fst.alive
                    # one device: alive_full IS alive (the same array)
                    fst, alive_full, alive, umask, rejoined = \
                        fp.transition(fst, step, state.dec_key, **rows)
                    if sched.straggle_aware:
                        dscale = fp.disp_scale(alive_full, state.dec_key,
                                               step)
            if fp is not None and fp.has_rejoin:
                # the warm-start consensus is the PREVIOUS step's mixing
                # cohort: mid-curriculum (solo) rows train but their
                # unrepresentative iterates stay out of it
                with jax.named_scope("engine.average"):
                    wp_c, opt_c, resid = warm_start(
                        wp_c, opt_c, resid,
                        fp.mix_at(alive_prev, step - 1, **rows), rejoined)
            if flat_native:
                losses, _, gplane = grads_fn(wp_c, batch, rngs)
                with jax.named_scope("engine.update"):
                    scal = self.optimizer.plane_scalars(step)
                wp_c, opt_c, outer_c, resid, sst, disp, code = \
                    self._flat_native_step(
                        spec, wp_c, gplane, opt_c, outer_c, scal, step,
                        sst, state.dec_key, resid=resid,
                        fmask=None if fp is None else (alive, umask),
                        dscale=dscale)
            else:
                if use_flat:
                    with jax.named_scope("engine.unpack"):
                        wp = spec.unpack(wp_c)
                else:
                    wp = wp_c
                wp_new, opt_new, losses, _ = self.worker_step(
                    wp, opt_c, batch, step, rngs)
                if use_flat:
                    with jax.named_scope("engine.pack"):
                        wp_new_c = spec.pack(wp_new)
                with jax.named_scope("engine.update"):
                    if fp is not None:
                        # dead/straggling rows keep params AND optimizer
                        # state (zeroed grads would still advance
                        # momentum)
                        wp_c = (faults_mod.select_rows(wp_new_c, wp_c,
                                                       umask)
                                if use_flat else
                                faults_mod.select_rows_tree(wp_new, wp,
                                                            umask))
                        opt_c = faults_mod.select_rows_tree(opt_new, opt_c,
                                                            umask)
                    else:
                        opt_c = opt_new
                        wp_c = wp_new_c if use_flat else wp_new
                    # the Eq. 4 dispersion is measured EVERY step (post
                    # update, pre average): the stateful decision
                    # consumes it and the trace records the true
                    # diagnostic on non-averaging steps too
                    if shard:
                        disp, own = self._sharded_dispersion(
                            wp_c, num_workers, alive_full)
                    elif fp is not None:
                        disp = (faults_mod.masked_dispersion(wp_c, alive)
                                if use_flat else
                                faults_mod.masked_dispersion_tree(wp_c,
                                                                  alive))
                    elif use_flat:
                        glob = jnp.mean(wp_c, axis=0)
                        disp = (jnp.sum(jnp.square(wp_c - glob[None]))
                                / num_workers)
                    else:
                        disp = worker_dispersion(wp_c)
                    code, sst = sched.decision_state(step, sst, disp,
                                                     state.dec_key,
                                                     event_cost=ec,
                                                     disp_scale=dscale)
                # a shard's events reuse its columns of this step's mean
                event = (average if not shard else
                         partial(self._psum_tree_average, own=own,
                                 m_global=num_workers,
                                 alive_full=alive_full))
                if sched.kind == "minibatch":
                    with jax.named_scope("engine.average"):
                        W = self._event_W(step, state.dec_key)
                        if comp is not None:
                            wp_c, resid = comp_event(
                                wp_c, resid, "all", step, W=W, alive=alive,
                                alive_full=alive_full)
                        else:
                            wp_c, outer_c = event(wp_c, outer_c, "all",
                                                  W=W, alive=alive)[:2]
                elif sched.kind != "oneshot":
                    def none_branch(args):
                        return args

                    def inner_branch(args):
                        if comp is not None:
                            pl_, r_ = comp_event(args[0], args[2],
                                                 "inner", step,
                                                 alive=alive,
                                                 alive_full=alive_full)
                            return pl_, args[1], r_
                        return event(args[0], args[1], "inner",
                                     alive=alive)[:2] + (args[2],)

                    def all_branch(args):
                        W = self._event_W(step, state.dec_key)
                        if comp is not None:
                            pl_, r_ = comp_event(args[0], args[2],
                                                 "all", step, W=W,
                                                 alive=alive,
                                                 alive_full=alive_full)
                            return pl_, args[1], r_
                        return event(args[0], args[1], "all",
                                     W=W, alive=alive)[:2] + (args[2],)

                    # only a hierarchical schedule emits inner events (code
                    # 1); the others switch on (none, all), which lets the
                    # TPU compiler pass the carry through the none branch
                    # in place rather than copy every leaf
                    if sched.kind == "hierarchical":
                        idx, branches = code, [none_branch, inner_branch,
                                               all_branch]
                    else:
                        idx, branches = code // 2, [none_branch, all_branch]
                    with jax.named_scope("engine.average"):
                        wp_c, outer_c, resid = jax.lax.switch(
                            idx, branches, (wp_c, outer_c, resid))
            if shard:
                loss_t = (jax.lax.psum(jnp.sum(losses), ax) / num_workers
                          if fp is None else
                          jax.lax.psum(jnp.sum(losses * alive), ax)
                          / jax.lax.psum(jnp.sum(alive), ax))
            else:
                loss_t = (jnp.mean(losses) if fp is None
                          else jnp.sum(losses * alive) / jnp.sum(alive))
            if tm is not None:
                n_alive, n_straggle = self._tele_occupancy(
                    fp, step, state.dec_key, num_workers)
                acc = tm.accumulate(
                    acc, loss=loss_t, disp=disp, code=code,
                    event_bytes_all=eb_all, event_bytes_inner=eb_inner,
                    n_alive=n_alive, n_straggle=n_straggle)
            return ((wp_c, opt_c, outer_c, key, step, sst, resid, fst,
                     acc),
                    (loss_t, disp.astype(jnp.float32), code))

        sst0 = (state.sched if isinstance(state.sched, SchedState)
                else sched.init_sched_state())
        fst0 = (state.fault if isinstance(state.fault, FaultState)
                else (faults_mod.init_fault_state(ml)
                      if fp is not None else ()))
        # the metrics accumulator is reconstructed fresh every phase —
        # never part of EngineState, never checkpointed
        acc0 = tm.init_metrics() if tm is not None else ()
        carry0 = (carry_p, carry_s, carry_o, state.key, state.step, sst0,
                  state.resid, fst0, acc0)
        (wp_c, opt_c, outer_c, key, step, sst, resid, fst, acc), \
            (loss, disp, code) = \
            jax.lax.scan(body, carry0, xs, unroll=self.scan_unroll)

        if use_flat and not flat_native:
            wp, opt_state = spec.unpack(wp_c), opt_c
            outer_state = state.outer_state
            if carry_o != ():
                outer_state = (spec.unpack1(outer_c[0]),
                               spec.unpack1(outer_c[1], dtypes=jnp.float32))
        else:
            wp, opt_state, outer_state = wp_c, opt_c, outer_c
        new_state = EngineState(wp, opt_state, outer_state, key,
                                state.dec_key, step, sst, resid, fst)
        trace = {"loss": loss, "dispersion": disp, "avg_code": code}
        if tm is not None:
            trace["metrics"] = acc
        return new_state, trace

    # ---- one shard of a mesh (``_phase`` with ``m_global``, shard_map) ---
    def _worker_axes(self) -> tuple:
        from repro.sharding.specs import mesh_worker_axes
        return tuple(self.shard_axes) or mesh_worker_axes(self.mesh)

    def _num_shards(self) -> int:
        n = 1
        for a in self._worker_axes():
            n *= self.mesh.shape[a]
        return n

    def _shard_index(self):
        """Flat index of this shard along the worker axes (row-major)."""
        idx = jnp.zeros((), jnp.int32)
        for a in self._worker_axes():
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def _psum_mean(self, wp, mask):
        """The mean of every leaf over the rows of ``mask`` on all
        shards, in f32 (a rejoin's warm start): this shard's masked
        column sums, each leaf at its own precision, and the count
        psum'd over the worker axes in ONE call, so XLA emits one
        combined all-reduce, not one per leaf."""
        def colsum(x):
            xf = widen(x) * mask.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.sum(xf, axis=0)
        sums, n = jax.lax.psum((jax.tree.map(colsum, wp), jnp.sum(mask)),
                               self._worker_axes())
        return jax.tree.map(lambda s: s / n, sums)

    def _sharded_dispersion(self, wp, m_global: int, alive_full=None):
        """The Eq. 4 dispersion over every shard's workers (the rows of
        ``alive_full`` only under a fault plan), reduced by columns:
        all-to-alls of each leaf's rows, in the leaf's own dtype, hand
        this shard all M workers' values of its 1/S of the leaf's
        columns (:func:`_column_view`). On them it forms the f32 mean
        and the two-pass squared deviations as :func:`worker_dispersion`
        does; one scalar psum sums them over the shards. Returns
        (dispersion, this shard's columns of the mean rounded to each
        leaf's dtype), which the all-mean event gathers
        (:meth:`_psum_tree_average`)."""
        ax, s = self._worker_axes(), self._num_shards()
        n = m_global if alive_full is None else jnp.sum(alive_full)

        def owner(x):
            x = _column_view(x, s)
            # one all-to-all per local row i, whose columns bound for
            # each shard are contiguous (exchanging all rows at once
            # makes the compiler copy the leaf into a shard-major
            # layout first): it returns the (S, ...) values of workers
            # k * ml + i, k the shard in _shard_index order
            xf = [widen(jax.lax.all_to_all(
                r.reshape((s, -1) + r.shape[1:]), ax, 0, 0, tiled=True))
                for r in x]
            w = ([1] * len(xf) if alive_full is None else list(
                alive_full.reshape((s, -1) + (1,) * (x.ndim - 1))
                .swapaxes(0, 1)))
            mean = sum(jnp.sum(v * a, axis=0) for v, a in zip(xf, w)) / n
            d = sum(jnp.sum(jnp.square(v - mean[None]) * a)
                    for v, a in zip(xf, w))
            return d, mean.astype(x.dtype)

        leaves, tdef = jax.tree.flatten(wp)
        with jax.named_scope("engine.dispersion"):
            sq, own = zip(*map(owner, leaves))
            return jax.lax.psum(sum(sq), ax) / n, tdef.unflatten(own)

    def _gather_mean(self, own, wp):
        """The whole mean tree, in each leaf's dtype, from every
        shard's columns of it (:meth:`_sharded_dispersion`)."""
        ax = self._worker_axes()

        def leaf(g, x):
            g = jax.lax.all_gather(g, ax, axis=0, tiled=True)
            # a flattened leaf drops its padding
            return g if g.shape == x.shape[1:] else \
                g[:math.prod(x.shape[1:])].reshape(x.shape[1:])
        return jax.tree.map(leaf, own, wp)

    def _psum_tree_average(self, wp, outer_c, scope: str, W=None,
                           alive=None, *, own, m_global: int,
                           alive_full=None):
        """The averaging event on one shard's leaves, no optimizer
        update. The all-scope mean is gathered from this step's ``own``
        (each shard's columns of the dispersion's mean, already in the
        leaf's dtype), so an event step adds one all-gather in the
        leaves' dtypes and no all-reduce; the outer optimizer steps on
        it, replicated.
        Group means and a mixing topology's ``W @ x`` need every row:
        each leaf is all_gathered, :meth:`_tree_average` runs on the
        whole worker set, and this shard's rows are kept — O(M) rows
        per leaf, on event steps only. ``alive`` (this shard's rows) /
        ``alive_full`` (all M) mask the event under a fault plan.
        Returns (wp, outer_c)."""
        ml = jax.tree.leaves(wp)[0].shape[0]
        if scope == "inner" or W is not None or self._all_groups() > 1:
            ax, i0 = self._worker_axes(), self._shard_index() * ml

            def leaf(x):
                full = jax.lax.all_gather(x, ax, axis=0, tiled=True)
                full = self._tree_average(full, (), scope, m_global, W=W,
                                          alive=alive_full)[0]
                return jax.lax.dynamic_slice_in_dim(full, i0, ml, 0)
            return jax.tree.map(leaf, wp), outer_c
        avg = self._gather_mean(own, wp)
        if alive is not None:
            # dead rows keep their last parameters
            return faults_mod.select_rows_tree(replicate(avg, ml), wp,
                                               alive), outer_c
        if self.outer is not None:
            prev_avg, vel = outer_c
            avg, vel = self.outer.apply(prev_avg, avg, vel)
            outer_c = (avg, vel)
        return replicate(avg, ml), outer_c

    def _psum_compressed_event(self, spec, plane, resid, scope: str, step,
                               dec_key, ml: int, m_global: int, W=None,
                               alive=None, alive_full=None):
        """Compressed cross-shard averaging event on this shard's rows,
        packed as an (M_l, P) plane around the event. Encoding is
        row-local (per-row scales, per-row fold_in uniforms keyed by the
        GLOBAL row id ``i0 + arange``), so each shard produces exactly
        the rows a single device would; the error-feedback residual
        update ``v - q`` stays shard-local and never crosses the wire.
        Mean events psum the per-shard sums of the ENCODED rows — that
        psum is the bytes-on-the-wire win the wire format buys. Mixing /
        group events all_gather q instead (boundary-crossing contractions
        need the full encoded plane)."""
        comp = self._comp()
        codes = spec.rounding_codes()
        ax = self._worker_axes()
        rows = self._shard_index() * ml + jnp.arange(ml, dtype=jnp.int32)
        u = (row_uniforms(dec_key, step, rows, spec.width)
             if comp.stochastic else None)
        q, r_new = encode_decode(plane, resid, wire=comp.wire, u=u,
                                 error_feedback=comp.error_feedback)
        resid = (r_new if alive is None
                 else faults_mod.select_rows(r_new, resid, alive))
        if scope == "all" and W is not None:
            if alive is not None:
                W = faults_mod.degraded_matrix(W.astype(jnp.float32),
                                               alive_full)
            full = jax.lax.all_gather(q, ax, axis=0, tiled=True)
            wrows = jax.lax.dynamic_slice_in_dim(
                W, self._shard_index() * ml, ml, 0)
            out = jnp.dot(wrows, full, preferred_element_type=jnp.float32)
        elif scope == "inner" or (scope == "all"
                                  and self._all_groups() > 1):
            groups = (max(self.schedule.inner_groups, 1)
                      if scope == "inner" else self._all_groups())
            full = jax.lax.all_gather(q, ax, axis=0, tiled=True)
            if alive is not None:
                full = faults_mod.masked_group_mean(full, alive_full,
                                                    groups)
            else:
                g = jnp.mean(
                    full.reshape(groups, m_global // groups, -1), axis=1)
                full = jnp.repeat(g, m_global // groups, axis=0)
            out = jax.lax.dynamic_slice_in_dim(
                full, self._shard_index() * ml, ml, 0)
        else:
            if alive is not None:
                glob = (jax.lax.psum(
                    jnp.sum(q * alive[:, None], axis=0), ax)
                    / jax.lax.psum(jnp.sum(alive), ax))
            else:
                glob = jax.lax.psum(jnp.sum(q, axis=0), ax) / m_global
            out = jnp.broadcast_to(glob[None], plane.shape)
        if codes is not None:
            out = round_to_codes(out, codes)
        if alive is not None:
            out = faults_mod.select_rows(out, plane, alive)
        return out, resid

    def _mesh_workers(self, state: EngineState, layout) -> int:
        """The worker count M of a mesh phase's tree-form ``state``. A
        plane-form state (``layout`` given) is refused: a mesh carries
        the leaves, and the plane form exists on one device only."""
        if layout is not None:
            raise ValueError(
                "a mesh phase carries the leaves: pass the tree-form "
                "state with layout=None (the (M, P) plane form exists on "
                "one device only)")
        m = jax.tree.leaves(state.worker_params)[0].shape[0]
        assert m % self._num_shards() == 0, (m, self._num_shards())
        return m

    def _state_specs(self, state: EngineState):
        ax = P(self._worker_axes())
        return EngineState(
            jax.tree.map(lambda _: ax, state.worker_params),
            jax.tree.map(lambda _: ax, state.opt_state),
            jax.tree.map(lambda _: P(), state.outer_state),
            P(), P(), P(),
            jax.tree.map(lambda _: P(), state.sched),
            jax.tree.map(lambda _: ax, state.resid),
            jax.tree.map(lambda _: ax, state.fault))

    def _trace_specs(self):
        specs = {"loss": P(), "dispersion": P(), "avg_code": P()}
        if self.telemetry:
            # identical on every shard (global inputs, pure streams):
            # replicated out spec, same as the loss/dispersion traces
            specs["metrics"] = P()
        return specs

    @partial(jax.jit, static_argnums=0, static_argnames="layout",
             donate_argnums=1)
    def run_phase(self, state: EngineState, batches, layout=None):
        """One compiled dispatch over a pre-staged (K, M, ...) batch
        block. ``layout`` (:meth:`plane_layout`, one device only) runs a
        plane-form state (:meth:`to_planes`) and returns one."""
        if self.mesh is None:
            return self._phase(state, batches, lambda b: b, layout)
        m = self._mesh_workers(state, layout)
        sspec = self._state_specs(state)
        ax = self._worker_axes()
        return jax.shard_map(
            lambda s, xs: self._phase(s, xs, lambda b: b, m_global=m),
            mesh=self.mesh,
            in_specs=(sspec, jax.tree.map(lambda _: P(None, ax), batches)),
            out_specs=(sspec, self._trace_specs()),
            check_vma=False)(state, batches)

    @partial(jax.jit, static_argnums=0, static_argnames="layout",
             donate_argnums=1)
    def run_phase_indexed(self, state: EngineState, dataset, idx_block,
                          layout=None):
        """One compiled dispatch over a (K, M, B) int32 index block:
        batches are gathered from the device-resident ``dataset``
        INSIDE the scan (``jnp.take``), so the host ships only
        indices. ``layout`` as in :meth:`run_phase`."""
        def fetch_from(ds):
            # indices are in range by construction: "clip" lowers to a
            # bare gather, where the default "fill" adds a bounds select
            # that changes how XLA:CPU emits the fused loss reduction
            # (its last ulp then differs from the list-fed program's)
            return lambda idx: jax.tree.map(
                lambda a: jnp.take(a, idx, axis=0, mode="clip"), ds)
        if self.mesh is None:
            return self._phase(state, idx_block, fetch_from(dataset),
                               layout)
        m = self._mesh_workers(state, layout)
        sspec = self._state_specs(state)
        ax = self._worker_axes()
        return jax.shard_map(
            lambda s, ds, idx: self._phase(s, idx, fetch_from(ds),
                                           m_global=m),
            mesh=self.mesh,
            in_specs=(sspec, jax.tree.map(lambda _: P(), dataset),
                      jax.tree.map(lambda _: P(None, ax), idx_block)),
            out_specs=(sspec, self._trace_specs()),
            check_vma=False)(state, dataset, idx_block)

    # ---- plane-form state (what run() carries between phases) ------------
    def carry(self, state: EngineState) -> str:
        """``"plane"`` or ``"leaf"``: what a phase of the tree-form
        ``state`` carries (:func:`carry_for`, on the default backend,
        as the kernels choose theirs): leaves on a TPU and on every
        mesh. ``flat=False`` and trees FlatSpec cannot embed carry
        leaves on one device too."""
        wp = state.worker_params
        if not (self.flat and FlatSpec.supports(wp)):
            return "leaf"
        return carry_for(jax.default_backend(), self.mesh is not None)

    def plane_layout(self, state: EngineState):
        """(FlatSpec, FlatOptSpec) of the flat-native carry for this
        (possibly abstract) state, or None where the phase carries a
        tree (:meth:`carry` "leaf", optimizers without the plane
        protocol)."""
        if self.carry(state) == "leaf":
            return None
        spec = FlatSpec.of(state.worker_params)
        opt_spec = self._opt_spec(spec, state.opt_state)
        return None if opt_spec is None else (spec, opt_spec)

    def to_planes(self, layout, state: EngineState) -> EngineState:
        """The plane form of ``state``: params as the (M, P) f32 plane,
        optimizer state as its S planes, outer state as (P,) vectors —
        exactly the carry :meth:`_phase` scans. A full-width model then
        holds ONE copy of its params across a phase, not a tree plus
        the planes packed from it."""
        spec, opt_spec = layout
        outer = state.outer_state
        if outer != ():
            outer = (spec.pack1(outer[0]), spec.pack1(outer[1]))
        return state._replace(worker_params=spec.pack(state.worker_params),
                              opt_state=opt_spec.pack(state.opt_state),
                              outer_state=outer)

    def to_tree(self, layout, state: EngineState) -> EngineState:
        """Inverse of :meth:`to_planes` (bit-exact)."""
        spec, opt_spec = layout
        outer = state.outer_state
        if outer != ():
            outer = (spec.unpack1(outer[0]),
                     spec.unpack1(outer[1], dtypes=jnp.float32))
        return state._replace(worker_params=spec.unpack(state.worker_params),
                              opt_state=opt_spec.unpack(state.opt_state),
                              outer_state=outer)

    @partial(jax.jit, static_argnums=(0, 1))
    def _to_planes(self, layout, state):
        return self.to_planes(layout, state)

    @partial(jax.jit, static_argnums=(0, 1))
    def _to_tree(self, layout, state):
        return self.to_tree(layout, state)

    @partial(jax.jit, static_argnums=(0, 1))
    def _params_tree(self, spec, plane):
        return spec.unpack(plane)

    def start_state(self, params, num_workers: int, seed: int = 0,
                    state: EngineState | None = None):
        """The state :meth:`run` carries: :meth:`init` (or the given
        ``state``), in plane form where the flat-native path applies (one
        device only). On a mesh a fresh state is built by one program
        whose output is already split over the worker axes, so each
        device computes only its own worker rows and none ever holds
        the whole state. Returns (state, layout)."""
        if state is None:
            build, args = (lambda p: self.init(p, num_workers, seed)), \
                (params,)
        else:
            build, args = (lambda s: s), (state,)
        if self.mesh is None:
            layout = self.plane_layout(jax.eval_shape(build, *args))
            state = build(*args)
            if layout is not None:
                state = self._to_planes(layout, state)
            return state, layout
        from repro.sharding.specs import engine_state_sharding
        shardings = lambda tree: engine_state_sharding(
            self.mesh, tree, axes=self._worker_axes())
        if state is not None:
            # a resumed (or resized) state may sit on another mesh; it is
            # placed, and run as it stands: a program rebuilding it would
            # hold it twice
            return jax.device_put(state, shardings(state)), None
        return jax.jit(build, out_shardings=shardings(
            jax.eval_shape(build, *args)))(*args), None

    def default_phase_len(self) -> int:
        """Compile-size heuristic: align phase blocks with the schedule's
        natural period (correctness never depends on the block size —
        decisions are per-step, on-device)."""
        s = self.schedule
        if s.kind == "periodic":
            return max(1, min(s.phase_len, 512))
        if s.kind == "hierarchical":
            return max(1, min(s.inner_phase_len, 512))
        if s.kind == "stochastic":
            return int(min(max(1.0 / max(s.zeta, 1e-12), 8), 128))
        if s.kind == "adaptive_budget":
            return int(min(max(s.budget_horizon / max(s.comm_budget, 1), 8),
                           128))
        # oneshot / minibatch / adaptive_threshold: any block size
        return 64

    # ---- drivers ---------------------------------------------------------
    def run(self, params, data, *, num_workers: int, seed: int = 0,
            record_every: int = 0, eval_fn=None, worker_eval_fn=None,
            phase_len: int | None = None, steps: int | None = None,
            prefetch: bool = True, state: EngineState | None = None,
            return_state: bool = False, sink=None):
        """Production driver: one run_phase dispatch per block of steps.

        data: an iterable of per-step worker batches (leading axis M) —
        staged to device by a background :class:`Prefetcher` thread
        (``prefetch=False`` stages synchronously; in-memory list/tuple
        sources skip the prefetch thread automatically, and a
        :class:`DeviceDataset` always takes the indexed on-device path,
        so only true streams ever pay for staging) — or a
        :class:`DeviceDataset`, in which case batches are gathered
        on-device from index blocks and ``steps`` bounds the run (it
        defaults to the dataset's precomputed index list, if any).
        eval_fn(consensus_params) / worker_eval_fn(worker_params) run on
        host every ``record_every`` steps (phase blocks are cut so record
        boundaries coincide with phase ends). Returns (final averaged
        params, history dict).

        The history records ``loss`` and ``disp_trace`` — the true
        per-step Eq. 4 dispersion, measured after the local update and
        before any averaging — every ``record_every`` steps, and
        ``dispersion`` (the same pre-average diagnostic) at every
        averaging event, plus the event count ``averages``.

        ``return_state`` appends the final :class:`EngineState` to the
        return tuple (for ``repro.checkpoint.save_engine_state``).
        ``state`` resumes a checkpointed :class:`EngineState`
        (``repro.checkpoint.load_engine_state``) instead of initializing
        from ``params``: step numbering, PRNG streams and averaging
        decisions continue exactly where the checkpoint stopped, and
        ``steps`` counts steps to run in THIS call. The returned history
        covers only this call.

        ``sink`` (a :class:`repro.telemetry.events.TelemetrySink`;
        requires ``PhaseEngine(telemetry=True)``) receives one
        ``phase_metrics`` record per compiled dispatch — flushed from
        the on-device accumulator that rode this phase's scan, on the
        SAME once-per-phase host fetch as the traces, naming the
        phase's ``carry`` (:meth:`carry`: "plane" or "leaf") — plus an
        ``averaging_event`` per event step and a ``fault_event`` per
        scripted crash/rejoin the phase covered.
        """
        self._check_workers(num_workers)
        if sink is not None and not self.telemetry:
            raise ValueError(
                "run(sink=...) flushes the on-device metrics "
                "accumulator, which this engine does not carry — "
                "construct it with PhaseEngine(..., telemetry=True)")
        with span("engine.start_state"):
            state, layout = self.start_state(params, num_workers, seed,
                                             state)
        carry = "plane" if layout is not None else self.carry(state)
        t0 = int(state.step)
        block = phase_len or self.default_phase_len()
        needs_eval = bool(record_every and (eval_fn or worker_eval_fn))
        hist = init_history()
        total = None if steps is None else t0 + steps

        def take_at(t):
            take = block
            if needs_eval:
                take = min(take, record_every - t % record_every)
            if total is not None:
                take = min(take, total - t)
            return take

        def worker_params():
            wp = state.worker_params
            if layout is not None:
                wp = self._params_tree(layout[0], wp)
            return wp

        def gathered(x):
            # a mesh-sharded worker axis is reassembled on the default
            # device so reductions over it (consensus) lower exactly
            # like the single-device engine's
            if self.mesh is None:
                return x
            # host-side, on concrete arrays (tree.map here is not a trace)
            return jnp.asarray(jax.device_get(x))  # analysis: ignore[trace-purity]

        def cons(wp):
            mean = consensus
            # under a fault plan the consensus is over alive workers
            # only — dead rows hold stale (or warm-start) parameters
            if (self._faults() is not None
                    and isinstance(state.fault, FaultState)):
                alive = jnp.asarray(jax.device_get(state.fault.alive))
                # mid-curriculum (solo) rows stay out of the consensus,
                # exactly as they stay out of averaging events
                alive = self._faults().mix_at(alive, int(state.step))
                mean = lambda x: faults_mod.masked_mean_tree(x, alive)
            # one leaf at a time: no device ever holds every worker's
            # params at once
            return jax.tree.map(lambda x: mean(gathered(x)), wp)

        def consume(t, k, trace, tw0=None):
            # THE once-per-phase host sync: traces AND (telemetry mode)
            # the metrics accumulator come back in this one fetch
            with span("engine.fetch", step=t + 1):
                trace = jax.device_get(trace)
            with span("engine.record", step=t + 1):
                return record(t, k, trace, tw0)

        def record(t, k, trace, tw0):
            wall = 0.0 if tw0 is None else time.perf_counter() - tw0
            t_first = t
            n_loss, n_disp = len(hist["loss"]), len(hist["disp_trace"])
            events = []
            for i in range(k):
                t += 1
                code = int(trace["avg_code"][i])
                if code:
                    d = float(trace["dispersion"][i])
                    hist["dispersion"].append((t, d))
                    hist["averages"] += 1
                    events.append((t, d, code))
                if record_every and t % record_every == 0:
                    hist["loss"].append((t, float(trace["loss"][i])))
                    hist["disp_trace"].append(
                        (t, float(trace["dispersion"][i])))
            if needs_eval and t % record_every == 0:
                wp = worker_params()
                if eval_fn is not None:
                    hist["eval"].append((t, eval_fn(cons(wp))))
                if worker_eval_fn is not None:
                    hist["worker_eval"].append(
                        (t, worker_eval_fn(jax.tree.map(gathered, wp))))
            if sink is not None:
                for t_ev, d_ev, c_ev in events:
                    sink.emit(make_record(
                        "averaging_event", step=t_ev, dispersion=d_ev,
                        scope="inner" if c_ev == 1 else "all"))
                fp = self._faults()
                if fp is not None:
                    for ev in fp.events_in(t_first, t):
                        sink.emit(make_record(
                            "fault_event", step=ev.step, kind=ev.kind,
                            worker=ev.worker))
                flushed = tele_metrics.flush_metrics(trace["metrics"])
                sink.emit(make_record(
                    "phase_metrics", t0=t_first + 1, t1=t, carry=carry,
                    wall_s=wall,
                    steps_per_s=(k / wall if wall > 0 else None),
                    loss_trace=hist["loss"][n_loss:],
                    disp_trace=hist["disp_trace"][n_disp:], **flushed))
            return t

        def finish():
            with span("engine.finish"):
                final = cons(worker_params())
                if not return_state:
                    return final, hist
                return final, hist, (state if layout is None
                                     else self._to_tree(layout, state))

        if isinstance(data, DeviceDataset):
            assert data.num_workers == num_workers, \
                (data.num_workers, num_workers)
            remaining = steps if steps is not None else data.num_steps
            assert remaining is not None, \
                "DeviceDataset with a sampler needs steps="
            if data.num_steps is not None:
                # like a streaming source, a precomputed index list ends
                # the run when exhausted
                remaining = min(remaining, data.num_steps)
            total = t0 + remaining
            t = t0
            while t < total:
                take = take_at(t)
                tw0 = time.perf_counter()
                with span("engine.next_block", step=t + 1):
                    idx = jnp.asarray(data.index_block(take))
                with span("engine.dispatch", step=t + 1):
                    state, trace = self.run_phase_indexed(
                        state, data.arrays, idx, layout=layout)
                t = consume(t, take, trace, tw0)
            return finish()

        def staged_blocks():
            it = iter(data)
            t, done = t0, False
            while not done:
                take = take_at(t)
                if take <= 0:
                    return
                chunk = []
                while len(chunk) < take:
                    try:
                        chunk.append(next(it))
                    except StopIteration:
                        done = True
                        break
                if not chunk:
                    return
                with span("engine.stage", step=t + 1):
                    staged = tree_stack(chunk)
                t += len(chunk)
                yield len(chunk), staged

        # a materialized in-memory source gains nothing from background
        # staging — the prefetch thread only contends with dispatch
        prefetch = prefetch and not isinstance(data, (list, tuple))
        pf = Prefetcher(staged_blocks()) if prefetch else None
        blocks = pf if pf is not None else staged_blocks()
        t = t0
        try:
            while True:
                with span("engine.next_block", step=t + 1):
                    nxt = next(blocks, None)
                if nxt is None:
                    break
                k, staged = nxt
                tw0 = time.perf_counter()
                with span("engine.dispatch", step=t + 1):
                    state, trace = self.run_phase(state, staged,
                                                  layout=layout)
                t = consume(t, k, trace, tw0)
        finally:
            if pf is not None:
                pf.close()
        return finish()

    # ---- legacy host-driven loop (benchmark baseline / equivalence) ------
    @partial(jax.jit, static_argnums=0)
    def _host_step(self, wp, opt_state, batch, step, rngs, sst, dec_key,
                   ec=None):
        """One host-loop step: the vmapped local update, the always-on
        Eq. 4 dispersion (post update, pre average) and the stateful
        schedule decision in one dispatch; the host reads the decision
        code and conditionally dispatches the averaging event."""
        wp, opt_state, losses, _ = self.worker_step(wp, opt_state, batch,
                                                    step, rngs)
        disp = worker_dispersion(wp).astype(jnp.float32)
        code, sst = self.schedule.decision_state(step, sst, disp, dec_key,
                                                 event_cost=ec)
        return wp, opt_state, jnp.mean(losses), disp, code, sst

    def _run_host_faults(self, params, batches, *, num_workers: int,
                         seed: int = 0, record_every: int = 0,
                         eval_fn=None, worker_eval_fn=None):
        """Host-driven loop under a fault plan: one :meth:`run_phase`
        dispatch per step, decisions and metrics read on host.

        Unlike the no-fault host loop, this path does NOT re-derive the
        step from tree ops: masked-update graphs large enough to carry
        the fault transition compile with different FMA contraction
        than the scan bodies (which sub-expressions LLVM fuses depends
        on the whole surrounding graph), drifting a second
        implementation one ulp per step no matter how the ops are
        ordered. Driving the SAME compiled phase one step at a time
        keeps the host loop's per-step dispatch granularity and host
        decision reads while making bit-identity with :meth:`run` hold
        by construction; the independent-implementation check under
        faults is the flat-native / flat / tree triple, which tier-1
        asserts bitwise."""
        state, layout = self.start_state(params, num_workers, seed)
        hist = init_history()

        def worker_params(state):
            if layout is None:
                return state.worker_params
            return self._params_tree(layout[0], state.worker_params)

        def cons(state):
            alive = jnp.asarray(jax.device_get(state.fault.alive))
            alive = self._faults().mix_at(alive, int(state.step))
            return faults_mod.masked_mean_tree(worker_params(state), alive)

        step = 0
        for batch in batches:
            step += 1
            state, trace = self.run_phase(state, tree_stack([batch]),
                                          layout=layout)
            trace = jax.device_get(trace)
            disp = float(trace["dispersion"][0])
            if int(trace["avg_code"][0]):
                hist["dispersion"].append((step, disp))
                hist["averages"] += 1
            if record_every and step % record_every == 0:
                hist["loss"].append((step, float(trace["loss"][0])))
                hist["disp_trace"].append((step, disp))
                if eval_fn is not None:
                    hist["eval"].append((step, eval_fn(cons(state))))
                if worker_eval_fn is not None:
                    hist["worker_eval"].append(
                        (step, worker_eval_fn(worker_params(state))))
        return cons(state), hist

    @partial(jax.jit, static_argnums=(0, 5))
    def _host_compressed_average(self, wp, resid, dec_key, step,
                                 scope: str, W=None):
        """Host-loop compressed averaging event: pack to the plane,
        encode -> event -> decode with the error-feedback residual,
        unpack. Same plane math as the fused in-scan event, so the host
        loop stays the bitwise baseline for :meth:`run`."""
        spec = FlatSpec.of(wp)
        plane, resid, _ = self._compressed_plane_event(
            spec, spec.pack(wp), resid, scope, step, dec_key, W=W)
        return spec.unpack(plane), resid

    @partial(jax.jit, static_argnums=(0, 3))
    def _host_average(self, wp, outer_state, scope: str, W=None):
        num_workers = jax.tree.leaves(wp)[0].shape[0]
        if scope == "inner":
            return (average_inner(wp, max(self.schedule.inner_groups, 1)),
                    outer_state)
        if W is not None:
            return mix_tree(wp, W), outer_state
        g = self._all_groups()
        if g > 1:
            return average_inner(wp, g), outer_state
        wp, outer_state = self._apply_all_average(wp, outer_state,
                                                  num_workers)
        return wp, outer_state

    def run_host(self, params, batches, *, num_workers: int, seed: int = 0,
                 record_every: int = 0, eval_fn=None, worker_eval_fn=None):
        """Per-step host-driven loop: one jit dispatch per step, the
        averaging decision read on host, blocking ``float()`` metric
        reads. Numerically identical to :meth:`run` (same per-step rng
        splits, same fold_in decision stream, same stateful-schedule
        transition on the same per-step dispersion) — kept as the
        dispatch-bound baseline the engine is benchmarked against. The
        history dict has the same keys and semantics as :meth:`run`'s,
        including ``disp_trace`` and ``worker_eval``. Under a fault
        plan the loop delegates to :meth:`_run_host_faults`, which
        keeps the per-step dispatch shape but drives the shared
        compiled phase."""
        self._check_workers(num_workers)
        if self._faults() is not None:
            return self._run_host_faults(
                params, batches, num_workers=num_workers, seed=seed,
                record_every=record_every, eval_fn=eval_fn,
                worker_eval_fn=worker_eval_fn)
        state = self.init(params, num_workers, seed)
        wp, opt_state, outer_state = (state.worker_params, state.opt_state,
                                      state.outer_state)
        key, sst, resid = state.key, state.sched, state.resid
        p_width = sum(x.size // num_workers
                      for x in jax.tree.leaves(wp))
        ec = self._sched_event_cost(p_width, num_workers)
        hist = init_history()
        step = 0
        for batch in batches:
            step += 1
            key, sub = jax.random.split(key)
            rngs = jax.random.split(sub, num_workers)
            wp, opt_state, loss, disp, code, sst = self._host_step(
                wp, opt_state, batch, jnp.asarray(step, jnp.int32),
                rngs, sst, state.dec_key, ec)
            code = int(code)
            if code:
                W = (self._event_W(jnp.asarray(step, jnp.int32),
                                   state.dec_key) if code == 2 else None)
                scope = "inner" if code == 1 else "all"
                if self._comp() is not None:
                    wp, resid = self._host_compressed_average(
                        wp, resid, state.dec_key,
                        jnp.asarray(step, jnp.int32), scope, W)
                else:
                    wp, outer_state = self._host_average(
                        wp, outer_state, scope, W)
                hist["dispersion"].append((step, float(disp)))
                hist["averages"] += 1
            if record_every and step % record_every == 0:
                hist["loss"].append((step, float(loss)))
                hist["disp_trace"].append((step, float(disp)))
                if eval_fn is not None:
                    hist["eval"].append((step, eval_fn(consensus(wp))))
                if worker_eval_fn is not None:
                    hist["worker_eval"].append(
                        (step, worker_eval_fn(wp)))
        return consensus(wp), hist
