"""Averaging schedules and averaging operators — the paper's technique.

A *schedule* decides WHEN the M workers' models are averaged:
  - one-shot     : only at the very end (Zinkevich et al. 2010)
  - minibatch    : every step (statistically = 1 worker with batch M)
  - periodic(K)  : every K steps — the paper's main subject
  - stochastic(ζ): i.i.d. per-step probability ζ (paper §2.3 / Lemma 1)
  - hierarchical : inner groups every K_inner, all workers every K_outer
                   (beyond-paper: matches TPU ICI/DCI bandwidth hierarchy)
  - adaptive_threshold : average when the running EMA of the Eq. 4
                   dispersion crosses ``disp_threshold`` — communication
                   follows the measured gradient-variance envelope the
                   paper says governs whether averaging helps
  - adaptive_budget : APA-style (Jiang & Agrawal, arXiv:2007.06134):
                   spend at most ``comm_budget`` averaging events over
                   ``budget_horizon`` steps, paced proportionally to the
                   measured dispersion envelope — high-dispersion
                   stretches get communication ahead of uniform pacing,
                   quiet stretches save it
  - adaptive_bytes : the same dispersion-paced accrual, but the budget
                   and the credit are BYTES on the wire, not events:
                   each event costs ``comm_bytes(topology, 1, P, wire)``
                   (the engine passes it as ``event_cost``), so the one
                   ``byte_budget`` knob prices timing x topology x
                   precision in a common currency — a ring event with an
                   int8 wire is ~100x cheaper than a full-mean f32 event
                   and the schedule fires proportionally more often

The two adaptive kinds are *stateful*: their decisions are pure
functions of an explicit :class:`SchedState` (dispersion EMA, cumulative
dispersion, pacing credit, events spent, steps since the last event)
threaded through the phase scan and checkpointed in ``EngineState`` —
see :meth:`AveragingSchedule.decision_state`. The static kinds flow
through the same transition (their state is pure bookkeeping), so every
engine path carries one uniform carry.

An averaging *operator* says HOW: plain mean, or an outer optimizer
(Nesterov momentum on the averaging direction — beyond-paper, DiLoCo-like).

Workers are represented as a leading axis of size M on every leaf of the
params pytree; on a device mesh this axis is sharded over the worker
(data / pod×data) mesh axes, so the means below lower to all-reduces over
exactly those axes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import widen


class SchedState(NamedTuple):
    """The stateful-schedule carry: everything an adaptive decision may
    depend on, as jnp scalars so it rides the phase scan and checkpoints
    inside ``EngineState`` bit-exactly.

    ``disp_ema`` is the running EMA of the per-step Eq. 4 dispersion,
    reset to 0 at every averaging event (so it measures dispersion built
    up *since* the last average). ``cum_disp`` is the un-reset running
    sum (the envelope's integral), ``credit`` the adaptive_budget pacing
    credit (in events) or the adaptive_bytes credit (in bytes — same
    slot, so the checkpointed leaf structure never changes),
    ``comm_spent`` the number of averaging events so far, and
    ``since_avg`` the steps since the last event. The static schedule
    kinds update the same fields (pure bookkeeping), so every engine
    path carries one uniform state."""
    disp_ema: jnp.ndarray    # f32 scalar
    cum_disp: jnp.ndarray    # f32 scalar
    credit: jnp.ndarray      # f32 scalar
    comm_spent: jnp.ndarray  # int32 scalar
    since_avg: jnp.ndarray   # int32 scalar


@dataclass(frozen=True)
class AveragingSchedule:
    kind: str = "periodic"      # oneshot | minibatch | periodic | stochastic
    #                           # | hierarchical | adaptive_threshold
    #                           # | adaptive_budget
    phase_len: int = 128        # K for periodic
    zeta: float = 0.0           # for stochastic
    inner_phase_len: int = 16   # hierarchical: average inner groups every K_i
    outer_phase_len: int = 512  # hierarchical: average everyone every K_o
    inner_groups: int = 1       # hierarchical: number of inner groups
    disp_threshold: float = 0.0  # adaptive_threshold: EMA trip level
    disp_ema_beta: float = 0.9  # adaptive: dispersion EMA decay
    comm_budget: int = 0        # adaptive_budget: max averaging events
    budget_horizon: int = 0     # adaptive_*: steps the budget spans
    byte_budget: int = 0        # adaptive_bytes: max bytes per worker
    straggle_aware: bool = False  # adaptive: discount straggler-widened
    #                           # dispersion (engine passes the alive/
    #                           # updated fraction as disp_scale)

    _KINDS = ("oneshot", "minibatch", "periodic", "stochastic",
              "hierarchical", "adaptive_threshold", "adaptive_budget",
              "adaptive_bytes")
    _ADAPTIVE = ("adaptive_threshold", "adaptive_budget",
                 "adaptive_bytes")

    def __post_init__(self):
        # the engine lowers decisions to traced integer mod / bernoulli
        # ops, where invalid parameters mis-schedule silently instead of
        # raising like the old host loop did — validate eagerly instead
        if self.kind not in self._KINDS:
            raise ValueError(f"unknown schedule kind {self.kind!r}")
        if self.kind == "periodic" and self.phase_len < 1:
            raise ValueError(f"periodic needs phase_len >= 1, "
                             f"got {self.phase_len}")
        if self.kind == "stochastic" and not 0.0 < self.zeta <= 1.0:
            raise ValueError(f"stochastic needs 0 < zeta <= 1, "
                             f"got {self.zeta}")
        if self.kind == "hierarchical" and (
                self.inner_phase_len < 1 or self.outer_phase_len < 1
                or self.inner_groups < 1):
            raise ValueError(
                "hierarchical needs inner_phase_len/outer_phase_len/"
                f"inner_groups >= 1, got ({self.inner_phase_len}, "
                f"{self.outer_phase_len}, {self.inner_groups})")
        if self.is_adaptive and not 0.0 <= self.disp_ema_beta < 1.0:
            raise ValueError(f"adaptive schedules need 0 <= disp_ema_beta "
                             f"< 1, got {self.disp_ema_beta}")
        if self.kind == "adaptive_threshold" and self.disp_threshold <= 0.0:
            raise ValueError(f"adaptive_threshold needs disp_threshold > 0, "
                             f"got {self.disp_threshold}")
        if self.kind == "adaptive_budget":
            if self.comm_budget < 1 or self.budget_horizon < 1:
                raise ValueError(
                    "adaptive_budget needs comm_budget >= 1 and "
                    f"budget_horizon >= 1, got ({self.comm_budget}, "
                    f"{self.budget_horizon})")
            if self.comm_budget > self.budget_horizon:
                raise ValueError(
                    f"adaptive_budget cannot spend {self.comm_budget} "
                    f"events in {self.budget_horizon} steps (at most one "
                    "averaging event per step)")
        if self.kind == "adaptive_bytes":
            if self.byte_budget < 1 or self.budget_horizon < 1:
                raise ValueError(
                    "adaptive_bytes needs byte_budget >= 1 and "
                    f"budget_horizon >= 1, got ({self.byte_budget}, "
                    f"{self.budget_horizon})")
        if self.straggle_aware and not self.is_adaptive:
            raise ValueError(
                f"straggle_aware discounts the dispersion fed to the "
                f"adaptive schedules; {self.kind!r} never consumes "
                "dispersion — drop straggle_aware or use one of "
                f"{self._ADAPTIVE}")

    @property
    def is_adaptive(self) -> bool:
        return self.kind in self._ADAPTIVE

    def expected_phase_len(self) -> float:
        """A-priori expected steps between communication events.

        For ``hierarchical`` this counts *any* event (inner or outer):
        events sit at multiples of K_i or K_o, so the rate is the
        harmonic combination 1/K_i + 1/K_o - 1/lcm(K_i, K_o) (the lcm
        term removes the double-counted coinciding steps). For
        ``adaptive_threshold`` the interval is data-dependent with no
        a-priori value — returns NaN. For ``adaptive_budget`` it is the
        budget's paced average interval."""
        if self.kind == "oneshot":
            return float("inf")
        if self.kind == "minibatch":
            return 1.0
        if self.kind == "periodic":
            return float(self.phase_len)
        if self.kind == "stochastic":
            return 1.0 / max(self.zeta, 1e-12)
        if self.kind == "hierarchical":
            ki, ko = self.inner_phase_len, self.outer_phase_len
            rate = 1.0 / ki + 1.0 / ko - 1.0 / math.lcm(ki, ko)
            return 1.0 / rate
        if self.kind == "adaptive_threshold":
            return float("nan")
        if self.kind == "adaptive_budget":
            return self.budget_horizon / self.comm_budget
        if self.kind == "adaptive_bytes":
            # bytes-per-event depends on (topology, wire, P), which only
            # the engine knows — no a-priori interval
            return float("nan")
        raise ValueError(self.kind)

    def init_sched_state(self) -> SchedState:
        # distinct arrays per field: EngineState is buffer-donated, and
        # aliased leaves would be donated twice
        f32 = lambda: jnp.zeros((), jnp.float32)
        i32 = lambda: jnp.zeros((), jnp.int32)
        return SchedState(f32(), f32(), f32(), i32(), i32())

    def decision_state(self, step, sched_state: SchedState, disp, key=None,
                       event_cost=None, disp_scale=None):
        """The stateful on-device decision: one pure transition
        ``(step, state, dispersion) -> (code, new state)`` shared by
        every engine path (flat-native scan, tree scan, sharded
        shard_map body, host loop), so decisions replay bit-identically
        across paths, phase blockings, and checkpoint/resume.

        ``disp`` is the Eq. 4 dispersion measured at THIS step, after
        the local update and before any averaging (the fused
        opt_step/avg_disp passes emit it every step). ``step`` may be a
        Python int (host loop) or a traced int32 scalar (scan body);
        the returned code is int32 (0: none, 1: inner, 2: all).

        Transition: the dispersion EMA advances by ``disp_ema_beta``
        (then resets to 0 when an averaging event fires, so it measures
        dispersion built since the last average); ``adaptive_threshold``
        fires when the EMA crosses ``disp_threshold``;
        ``adaptive_budget`` accrues pacing credit at the uniform rate
        ``comm_budget / budget_horizon`` scaled by the current EMA
        relative to the long-run mean dispersion (APA-style: spend the
        budget where the envelope is high), fires when a whole credit is
        accumulated, and never exceeds ``comm_budget`` events.
        ``adaptive_bytes`` is the same accrual with the credit
        denominated in BYTES: it accrues ``byte_budget/budget_horizon``
        bytes-per-step (EMA-scaled), fires when the credit covers one
        event's ``event_cost`` (the engine passes
        ``comm_bytes(topology, 1, P, wire)``), and never lets
        ``(events+1) * event_cost`` exceed ``byte_budget``. Static kinds
        defer to :meth:`decision_code` and only update the bookkeeping
        fields.

        Determinism caveat: the transition is bitwise reproducible for
        a FIXED ``disp`` stream, but ``disp`` itself is a float32
        reduction whose summation order differs across engine paths
        (flat plane vs per-leaf tree sums vs psum of shard partials).
        A run whose EMA lands within a last-ulp tie of the trip level
        at a decision step could therefore fire one step apart between
        paths on multi-leaf models; the single-buffer paths (flat vs
        host on one leaf, gather-collective vs single-device) reduce
        identically and replay identical decision streams — what the
        equivalence tests pin.

        ``disp_scale``: with ``straggle_aware=True`` the engine passes
        the fraction of the mixing cohort that applied its update this
        step (``FaultPlan.disp_scale``); the measured dispersion is
        multiplied by it before entering the EMA/budget accrual, so a
        straggler's frozen iterate — which lags the mean and widens the
        dispersion without carrying gradient-variance signal — is
        discounted instead of triggering spurious averaging events. The
        recorded dispersion trace is NOT scaled; only the decision
        input is."""
        s = sched_state
        disp = jnp.asarray(disp, jnp.float32)
        if self.straggle_aware and disp_scale is not None:
            disp = disp * jnp.asarray(disp_scale, jnp.float32)
        beta = jnp.asarray(self.disp_ema_beta, jnp.float32)
        ema = beta * s.disp_ema + (1.0 - beta) * disp
        cum = s.cum_disp + disp
        credit = s.credit
        if self.kind == "adaptive_threshold":
            code = jnp.where(ema > self.disp_threshold, 2, 0)
            code = code.astype(jnp.int32)
        elif self.kind == "adaptive_budget":
            rate = jnp.asarray(self.comm_budget / self.budget_horizon,
                               jnp.float32)
            mean = cum / jnp.maximum(jnp.asarray(step, jnp.float32), 1.0)
            w = jnp.where(mean > 0.0, ema / jnp.maximum(mean, 1e-30), 0.0)
            credit = credit + rate * w
            fire = (credit >= 1.0) & (s.comm_spent < self.comm_budget)
            code = jnp.where(fire, 2, 0).astype(jnp.int32)
            credit = jnp.where(fire, credit - 1.0, credit)
        elif self.kind == "adaptive_bytes":
            if event_cost is None:
                raise ValueError(
                    "adaptive_bytes needs event_cost (bytes one event "
                    "puts on the wire per worker) — the engine passes "
                    "comm_bytes(topology, 1, P, wire)")
            ec = jnp.asarray(event_cost, jnp.float32)
            rate = jnp.asarray(self.byte_budget / self.budget_horizon,
                               jnp.float32)
            mean = cum / jnp.maximum(jnp.asarray(step, jnp.float32), 1.0)
            w = jnp.where(mean > 0.0, ema / jnp.maximum(mean, 1e-30), 0.0)
            credit = credit + rate * w
            spent_after = (s.comm_spent + 1).astype(jnp.float32) * ec
            fire = (credit >= ec) & (spent_after <= self.byte_budget)
            code = jnp.where(fire, 2, 0).astype(jnp.int32)
            credit = jnp.where(fire, credit - ec, credit)
        else:
            code = self.decision_code(step, key)
        avg = code > 0
        new = SchedState(
            disp_ema=jnp.where(avg, 0.0, ema).astype(jnp.float32),
            cum_disp=cum,
            credit=jnp.asarray(credit, jnp.float32),
            comm_spent=s.comm_spent + avg.astype(jnp.int32),
            since_avg=jnp.where(avg, 0, s.since_avg + 1).astype(jnp.int32))
        return code, new

    def decision_code(self, step, key=None):
        """On-device decision for step ``step`` (1-indexed steps done).
        Returns an int32 code — 0: none, 1: inner, 2: all — computable
        under a jit trace, so the whole schedule lowers to ``lax.switch``
        inside the phase engine's scan. ``step`` may be a traced scalar.

        Stochastic draws come from ``fold_in(key, step)``, which makes the
        schedule a pure function of (key, step): reproducible, resumable
        from a checkpointed key, and identical whether evaluated on-device
        (engine) or eagerly on host (legacy loop).

        The adaptive kinds have no stateless decision — use
        :meth:`decision_state`.
        """
        if self.is_adaptive:
            raise ValueError(
                f"{self.kind} decisions depend on SchedState; use "
                "decision_state(step, sched_state, disp, key)")
        if self.kind == "oneshot":
            return jnp.zeros((), jnp.int32)
        if self.kind == "minibatch":
            return jnp.full((), 2, jnp.int32)
        if self.kind == "periodic":
            return jnp.where(step % self.phase_len == 0, 2, 0).astype(jnp.int32)
        if self.kind == "stochastic":
            assert key is not None, "stochastic schedule needs a PRNG key"
            hit = jax.random.bernoulli(jax.random.fold_in(key, step),
                                       self.zeta)
            return jnp.where(hit, 2, 0).astype(jnp.int32)
        if self.kind == "hierarchical":
            outer = step % self.outer_phase_len == 0
            inner = step % self.inner_phase_len == 0
            return jnp.where(outer, 2,
                             jnp.where(inner, 1, 0)).astype(jnp.int32)
        raise ValueError(self.kind)

    def wants_average(self, step: int, rng: np.random.Generator | None = None):
        """Legacy host-side decision for step ``step`` (1-indexed steps
        done). Returns "none" | "inner" | "all". Stochastic draws use the
        numpy generator; the engine path uses ``decision_code`` instead.
        The adaptive kinds need :meth:`decision_state`."""
        if self.is_adaptive:
            raise ValueError(
                f"{self.kind} decisions depend on SchedState; use "
                "decision_state(step, sched_state, disp, key)")
        if self.kind == "oneshot":
            return "none"
        if self.kind == "minibatch":
            return "all"
        if self.kind == "periodic":
            return "all" if step % self.phase_len == 0 else "none"
        if self.kind == "stochastic":
            assert rng is not None
            return "all" if rng.random() < self.zeta else "none"
        if self.kind == "hierarchical":
            if step % self.outer_phase_len == 0:
                return "all"
            if step % self.inner_phase_len == 0:
                return "inner"
            return "none"
        raise ValueError(self.kind)


# --------------------------------------------------------------------------
# Operators (worker axis = leading dim 0 of every leaf)
# --------------------------------------------------------------------------

def average_all(worker_tree):
    """Mean over the worker axis, broadcast back — the paper's operator."""
    def avg(x):
        m = jnp.mean(x, axis=0, keepdims=True)
        return jnp.broadcast_to(m, x.shape).astype(x.dtype)
    return jax.tree.map(avg, worker_tree)


def average_inner(worker_tree, inner_groups: int):
    """Hierarchical inner average: W workers = inner_groups contiguous
    groups; mean within each group only (lowers to an all-reduce over the
    intra-pod mesh axis when groups align with pods)."""
    def avg(x):
        w = x.shape[0]
        g = inner_groups
        xg = x.reshape((g, w // g) + x.shape[1:])
        m = jnp.mean(xg, axis=1, keepdims=True)
        return jnp.broadcast_to(m, xg.shape).reshape(x.shape).astype(x.dtype)
    return jax.tree.map(avg, worker_tree)


def worker_dispersion(worker_tree):
    """Mean squared distance of workers from their average — the paper's
    E||w_i - w̄||² variance diagnostic (Eq. 4), each leaf measured at
    its own precision (:func:`repro.kernels.ref.widen`)."""
    def sq(x):
        xf = widen(x)
        m = jnp.mean(xf, axis=0, keepdims=True)
        return jnp.sum(jnp.square(xf - m)) / x.shape[0]
    return sum(jax.tree.leaves(jax.tree.map(sq, worker_tree)))


# --------------------------------------------------------------------------
# Outer optimizer (beyond-paper): treat the consensus move as a gradient
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class OuterOptimizer:
    """DiLoCo-style outer Nesterov momentum applied at averaging steps.
    With lr=1, momentum=0 this reduces exactly to the paper's plain mean."""
    lr: float = 1.0
    momentum: float = 0.0
    nesterov: bool = True

    def init(self, avg_tree):
        return jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32),
                            avg_tree)

    def apply(self, prev_avg, new_avg, velocity):
        """prev_avg/new_avg: trees WITHOUT worker axis. Returns
        (updated average, velocity). Two plain tree.map passes — params
        may be arbitrarily nested pytrees (incl. tuples), so no is_leaf
        tricks on the mapped output."""
        def outer_grad(p, n):
            return p.astype(jnp.float32) - n.astype(jnp.float32)

        velocity = jax.tree.map(
            lambda p, n, v: self.momentum * v + outer_grad(p, n),
            prev_avg, new_avg, velocity)
        updated = jax.tree.map(
            lambda p, n, v: (p.astype(jnp.float32) - self.lr * (
                self.momentum * v + outer_grad(p, n) if self.nesterov else v
            )).astype(p.dtype),
            prev_avg, new_avg, velocity)
        return updated, velocity
