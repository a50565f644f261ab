"""Deterministic fault injection: worker failure as a scenario axis.

The paper's claim — averaging helps in proportion to the gradient-
variance envelope — is most interesting exactly where distributed
training is ugliest: workers die, straggle, and rejoin mid-run. This
module makes those faults a first-class, bit-reproducible scenario axis
instead of an ops accident:

* a :class:`FaultPlan` scripts crash / rejoin events (and membership
  changes M -> M': :meth:`FaultPlan.shrink` / :meth:`FaultPlan.grow`,
  which are simultaneous crashes / rejoins — ``repro.elastic`` applies
  the same change to a live ``EngineState`` by actually repacking the
  plane), an optional stochastic per-step straggle probability, and
  **solo windows**: steps during which a row trains (its local update
  applies) but is masked out of every averaging / mixing event, the
  loss and the dispersion. ``rejoin_curriculum=c`` derives a c-step
  solo window after every scripted rejoin, so a warm-started worker
  re-converges alone before its iterate re-enters the mix;
* the plan compiles to a pure per-step transition on a small
  :class:`FaultState` ``(alive, staleness)`` carry riding the engine
  scan exactly like ``SchedState`` — scripted liveness is a pure
  function of ``step``, stochastic straggles are a pure function of
  ``fold_in(dec_key, salt, step, row)`` — so every engine path, phase
  blocking, shard layout and checkpoint-resume replays the identical
  fault stream;
* degradation is graceful by construction: dead rows are masked out of
  every averaging / mixing event (:func:`degraded_matrix` renormalizes
  a doubly-stochastic ``W`` over the alive workers, Metropolis-style),
  stragglers skip their local update but still receive the mix, and
  rejoining workers warm-start from the current alive average with
  optimizer planes and error-feedback residuals zeroed.

A trivial plan (no events, zero straggle probability) is lowered away
by the engine entirely, so an all-alive ``FaultPlan`` is bit-identical
to the no-fault engine by construction.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.ref import widen

#: fold_in salt for the straggle uniforms ("str"), keeping the stream
#: independent of the gossip-partner (0x676F73) and stochastic-rounding
#: (0x656E63) streams that hang off the same dec_key
_STRAGGLE_SALT = 0x737472

EVENT_KINDS = ("crash", "rejoin")

_EVENT_RE = re.compile(r"^\s*(\w+)\s*:\s*m\s*=\s*(\d+)\s*@\s*t\s*=\s*(\d+)\s*$")


class FaultEvent(NamedTuple):
    """One scripted liveness change: ``worker`` crashes or rejoins at
    the local step ``step`` (1-based, matching ``EngineState.step``).
    The event takes effect DURING step ``step``: a worker crashed at
    ``t`` contributes no update and no averaging weight from step ``t``
    on; a worker rejoined at ``t`` is warm-started and participates
    from step ``t`` on."""
    kind: str
    worker: int
    step: int


class FaultState(NamedTuple):
    """Per-worker fault carry riding the engine scan (like SchedState).

    alive:     (M,) float32 — 1.0 for rows participating in averaging.
               Scripted liveness is a pure function of the step, but the
               carried copy is what rejoin detection diffs against, so
               checkpoint-resume replays warm-starts exactly once.
    staleness: (M,) int32 — steps since the row last applied a local
               update (dead and straggling rows age; diagnostics and
               schedules can consume it).
    """
    alive: Any
    staleness: Any


def init_fault_state(num_workers: int) -> FaultState:
    return FaultState(jnp.ones((num_workers,), jnp.float32),
                      jnp.zeros((num_workers,), jnp.int32))


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A deterministic fault script for an ``num_workers``-row plane.

    events:        scripted :class:`FaultEvent` crashes / rejoins,
                   validated (rows in range, steps >= 1, per-worker
                   crash/rejoin alternation, at least one worker alive
                   at every point).
    straggle_prob: per-step probability that an alive worker skips its
                   local update (it still receives the averaging /
                   mixing event). Drawn per (step, row) from the salted
                   ``dec_key`` stream — identical across engine paths,
                   shards and resume.
    solo:          ``(worker, start, stop)`` windows — during steps
                   ``start <= t < stop`` the row keeps updating but is
                   excluded from averaging / mixing events, the loss
                   and the dispersion (a curriculum: train alone, then
                   re-enter the mix). ``repro.elastic`` uses these for
                   grown rows.
    rejoin_curriculum: c > 0 derives a ``(worker, t, t + c)`` solo
                   window after every scripted rejoin at ``t``, so the
                   warm-started worker runs c solo steps before its
                   iterate re-enters the mix.
    """
    num_workers: int
    events: tuple = ()
    straggle_prob: float = 0.0
    solo: tuple = ()
    rejoin_curriculum: int = 0

    def __post_init__(self):
        if self.num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {self.num_workers}")
        if not 0.0 <= self.straggle_prob <= 1.0:
            raise ValueError(
                f"straggle_prob must be in [0, 1], got {self.straggle_prob}")
        events = tuple(FaultEvent(*e) for e in self.events)
        for ev in events:
            if ev.kind not in EVENT_KINDS:
                raise ValueError(
                    f"unknown fault kind {ev.kind!r} (expected one of "
                    f"{EVENT_KINDS})")
            if not 0 <= ev.worker < self.num_workers:
                raise ValueError(
                    f"fault event row m={ev.worker} out of range for "
                    f"{self.num_workers} workers")
            if ev.step < 1:
                raise ValueError(
                    f"fault event step t={ev.step} must be >= 1")
        events = tuple(sorted(events, key=lambda e: (e.step, e.worker)))
        seen = set()
        for ev in events:
            if (ev.worker, ev.step) in seen:
                raise ValueError(
                    f"multiple fault events for worker {ev.worker} at "
                    f"step {ev.step} are ambiguous")
            seen.add((ev.worker, ev.step))
        # per-worker crash/rejoin alternation + never-all-dead
        alive = [True] * self.num_workers
        for ev in events:
            if ev.kind == "crash":
                if not alive[ev.worker]:
                    raise ValueError(
                        f"worker {ev.worker} crashes at step {ev.step} "
                        "but is already dead (crash requires an alive "
                        "worker)")
                alive[ev.worker] = False
            else:
                if alive[ev.worker]:
                    raise ValueError(
                        f"worker {ev.worker} rejoins at step {ev.step} "
                        "without a prior crash (rejoin requires a dead "
                        "worker)")
                alive[ev.worker] = True
            if not any(alive):
                raise ValueError(
                    f"all {self.num_workers} workers are dead from step "
                    f"{ev.step} — at least one must stay alive")
        object.__setattr__(self, "events", events)
        if self.rejoin_curriculum < 0:
            raise ValueError(
                f"rejoin_curriculum must be >= 0, got "
                f"{self.rejoin_curriculum}")
        solo = tuple(tuple(int(v) for v in w) for w in self.solo)
        for w in solo:
            if len(w) != 3:
                raise ValueError(
                    f"solo window {w!r} must be (worker, start, stop)")
            worker, start, stop = w
            if not 0 <= worker < self.num_workers:
                raise ValueError(
                    f"solo window row m={worker} out of range for "
                    f"{self.num_workers} workers")
            if not 1 <= start < stop:
                raise ValueError(
                    f"solo window {w!r} needs 1 <= start < stop")
        object.__setattr__(self, "solo", solo)
        # curriculum windows derive from the scripted rejoins; explicit
        # solo windows come from the caller (repro.elastic adds them
        # for grown rows). _solo_windows is what the streams consume.
        derived = tuple((ev.worker, ev.step, ev.step + self.rejoin_curriculum)
                        for ev in events
                        if ev.kind == "rejoin" and self.rejoin_curriculum > 0)
        windows = solo + tuple(w for w in derived if w not in solo)
        object.__setattr__(self, "_solo_windows", windows)
        if windows:
            # at every liveness/solo breakpoint, some row must remain in
            # the mix (alive and not solo) — events and dispersion are
            # normalized by the mix count
            breaks = sorted({1} | {ev.step for ev in events}
                            | {t for _, a, b in windows for t in (a, b)})
            for t in breaks:
                alive = [True] * self.num_workers
                for ev in events:
                    if ev.step <= t:
                        alive[ev.worker] = ev.kind == "rejoin"
                in_solo = [any(w == i and a <= t < b
                               for i, a, b in windows)
                           for w in range(self.num_workers)]
                if not any(a and not s for a, s in zip(alive, in_solo)):
                    raise ValueError(
                        f"no worker left in the mix at step {t}: every "
                        "alive row is inside a solo window — at least "
                        "one must keep averaging")

    # -- static structure ------------------------------------------------

    @property
    def is_trivial(self) -> bool:
        """True when the plan can be lowered away entirely (the engine
        then runs its unmodified no-fault paths, bit-identically)."""
        return (not self.events and self.straggle_prob == 0.0
                and not self._solo_windows)

    @property
    def has_rejoin(self) -> bool:
        return any(ev.kind == "rejoin" for ev in self.events)

    @classmethod
    def parse(cls, text: str, num_workers: int, *,
              straggle_prob: float = 0.0, rejoin_after: int = 0,
              rejoin_curriculum: int = 0) -> "FaultPlan":
        """Parse a CLI fault script: comma-separated
        ``kind:m=<row>@t=<step>`` terms, e.g.
        ``"crash:m=3@t=100,rejoin:m=3@t=200"``. ``rejoin_after > 0``
        auto-appends a rejoin N steps after every crash that has no
        later scripted event for the same worker; ``rejoin_curriculum``
        passes through to the plan (c solo steps after every rejoin)."""
        events = []
        for part in text.split(","):
            if not part.strip():
                continue
            match = _EVENT_RE.match(part)
            if not match:
                raise ValueError(
                    f"cannot parse fault event {part.strip()!r} "
                    "(expected kind:m=<row>@t=<step>, e.g. "
                    "crash:m=3@t=100)")
            kind, worker, step = match.groups()
            if kind not in EVENT_KINDS:
                raise ValueError(
                    f"unknown fault kind {kind!r} in {part.strip()!r} "
                    f"(expected one of {EVENT_KINDS})")
            events.append(FaultEvent(kind, int(worker), int(step)))
        if rejoin_after > 0:
            for ev in list(events):
                if ev.kind != "crash":
                    continue
                later = [e for e in events
                         if e.worker == ev.worker and e.step > ev.step]
                if not later:
                    events.append(FaultEvent("rejoin", ev.worker,
                                             ev.step + rejoin_after))
        return cls(num_workers, tuple(events), straggle_prob,
                   rejoin_curriculum=rejoin_curriculum)

    @classmethod
    def shrink(cls, num_workers: int, new_num_workers: int, step: int,
               **kw) -> "FaultPlan":
        """Scripted membership change M -> M' at ``step``: rows
        ``new_num_workers..num_workers-1`` crash simultaneously."""
        if not 1 <= new_num_workers <= num_workers:
            raise ValueError(
                f"cannot shrink {num_workers} workers to {new_num_workers}")
        events = tuple(FaultEvent("crash", m, step)
                       for m in range(new_num_workers, num_workers))
        return cls(num_workers, events, **kw)

    @classmethod
    def grow(cls, num_workers: int, new_num_workers: int, step: int,
             **kw) -> "FaultPlan":
        """Scripted membership change M -> M' (M' >= M) at ``step``: a
        plan for the GROWN M'-row plane whose rows
        ``num_workers..new_num_workers-1`` are dead from step 1 and
        rejoin (warm-started from the alive consensus) at ``step``.
        Pass ``rejoin_curriculum=c`` for c solo steps before the new
        rows re-enter the mix. ``repro.elastic`` applies the same
        change to a LIVE ``EngineState`` without padding the plane."""
        if not 1 <= num_workers <= new_num_workers:
            raise ValueError(
                f"cannot grow {num_workers} workers to {new_num_workers}")
        if step < 2:
            raise ValueError(
                f"grow step t={step} must be >= 2 (the joining rows "
                "crash at t=1 and rejoin at t)")
        events = tuple(ev for m in range(num_workers, new_num_workers)
                       for ev in (FaultEvent("crash", m, 1),
                                  FaultEvent("rejoin", m, step)))
        return cls(new_num_workers, events, **kw)

    def events_in(self, t0: int, t1: int) -> tuple:
        """Scripted events with ``t0 < step <= t1``, in script order —
        the host-side enumeration the telemetry ``fault_event``
        records ride (one record per scripted crash/rejoin in the
        phase the driver just consumed)."""
        return tuple(ev for ev in self.events if t0 < ev.step <= t1)

    # -- pure per-step streams -------------------------------------------

    def alive_at(self, step):
        """(M,) f32 liveness at local step ``step`` — a pure function of
        the scripted events, safe under trace and across resume."""
        alive = jnp.ones((self.num_workers,), jnp.float32)
        for ev in self.events:  # sorted by step: later events override
            val = jnp.float32(0.0 if ev.kind == "crash" else 1.0)
            alive = alive.at[ev.worker].set(
                jnp.where(step >= ev.step, val, alive[ev.worker]))
        return alive

    def straggle_mask(self, dec_key, step, rows):
        """(len(rows),) f32 — 1.0 where the row straggles this step.
        Pure function of ``(dec_key, step, row)`` via the salted
        fold_in chain, so every path and shard draws identical masks."""
        rows = jnp.asarray(rows, jnp.int32)
        if self.straggle_prob <= 0.0:
            return jnp.zeros(rows.shape, jnp.float32)
        base = jax.random.fold_in(
            jax.random.fold_in(dec_key, _STRAGGLE_SALT), step)
        u = jax.vmap(lambda r: jax.random.uniform(
            jax.random.fold_in(base, r), (), jnp.float32))(rows)
        return (u < self.straggle_prob).astype(jnp.float32)

    def solo_at(self, step):
        """(M,) f32 — 1.0 where the row is inside a solo window at local
        step ``step`` (explicit windows plus the rejoin-curriculum
        derived ones). Pure function of ``step``, like :meth:`alive_at`."""
        out = jnp.zeros((self.num_workers,), jnp.float32)
        for worker, start, stop in self._solo_windows:
            out = out.at[worker].set(
                jnp.where((step >= start) & (step < stop), 1.0,
                          out[worker]))
        return out

    def mix_at(self, alive, step, *, row0=0, num_rows: int | None = None):
        """Mask ``alive`` down to the mixing cohort at ``step`` —
        alive rows not inside a solo window. With no solo windows this
        returns ``alive`` unchanged (the same array: bit-exact no-op).
        ``alive`` spans the full plane by default; shards pass their
        slice via ``row0``/``num_rows``."""
        if not self._solo_windows:
            return alive
        solo = self.solo_at(step)
        if num_rows is not None and num_rows != self.num_workers:
            solo = jax.lax.dynamic_slice_in_dim(solo, row0, num_rows, 0)
        return alive * (1.0 - solo)

    def disp_scale(self, mix_full, dec_key, step):
        """Fraction of the mixing cohort that applied its local update
        this step — the discount ``straggle_aware`` adaptive schedules
        multiply into the measured dispersion before it feeds their
        EMA/budget (a straggler's frozen iterate lags the mean and
        widens dispersion without carrying gradient-variance signal).
        Pure function of ``(dec_key, step)`` plus the scripted masks,
        so every engine path and every shard computes the identical
        scalar with no collective."""
        rows = jnp.arange(self.num_workers, dtype=jnp.int32)
        straggle = self.straggle_mask(dec_key, step, rows)
        updated = jnp.sum(mix_full * (1.0 - straggle))
        return updated / jnp.maximum(jnp.sum(mix_full), 1.0)

    def transition(self, state: FaultState, step, dec_key, *,
                   row0=0, num_rows: int | None = None):
        """One pure fault-state step for rows ``[row0, row0+num_rows)``
        (the full plane by default; shards pass their slice).

        Returns ``(new_state, mix_full, mix, umask, rejoined)``:
        ``mix_full`` the global (M,) mixing cohort — alive rows not in
        a solo window (every shard computes it locally — mixing
        matrices need all rows), ``mix`` / ``umask`` / ``rejoined`` the
        local-row masks. ``umask`` = alive and not straggling = rows
        that apply their local update this step (solo rows DO update —
        that is the curriculum). Without solo windows the mix masks are
        exactly the alive masks, bitwise. The carried ``new_state``
        keeps the *scripted* liveness, so rejoin detection (and its
        one-time warm start) is independent of curricula.
        """
        m = self.num_workers
        if num_rows is None:
            num_rows = m
        alive_prev = state.alive
        alive_full = self.alive_at(step)
        mix_full = self.mix_at(alive_full, step)
        if num_rows == m and isinstance(row0, int) and row0 == 0:
            alive = alive_full
            mix = mix_full
            rows = jnp.arange(m, dtype=jnp.int32)
        else:
            alive = jax.lax.dynamic_slice_in_dim(alive_full, row0,
                                                 num_rows, 0)
            mix = (alive if mix_full is alive_full else
                   jax.lax.dynamic_slice_in_dim(mix_full, row0,
                                                num_rows, 0))
            rows = jnp.asarray(row0, jnp.int32) + jnp.arange(
                num_rows, dtype=jnp.int32)
        straggle = self.straggle_mask(dec_key, step, rows)
        umask = alive * (1.0 - straggle)
        rejoined = alive * (1.0 - alive_prev)
        staleness = jnp.where(umask > 0, jnp.int32(0), state.staleness + 1)
        return (FaultState(alive, staleness), mix_full, mix, umask,
                rejoined)


# --------------------------------------------------------------------------
# Masked plane primitives (jnp; shared by the kernel refs, the Pallas
# wrappers and the engine's sharded collectives)
# --------------------------------------------------------------------------

def masked_mean(plane, alive):
    """Exact mean over alive rows: (M, P), (M,) -> (P,)."""
    return (jnp.sum(plane * alive[:, None], axis=0) / jnp.sum(alive))


def masked_dispersion(plane, alive):
    """Eq. 4 dispersion restricted to alive rows:
    sum_i alive_i ||w_i - w̄_alive||^2 / n_alive."""
    glob = masked_mean(plane, alive)
    return (jnp.sum(jnp.square(plane - glob[None]) * alive[:, None])
            / jnp.sum(alive))


def masked_group_mean(plane, alive, groups: int):
    """Per-group alive means broadcast back to (M, P); dead groups
    (no alive member) broadcast zeros — callers keep dead rows via
    :func:`select_rows` so those never land in the plane."""
    m, p = plane.shape
    mg = m // groups
    a = alive.reshape(groups, mg)
    sums = jnp.sum(plane.reshape(groups, mg, p) * a[..., None], axis=1)
    cnt = jnp.sum(a, axis=1)
    gm = sums / jnp.maximum(cnt, 1.0)[:, None]
    out = jnp.broadcast_to(gm[:, None], (groups, mg, p))
    return out.reshape(m, p)


def masked_event_matrix(alive, groups: int = 1):
    """The masked (group-)mean event as a doubly-stochastic (M, M)
    matrix: alive rows average the alive members of their group
    (``A[i, j] = a_i a_j / n_g``), dead rows are identity. Lets the
    fused Pallas ``mix`` kernels execute masked mean events as the same
    single ``A @ plane`` pass they already run for gossip mixing
    (equal to the exact-sum refs up to matmul rounding)."""
    a = alive.astype(jnp.float32)
    m = a.shape[0]
    gid = jnp.arange(m) // (m // groups)
    same = (gid[:, None] == gid[None, :]).astype(jnp.float32)
    cnt = jnp.sum(same * a[None, :], axis=1)  # alive count of my group
    A = same * a[:, None] * a[None, :] / jnp.maximum(cnt, 1.0)[:, None]
    return A + jnp.diag(1.0 - a)


def degraded_matrix(W, alive):
    """Renormalize a doubly-stochastic mixing matrix over the alive
    workers: off-diagonal mass to/from dead rows is dropped and folded
    back onto the diagonal (the Metropolis self-weight refill), giving
    identity rows/columns for dead workers and a matrix that is again
    doubly stochastic whenever ``W`` is symmetric (every built-in
    topology is). All-alive returns ``W`` itself, bitwise."""
    a = alive.astype(W.dtype)
    eye = jnp.eye(W.shape[0], dtype=W.dtype)
    off = W * (1.0 - eye) * a[:, None] * a[None, :]
    Wm = off + jnp.diag(1.0 - jnp.sum(off, axis=1))
    return jnp.where(jnp.all(a > 0), W, Wm)


def select_rows(new, old, mask):
    """Row-mask merge: rows with ``mask > 0`` from ``new``, others kept
    from ``old``. Works on (M, ...) arrays."""
    m = mask.reshape((mask.shape[0],) + (1,) * (new.ndim - 1))
    return jnp.where(m > 0, new, old)


def zero_rows(x, mask):
    """Zero the rows with ``mask > 0`` (rejoin resets for optimizer
    planes and error-feedback residuals)."""
    m = mask.reshape((mask.shape[0],) + (1,) * (x.ndim - 1))
    return jnp.where(m > 0, jnp.zeros_like(x), x)


# --------------------------------------------------------------------------
# Pytree twins (the engine's tree path and run_host; per-leaf math and
# reduction order match the plane primitives, so a single-leaf f32 model
# is bitwise identical across paths)
# --------------------------------------------------------------------------

def _row(mask, x):
    return mask.reshape((mask.shape[0],) + (1,) * (jnp.ndim(x) - 1))


def select_rows_tree(new_tree, old_tree, mask):
    return jax.tree.map(
        lambda n, o: jnp.where(_row(mask, n) > 0, n, o), new_tree, old_tree)


def zero_rows_tree(tree, mask):
    return jax.tree.map(
        lambda x: jnp.where(_row(mask, x) > 0, jnp.zeros_like(x), x), tree)


def masked_mean_tree(tree, alive):
    """Per-leaf alive mean (f32 accumulate, cast back): the tree twin of
    :func:`masked_mean` / ``consensus`` over the alive rows."""
    def leaf(x):
        xf = x.astype(jnp.float32)
        glob = (jnp.sum(xf * _row(alive, x), axis=0) / jnp.sum(alive))
        return glob.astype(x.dtype)
    return jax.tree.map(leaf, tree)


def masked_dispersion_tree(tree, alive):
    """Tree twin of :func:`masked_dispersion` (per-leaf f32 sums, each
    leaf at its own precision: :func:`repro.kernels.ref.widen`)."""
    total = jnp.float32(0.0)
    for x in jax.tree.leaves(tree):
        xf = widen(x)
        glob = jnp.sum(xf * _row(alive, x), axis=0) / jnp.sum(alive)
        total = total + jnp.sum(
            jnp.square(xf - glob[None]) * _row(alive, x))
    return total / jnp.sum(alive)


def warm_start_tree(tree, alive_prev, rejoined):
    """Rejoining rows take the current alive average (measured over the
    PREVIOUS step's alive set — the rejoiner itself excluded)."""
    mean = masked_mean_tree(tree, alive_prev)
    return jax.tree.map(
        lambda x, g: jnp.where(_row(rejoined, x) > 0,
                               jnp.broadcast_to(g[None], x.shape), x),
        tree, mean)


def masked_average_all_tree(tree, alive, *, groups: int = 1):
    """Masked averaging event on a pytree: alive rows get the (group)
    alive mean, dead rows keep their stale params."""
    def leaf(x):
        xf = x.astype(jnp.float32)
        m = x.shape[0]
        if groups > 1:
            mg = m // groups
            a = alive.reshape(groups, mg)
            rest = xf.shape[1:]
            sums = jnp.sum(xf.reshape((groups, mg) + rest)
                           * a.reshape((groups, mg) + (1,) * len(rest)),
                           axis=1)
            cnt = jnp.maximum(jnp.sum(a, axis=1), 1.0)
            gm = sums / cnt.reshape((groups,) + (1,) * len(rest))
            out = jnp.broadcast_to(gm[:, None], (groups, mg) + rest)
            out = out.reshape(x.shape)
        else:
            glob = jnp.sum(xf * _row(alive, x), axis=0) / jnp.sum(alive)
            out = jnp.broadcast_to(glob[None], x.shape)
        out = out.astype(x.dtype)
        return jnp.where(_row(alive, x) > 0, out, x)
    return jax.tree.map(leaf, tree)


def masked_mix_tree(tree, W, alive):
    """Masked gossip mix on a pytree: the degraded (alive-renormalized)
    ``W`` mixes alive rows; dead rows keep their stale params."""
    Wm = degraded_matrix(W.astype(jnp.float32), alive)

    def leaf(x):
        m = x.shape[0]
        flat = x.astype(jnp.float32).reshape(m, -1)
        out = jnp.dot(Wm, flat, preferred_element_type=jnp.float32)
        out = out.reshape(x.shape).astype(x.dtype)
        return jnp.where(_row(alive, x) > 0, out, x)
    return jax.tree.map(leaf, tree)
