"""Adaptive dispersion-driven schedules: the stateful subsystem.

The adaptive kinds decide WHEN to average from the measured Eq. 4
dispersion, carried as an explicit ``SchedState`` in the phase scan and
in ``EngineState``. These tests pin the system-level guarantees:

  1. engine == host on the FULL per-step trajectory — decision sequence,
     dispersion trace, loss trace, final params — for both adaptive
     kinds (the host loop replays the identical pure transition from its
     own per-step dispersion).
  2. Decisions are independent of phase blocking (the state rides the
     scan carry across run_phase boundaries) and of prefetch staging.
  3. Checkpoint/resume is bit-identical, INCLUDING the schedule state
     (dispersion EMA, pacing credit, budget spent): a resumed run replays
     the decisions of the uninterrupted one.
  4. The dispersion trace is the true Eq. 4 value on EVERY step (it used
     to read 0.0 between averaging events), in the engine and host paths.
  5. ``PhaseEngine`` rejects a worker count the hierarchical inner
     grouping cannot split — eagerly, not as a mid-trace reshape error.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_engine_state, save_engine_state
from repro.core import (AveragingSchedule, OuterOptimizer, PhaseEngine,
                        SchedState)
from repro.data.pipeline import DeviceDataset
from repro.optim import SGD, Momentum

WORKERS, STEPS, DIM, SAMPLES = 4, 65, 12, 256


def _convex_problem(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((SAMPLES, DIM))
    y = X @ rng.standard_normal(DIM) + 0.1 * rng.standard_normal(SAMPLES)
    return X, y


def _loss_fn(params, batch, rng):
    r = batch["x"] @ params["w"] - batch["y"]
    return 0.5 * jnp.mean(r * r), {}


def _params():
    return {"w": jnp.zeros(DIM)}


def _index_draws(seed=1, steps=STEPS):
    rng = np.random.default_rng(seed)
    return rng.integers(0, SAMPLES, (steps, WORKERS, 8))


def _batches(X, y, idx):
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    return [{"x": Xj[idx[t]], "y": yj[idx[t]]} for t in range(len(idx))]


# tuned so both kinds produce a non-trivial, non-degenerate decision
# sequence on this workload (some events, not every step)
ADAPTIVE = {
    "adaptive_threshold": AveragingSchedule("adaptive_threshold",
                                            disp_threshold=0.05,
                                            disp_ema_beta=0.5),
    "adaptive_budget": AveragingSchedule("adaptive_budget", comm_budget=8,
                                         budget_horizon=STEPS),
}


@pytest.mark.parametrize("name", list(ADAPTIVE))
def test_adaptive_engine_matches_host_full_trace(name):
    """Engine and host replay the identical decision sequence from
    their independently measured dispersion, and agree on the FULL
    per-step dispersion/loss traces — not just at averaging events."""
    X, y = _convex_problem()
    idx = _index_draws()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05), ADAPTIVE[name])
    kw = dict(num_workers=WORKERS, seed=3, record_every=1)
    f_eng, h_eng = engine.run(_params(), _batches(X, y, idx), **kw)
    f_host, h_host = engine.run_host(_params(), _batches(X, y, idx), **kw)

    # decision sequences are exactly equal (discrete — no tolerance)
    assert h_eng["averages"] == h_host["averages"] > 0
    assert [t for t, _ in h_eng["dispersion"]] == \
        [t for t, _ in h_host["dispersion"]]
    # non-degenerate: the schedule must skip some steps too
    assert h_eng["averages"] < STEPS
    np.testing.assert_allclose(np.asarray(f_eng["w"]),
                               np.asarray(f_host["w"]),
                               rtol=1e-6, atol=1e-7)
    # FULL per-step traces agree (65 points each)
    assert len(h_eng["disp_trace"]) == STEPS
    np.testing.assert_allclose([v for _, v in h_eng["disp_trace"]],
                               [v for _, v in h_host["disp_trace"]],
                               rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose([v for _, v in h_eng["loss"]],
                               [v for _, v in h_host["loss"]],
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("name", list(ADAPTIVE))
def test_adaptive_flat_tree_indexed_paths_agree(name):
    """flat-native (default), PR 2 flat, tree carry and the indexed
    on-device data plane all take the same averaging decisions and land
    on the same params."""
    X, y = _convex_problem()
    idx = _index_draws()
    kw = dict(num_workers=WORKERS, seed=3, record_every=1)
    mk = lambda **e: PhaseEngine(_loss_fn, SGD(lr=0.05), ADAPTIVE[name],
                                 **e)
    f_nat, h_nat = mk().run(_params(), _batches(X, y, idx), **kw)
    f_pr2, h_pr2 = mk(fused_opt=False).run(_params(), _batches(X, y, idx),
                                           **kw)
    f_tree, h_tree = mk(flat=False).run(_params(), _batches(X, y, idx),
                                        **kw)
    ds = DeviceDataset({"x": X, "y": y}, WORKERS, indices=idx)
    f_idx, h_idx = mk().run(_params(), ds, **kw)

    np.testing.assert_array_equal(np.asarray(f_nat["w"]),
                                  np.asarray(f_idx["w"]))
    assert h_nat == h_idx
    for f, h in ((f_pr2, h_pr2), (f_tree, h_tree)):
        assert h_nat["averages"] == h["averages"] > 0
        assert [t for t, _ in h_nat["dispersion"]] == \
            [t for t, _ in h["dispersion"]]
        np.testing.assert_allclose(np.asarray(f_nat["w"]),
                                   np.asarray(f["w"]),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("block", [1, 7, 32, 100])
def test_adaptive_decisions_invariant_to_phase_blocking(block):
    """SchedState rides the scan carry across run_phase boundaries, so
    phase blocking stays a pure perf knob for adaptive schedules too."""
    X, y = _convex_problem()
    idx = _index_draws()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05),
                         ADAPTIVE["adaptive_threshold"])
    kw = dict(num_workers=WORKERS, seed=0, record_every=1)
    ref, h_ref = engine.run(_params(), _batches(X, y, idx), phase_len=8,
                            **kw)
    got, h_got = engine.run(_params(), _batches(X, y, idx),
                            phase_len=block, **kw)
    np.testing.assert_array_equal(np.asarray(ref["w"]),
                                  np.asarray(got["w"]))
    assert h_ref == h_got


@pytest.mark.parametrize("name", list(ADAPTIVE))
def test_adaptive_checkpoint_resume_bit_identical(tmp_path, name):
    """Interrupt -> save_engine_state -> load -> resume == uninterrupted,
    bit for bit: the SchedState fields (EMA, credit, budget spent) are
    checkpointed, so the resumed run replays the adaptive decisions."""
    X, y = _convex_problem()
    idx = _index_draws(seed=7)
    mk = lambda: PhaseEngine(_loss_fn, Momentum(lr=0.05, mu=0.9),
                             ADAPTIVE[name],
                             outer=OuterOptimizer(lr=0.9, momentum=0.5))
    batches = _batches(X, y, idx)
    kw = dict(num_workers=WORKERS, record_every=8)

    f_full, h_full = mk().run(_params(), batches, seed=7, **kw)

    cut = 32
    _, h1, st = mk().run(_params(), batches[:cut], seed=7,
                         return_state=True, **kw)
    # mid-run: the stateful schedule has accumulated real state
    assert isinstance(st.sched, SchedState)
    assert int(st.sched.comm_spent) == h1["averages"]
    path = os.path.join(tmp_path, "ck")
    save_engine_state(path, st)

    loaded, at = load_engine_state(path, mk().init(_params(), WORKERS, 7))
    assert at == cut
    # every field — including each SchedState scalar — restored bit-exact
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    f_res, h2 = mk().run(None, batches[cut:], state=loaded, **kw)
    np.testing.assert_array_equal(np.asarray(f_full["w"]),
                                  np.asarray(f_res["w"]))
    assert h_full["dispersion"] == h1["dispersion"] + h2["dispersion"]
    assert h_full["disp_trace"] == h1["disp_trace"] + h2["disp_trace"]
    assert h_full["averages"] == h1["averages"] + h2["averages"] > 0


def test_pre_schedstate_checkpoint_still_loads(tmp_path):
    """Checkpoints written before EngineState carried SchedState (PR 3
    and earlier) must still load: the missing sched leaves are taken
    fresh (all-zero) from the like-state instead of tripping the
    leaf-count assert."""
    X, y = _convex_problem()
    idx = _index_draws()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05),
                         AveragingSchedule("periodic", 8))
    _, _, st = engine.run(_params(), _batches(X, y, idx)[:16],
                          num_workers=WORKERS, seed=1, return_state=True)
    path = os.path.join(tmp_path, "old")
    save_engine_state(path, st._replace(sched=()))  # PR 3 layout

    like = engine.init(_params(), WORKERS, 1)
    loaded, step = load_engine_state(path, like)
    assert step == 16 and isinstance(loaded.sched, SchedState)
    assert int(loaded.sched.comm_spent) == 0  # fresh bookkeeping
    np.testing.assert_array_equal(
        np.asarray(st.worker_params["w"]),
        np.asarray(loaded.worker_params["w"]))
    # and the resumed run proceeds normally
    f, h = engine.run(None, _batches(X, y, idx)[16:32], state=loaded,
                      num_workers=WORKERS, record_every=8)
    assert h["averages"] == 2 and np.isfinite(np.asarray(f["w"])).all()


def test_dispersion_trace_true_on_non_averaging_steps():
    """The Eq. 4 trace regression: between periodic events the recorded
    dispersion must be the true (growing) diagnostic, not 0.0 — and the
    engine's per-step values must match the host loop's."""
    X, y = _convex_problem()
    idx = _index_draws()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05),
                         AveragingSchedule("periodic", 8))
    kw = dict(num_workers=WORKERS, seed=3, record_every=1)
    _, h_eng = engine.run(_params(), _batches(X, y, idx), **kw)
    _, h_host = engine.run_host(_params(), _batches(X, y, idx), **kw)
    trace = dict(h_eng["disp_trace"])
    assert len(trace) == STEPS
    # every step from 2 on has genuinely dispersed workers (step 1 may
    # round to ~0 from identical init); non-averaging steps especially
    non_avg = [t for t in range(2, STEPS + 1) if t % 8]
    assert all(trace[t] > 0 for t in non_avg)
    # within a phase the dispersion grows from the post-average collapse
    assert trace[9] < trace[15]
    np.testing.assert_allclose([v for _, v in h_eng["disp_trace"]],
                               [v for _, v in h_host["disp_trace"]],
                               rtol=1e-5, atol=1e-8)
    # at event steps the trace equals the event diagnostic (pre-average)
    for t, v in h_eng["dispersion"]:
        assert trace[t] == v


def test_run_phase_trace_matches_host_per_step():
    """The raw run_phase trace (the engine's only host transfer) carries
    the true per-step dispersion for a rare-averaging schedule."""
    from repro.core import tree_stack
    X, y = _convex_problem()
    idx = _index_draws()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05),
                         AveragingSchedule("periodic", 16))
    state, layout = engine.start_state(_params(), WORKERS, seed=3)
    _, trace = engine.run_phase(state, tree_stack(_batches(X, y, idx)),
                                layout=layout)
    disp = np.asarray(trace["dispersion"])
    codes = np.asarray(trace["avg_code"])
    assert disp.shape == (STEPS,)
    assert (disp[1:] > 0).all()          # true value on EVERY step
    assert (codes[15::16] == 2).all()    # periodic-16 events intact
    _, h_host = engine.run_host(_params(), _batches(X, y, idx),
                                num_workers=WORKERS, seed=3,
                                record_every=1)
    np.testing.assert_allclose(disp, [v for _, v in h_host["disp_trace"]],
                               rtol=1e-5, atol=1e-8)


def test_inner_groups_must_divide_workers_eagerly():
    """M % inner_groups != 0 must fail with a clear eager error in
    init/run/run_host — not an opaque reshape error mid-trace."""
    X, y = _convex_problem()
    idx = _index_draws()
    sch = AveragingSchedule("hierarchical", inner_phase_len=5,
                            outer_phase_len=20, inner_groups=3)
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05), sch)
    with pytest.raises(ValueError, match="inner_groups"):
        engine.init(_params(), WORKERS)  # 4 % 3 != 0
    with pytest.raises(ValueError, match="inner_groups"):
        engine.run(_params(), _batches(X, y, idx), num_workers=WORKERS)
    with pytest.raises(ValueError, match="inner_groups"):
        engine.run_host(_params(), _batches(X, y, idx),
                        num_workers=WORKERS)
    # a dividing count passes through
    PhaseEngine(_loss_fn, SGD(lr=0.05), AveragingSchedule(
        "hierarchical", inner_phase_len=5, outer_phase_len=20,
        inner_groups=2)).init(_params(), WORKERS)


class TestTrainCliValidation:
    """train.py schedule-arg validation fails at parse time (argparse
    error, exit code 2) instead of deep inside a trace — the
    hierarchical inner>=outer case used to silently never inner-average
    and an invalid stochastic zeta surfaced as a raw ValueError."""

    def _error(self, argv):
        from repro.launch.train import main
        with pytest.raises(SystemExit) as e:
            main(argv)
        assert e.value.code == 2

    def test_hierarchical_inner_ge_outer_rejected(self):
        self._error(["--avg", "hierarchical", "--phase-len", "10",
                     "--outer-phase-len", "5"])
        self._error(["--avg", "hierarchical", "--phase-len", "10",
                     "--outer-phase-len", "10"])

    def test_stochastic_needs_nonzero_zeta(self):
        self._error(["--avg", "stochastic", "--zeta", "0.0"])
        self._error(["--avg", "stochastic", "--zeta", "1.5"])

    def test_adaptive_threshold_needs_threshold(self):
        self._error(["--avg", "adaptive_threshold"])

    def test_adaptive_budget_needs_feasible_budget(self):
        self._error(["--avg", "adaptive_budget"])
        self._error(["--avg", "adaptive_budget", "--comm-budget", "200",
                     "--steps", "100"])


def test_adaptive_with_outer_optimizer_matches_host():
    """Adaptive events drive the DiLoCo-style outer momentum step too."""
    X, y = _convex_problem()
    idx = _index_draws(seed=5)
    engine = PhaseEngine(_loss_fn, Momentum(lr=0.05, mu=0.9),
                         ADAPTIVE["adaptive_threshold"],
                         outer=OuterOptimizer(lr=0.8, momentum=0.5))
    kw = dict(num_workers=WORKERS, seed=5, record_every=1)
    f_eng, h_eng = engine.run(_params(), _batches(X, y, idx), **kw)
    f_host, h_host = engine.run_host(_params(), _batches(X, y, idx), **kw)
    assert h_eng["averages"] == h_host["averages"] > 0
    assert [t for t, _ in h_eng["dispersion"]] == \
        [t for t, _ in h_host["dispersion"]]
    np.testing.assert_allclose(np.asarray(f_eng["w"]),
                               np.asarray(f_host["w"]),
                               rtol=1e-6, atol=1e-7)
