"""Phase-engine correctness: the compiled phase program (one donated
scan per phase, on-device averaging decisions) must match the step-by-step
host-driven loop numerically — same final consensus params, same loss
trace, same averaging events — for all four paper schedules, and be
invariant to how steps are blocked into phases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AveragingSchedule, EngineState, LocalSGD,
                        OuterOptimizer, PhaseEngine, consensus, tree_stack)
from repro.optim import SGD, Momentum

WORKERS, STEPS, DIM, SAMPLES = 4, 65, 12, 256


def _convex_problem(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((SAMPLES, DIM))
    w_true = rng.standard_normal(DIM)
    y = X @ w_true + 0.1 * rng.standard_normal(SAMPLES)
    return jnp.asarray(X), jnp.asarray(y)


def _loss_fn(params, batch, rng):
    r = batch["x"] @ params["w"]["inner"] - batch["y"]
    return 0.5 * jnp.mean(r * r), {}


def _params():
    # nested dict on purpose: the engine must be tree-structure agnostic
    return {"w": {"inner": jnp.zeros(DIM)}}


def _batches(X, y, workers=WORKERS, steps=STEPS, seed=1):
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        idx = rng.integers(0, SAMPLES, (workers, 8))
        yield {"x": X[idx], "y": y[idx]}


SCHEDULES = {
    "oneshot": AveragingSchedule("oneshot"),
    "minibatch": AveragingSchedule("minibatch"),
    "periodic": AveragingSchedule("periodic", 8),
    "stochastic": AveragingSchedule("stochastic", zeta=0.2),
    "hierarchical": AveragingSchedule("hierarchical", inner_phase_len=5,
                                      outer_phase_len=20, inner_groups=2),
    # stateful kinds: the engine's decisions consume the on-device
    # per-step dispersion through SchedState; the host loop must replay
    # the identical decision sequence from its own dispersion stream
    "adaptive_threshold": AveragingSchedule("adaptive_threshold",
                                            disp_threshold=0.05,
                                            disp_ema_beta=0.5),
    "adaptive_budget": AveragingSchedule("adaptive_budget", comm_budget=6,
                                         budget_horizon=STEPS),
}


@pytest.mark.parametrize("name", list(SCHEDULES))
def test_engine_matches_host_loop(name):
    """Compiled phase == step-by-step dispatch, bit-for-bit history."""
    X, y = _convex_problem()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05), SCHEDULES[name])
    f_eng, h_eng = engine.run(_params(), _batches(X, y), seed=3,
                              num_workers=WORKERS, record_every=1)
    f_host, h_host = engine.run_host(_params(), _batches(X, y), seed=3,
                                     num_workers=WORKERS, record_every=1)
    np.testing.assert_allclose(np.asarray(f_eng["w"]["inner"]),
                               np.asarray(f_host["w"]["inner"]),
                               rtol=1e-6, atol=1e-7)
    assert h_eng["averages"] == h_host["averages"]
    assert [t for t, _ in h_eng["dispersion"]] == \
        [t for t, _ in h_host["dispersion"]]
    np.testing.assert_allclose([v for _, v in h_eng["loss"]],
                               [v for _, v in h_host["loss"]],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose([v for _, v in h_eng["dispersion"]],
                               [v for _, v in h_host["dispersion"]],
                               rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("block", [1, 7, 32, 100])
def test_engine_block_size_invariance(block):
    """Phase blocking is a perf knob, not semantics: any block size gives
    the identical trajectory (decisions are per-step, on-device)."""
    X, y = _convex_problem()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05),
                         AveragingSchedule("periodic", 8))
    ref, _ = engine.run(_params(), _batches(X, y), num_workers=WORKERS,
                        seed=0, phase_len=8)
    got, _ = engine.run(_params(), _batches(X, y), num_workers=WORKERS,
                        seed=0, phase_len=block)
    np.testing.assert_array_equal(np.asarray(ref["w"]["inner"]),
                                  np.asarray(got["w"]["inner"]))


def test_engine_unroll_is_equivalent():
    """scan_unroll (the CPU-backend speed knob) must not change numerics,
    including on partial final blocks."""
    X, y = _convex_problem()
    sch = AveragingSchedule("periodic", 8)
    ref, h_ref = PhaseEngine(_loss_fn, SGD(lr=0.05), sch).run(
        _params(), _batches(X, y), num_workers=WORKERS, seed=1,
        record_every=1)
    got, h_got = PhaseEngine(_loss_fn, SGD(lr=0.05), sch,
                             scan_unroll=True).run(
        _params(), _batches(X, y), num_workers=WORKERS, seed=1,
        record_every=1)
    np.testing.assert_allclose(np.asarray(ref["w"]["inner"]),
                               np.asarray(got["w"]["inner"]),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose([v for _, v in h_ref["loss"]],
                               [v for _, v in h_got["loss"]],
                               rtol=1e-6, atol=1e-7)


def test_engine_with_outer_optimizer_matches_host():
    """The DiLoCo-style outer optimizer state threads through the scan
    carry exactly as through the host loop."""
    X, y = _convex_problem()
    engine = PhaseEngine(_loss_fn, Momentum(lr=0.05, mu=0.9),
                         AveragingSchedule("periodic", 8),
                         outer=OuterOptimizer(lr=0.8, momentum=0.5))
    f_eng, h_eng = engine.run(_params(), _batches(X, y), seed=5,
                              num_workers=WORKERS, record_every=1)
    f_host, h_host = engine.run_host(_params(), _batches(X, y), seed=5,
                                     num_workers=WORKERS, record_every=1)
    np.testing.assert_allclose(np.asarray(f_eng["w"]["inner"]),
                               np.asarray(f_host["w"]["inner"]),
                               rtol=1e-6, atol=1e-7)
    assert h_eng["averages"] == h_host["averages"] == STEPS // 8


def test_engine_state_resumable():
    """run_phase is a pure state transition: splitting one run into two
    run_phase calls equals one big call (checkpoint/resume safety)."""
    X, y = _convex_problem()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05),
                         AveragingSchedule("stochastic", zeta=0.3))
    blocks = list(_batches(X, y, steps=24))
    s1, layout = engine.start_state(_params(), WORKERS, seed=9)
    s1, tr_a = engine.run_phase(s1, tree_stack(blocks[:10]), layout=layout)
    s1, tr_b = engine.run_phase(s1, tree_stack(blocks[10:]), layout=layout)
    s2, _ = engine.start_state(_params(), WORKERS, seed=9)
    s2, tr = engine.run_phase(s2, tree_stack(blocks), layout=layout)
    assert isinstance(s1, EngineState) and int(s1.step) == int(s2.step) == 24
    s1, s2 = engine.to_tree(layout, s1), engine.to_tree(layout, s2)
    np.testing.assert_array_equal(
        np.asarray(consensus(s1.worker_params)["w"]["inner"]),
        np.asarray(consensus(s2.worker_params)["w"]["inner"]))
    np.testing.assert_array_equal(
        np.concatenate([tr_a["avg_code"], tr_b["avg_code"]]),
        np.asarray(tr["avg_code"]))


def test_engine_history_semantics():
    """Averaging count, dispersion timestamps and loss records follow the
    schedule; dispersion is measured BEFORE the average collapses it."""
    X, y = _convex_problem()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05),
                         AveragingSchedule("periodic", 10))
    _, hist = engine.run(_params(), _batches(X, y, steps=40),
                         num_workers=WORKERS, seed=0, record_every=10)
    assert hist["averages"] == 4
    assert [t for t, _ in hist["dispersion"]] == [10, 20, 30, 40]
    assert [t for t, _ in hist["loss"]] == [10, 20, 30, 40]
    assert all(v > 0 for _, v in hist["dispersion"])


def test_engine_eval_fns_at_record_boundaries():
    X, y = _convex_problem()
    engine = PhaseEngine(_loss_fn, SGD(lr=0.05),
                         AveragingSchedule("periodic", 8))
    calls = []

    def eval_fn(p):
        calls.append(p["w"]["inner"].shape)
        return 1.0

    def worker_eval_fn(wp):
        assert jax.tree.leaves(wp)[0].shape[0] == WORKERS
        return 2.0

    _, hist = engine.run(_params(), _batches(X, y, steps=50),
                         num_workers=WORKERS, seed=0, record_every=20,
                         eval_fn=eval_fn, worker_eval_fn=worker_eval_fn)
    assert [t for t, _ in hist["eval"]] == [20, 40]
    assert [t for t, _ in hist["worker_eval"]] == [20, 40]
    assert calls == [(DIM,), (DIM,)]  # consensus params, no worker axis


def test_localsgd_average_without_outer_state_falls_back_to_mean():
    """Legacy contract: with an outer optimizer configured but no state
    yet, average() applies the paper's plain mean instead of crashing."""
    algo = LocalSGD(_loss_fn, SGD(lr=0.05), AveragingSchedule("periodic", 8),
                    outer=OuterOptimizer(lr=0.8, momentum=0.5))
    wp = {"w": {"inner": jnp.arange(WORKERS * DIM, dtype=jnp.float32)
                .reshape(WORKERS, DIM)}}
    avg_wp, outer_state, disp = algo.average(wp, None)
    assert outer_state is None
    np.testing.assert_allclose(
        np.asarray(avg_wp["w"]["inner"]),
        np.broadcast_to(np.asarray(wp["w"]["inner"]).mean(0), (WORKERS, DIM)),
        rtol=1e-6)
    assert float(disp) > 0


def test_localsgd_wrapper_delegates_to_engine():
    """LocalSGD.run is a thin wrapper: identical output to PhaseEngine.run
    with the same seed and schedule."""
    X, y = _convex_problem()
    sch = AveragingSchedule("periodic", 8)
    algo = LocalSGD(_loss_fn, SGD(lr=0.05), sch)
    f_a, h_a = algo.run(_params(), _batches(X, y), num_workers=WORKERS,
                        seed=2, record_every=5)
    f_b, h_b = algo.engine.run(_params(), _batches(X, y),
                               num_workers=WORKERS, seed=2, record_every=5)
    np.testing.assert_array_equal(np.asarray(f_a["w"]["inner"]),
                                  np.asarray(f_b["w"]["inner"]))
    assert h_a["loss"] == h_b["loss"]
    assert h_a["averages"] == h_b["averages"]


# --------------------------------------------------------------------------
# plane-form state: what run() carries between phases
# --------------------------------------------------------------------------

def test_plane_form_roundtrip_and_phase_match_tree_form():
    """to_planes / to_tree are exact inverses, a flat-native state runs
    only in plane form, and a phase run on the plane form lands on the
    state the tree carry (``flat=False``) reaches at f32 roundoff."""
    X, y = _convex_problem()
    mk = lambda **kw: PhaseEngine(_loss_fn, Momentum(lr=0.05, mu=0.9),
                                  SCHEDULES["periodic"],
                                  outer=OuterOptimizer(lr=0.8, momentum=0.5),
                                  **kw)
    engine = mk()
    tree = engine.init(_params(), WORKERS, seed=4)
    layout = engine.plane_layout(tree)
    assert layout is not None
    planes = engine.to_planes(layout, tree)
    assert planes.worker_params.shape == (WORKERS, DIM)
    back = engine.to_tree(layout, planes)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    block = tree_stack(list(_batches(X, y, steps=16)))
    with pytest.raises(AssertionError, match="plane form"):
        engine.run_phase(jax.tree.map(jnp.array, tree), block)
    s_tree, tr_tree = mk(flat=False).run_phase(
        jax.tree.map(jnp.array, tree), block)
    s_pl, tr_pl = engine.run_phase(planes, block, layout=layout)
    s_pl = engine.to_tree(layout, s_pl)
    for a, b in zip(jax.tree.leaves(s_pl), jax.tree.leaves(s_tree)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(tr_pl["avg_code"], tr_tree["avg_code"])
    np.testing.assert_allclose(tr_pl["loss"], tr_tree["loss"], rtol=1e-6)


def test_plane_layout_none_for_tree_carries():
    """No plane form where the phase carries a tree: flat=False, or an
    optimizer without the plane protocol (fused_opt=False)."""
    for kw in (dict(flat=False), dict(fused_opt=False)):
        engine = PhaseEngine(_loss_fn, Momentum(lr=0.05, mu=0.9),
                             SCHEDULES["periodic"], **kw)
        state = engine.init(_params(), WORKERS)
        assert engine.plane_layout(state) is None
        started, layout = engine.start_state(_params(), WORKERS)
        assert layout is None
        assert isinstance(started.worker_params, dict)


@pytest.mark.parametrize("name", ["minibatch", "periodic", "hierarchical",
                                  "adaptive_threshold"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_engine_matches_ref_engine(name, dtype):
    """The engine's Pallas path (interpret mode here; Mosaic on a TPU)
    against its jnp reference path, through run(): the same decisions,
    params and losses at f32 roundoff. bf16 params take the kernels'
    rounding path with the one-dtype static code."""
    X, y = _convex_problem()
    params = jax.tree.map(lambda x: x.astype(dtype), _params())
    kw = dict(num_workers=WORKERS, seed=2, record_every=1)
    out = {}
    for impl in ("pallas", "ref"):
        engine = PhaseEngine(_loss_fn, Momentum(lr=0.05, mu=0.9),
                             SCHEDULES[name], kernel_impl=impl)
        out[impl] = engine.run(params, list(_batches(X, y, steps=24)),
                               **kw)
    (f_p, h_p), (f_r, h_r) = out["pallas"], out["ref"]
    assert f_p["w"]["inner"].dtype == dtype
    assert h_p["averages"] == h_r["averages"]
    assert [t for t, _ in h_p["dispersion"]] == \
        [t for t, _ in h_r["dispersion"]]
    np.testing.assert_allclose(
        np.asarray(f_p["w"]["inner"], np.float32),
        np.asarray(f_r["w"]["inner"], np.float32), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose([v for _, v in h_p["loss"]],
                               [v for _, v in h_r["loss"]], rtol=1e-5)
