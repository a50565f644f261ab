"""Flat-native optimizer planes: FlatOptSpec + fused opt_step kernel.

Three layers of guarantees:
  1. The plane-resident optimizer update (``plane_update_ref`` /
     ``opt_step``) is BIT-EXACT against the pytree ``optimizer.apply``
     for SGD / Momentum(+nesterov) / AdamW across f32/bf16/f16 params
     and all lr schedules (constant, inverse, exponential_epoch) — the
     plane always holds the exact float32 image of the tree.
  2. The Pallas opt_step kernel (interpret mode on CPU) matches the
     kernels/ref.py jnp twin across kinds, modes, padding and rounding
     codes.
  3. The flat-native engine (fused_opt=True, the default) reproduces
     the PR 2 flat path and the tree path for Momentum/AdamW across
     averaging schedules, incl. mixed-dtype trees and the outer
     optimizer.

Plus the satellite regressions: lr schedules produce strong float32 for
Python-int steps, and in-memory list sources skip the Prefetcher.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AveragingSchedule, FlatOptSpec, FlatSpec,
                        OuterOptimizer, PhaseEngine)
from repro.core import engine as engine_mod
from repro.kernels.opt_step import opt_step
from repro.kernels.ref import opt_step_ref, plane_update_ref
from repro.optim import SGD, AdamW, Momentum, schedules

KEY = jax.random.PRNGKey(0)
WORKERS, STEPS, DIM, SAMPLES = 4, 49, 12, 256

OPTIMIZERS = {
    "sgd": lambda lr: SGD(lr=lr),
    "momentum": lambda lr: Momentum(lr=lr, mu=0.9),
    "nesterov": lambda lr: Momentum(lr=lr, mu=0.9, nesterov=True),
    "adamw": lambda lr: AdamW(lr=lr, weight_decay=0.01),
}
LRS = {
    "const": 0.05,
    "inverse": schedules.inverse(1.0, 10.0),
    "exp_epoch": schedules.exponential_epoch(0.1, 0.9, 5),
}


def _worker_tree(dt, m=WORKERS):
    ks = jax.random.split(KEY, 2)
    return {"a": jax.random.normal(ks[0], (m, 3, 5)).astype(dt),
            "b": (jax.random.normal(ks[1], (m, 7)).astype(dt),)}


# --------------------------------------------------------------------------
# 1. FlatOptSpec layout
# --------------------------------------------------------------------------

class TestFlatOptSpec:
    def test_state_plane_counts(self):
        tree = _worker_tree(jnp.float32)
        spec = FlatSpec.of(tree)
        for name, mk in OPTIMIZERS.items():
            opt = mk(0.1)
            ospec = FlatOptSpec.of(spec, jax.vmap(opt.init)(tree))
            assert ospec is not None
            assert ospec.num_planes == opt.state_planes, name

    def test_pack_unpack_roundtrip(self):
        tree = _worker_tree(jnp.float32)
        spec = FlatSpec.of(tree)
        opt = AdamW(lr=0.1)
        state = jax.vmap(opt.init)(tree)
        ospec = FlatOptSpec.of(spec, state)
        planes = ospec.pack(state)
        assert len(planes) == 2
        assert all(p.shape == (WORKERS, spec.width) for p in planes)
        back = ospec.unpack(planes)
        assert jax.tree.structure(back) == jax.tree.structure(state)
        for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_misaligned_state_rejected(self):
        tree = _worker_tree(jnp.float32)
        spec = FlatSpec.of(tree)
        # wrong shape
        assert FlatOptSpec.of(
            spec, {"v": jnp.zeros((WORKERS, 9))}) is None
        # wrong dtype
        bad = jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.int32), tree)
        assert FlatOptSpec.of(spec, bad) is None
        # SGD's empty state is fine (0 planes)
        ospec = FlatOptSpec.of(spec, ())
        assert ospec is not None and ospec.num_planes == 0
        assert ospec.pack(()) == ()

    def test_rounding_codes(self):
        f32 = FlatSpec.of(_worker_tree(jnp.float32))
        assert f32.rounding_codes() is None
        mixed = FlatSpec.of({
            "a": jnp.zeros((2, 3)),
            "b": jnp.zeros((2, 4), jnp.bfloat16),
            "c": jnp.zeros((2, 2), jnp.float16)})
        codes = mixed.rounding_codes()
        np.testing.assert_array_equal(codes, [0, 0, 0, 1, 1, 1, 1, 2, 2])

    @pytest.mark.parametrize("dt,code", [(jnp.bfloat16, 1),
                                         (jnp.float16, 2)])
    def test_rounding_codes_one_dtype_is_scalar(self, dt, code):
        """A one-dtype tree needs no P-wide codes row: its code is one
        Python int, a trace-time constant."""
        codes = FlatSpec.of(_worker_tree(dt)).rounding_codes()
        assert type(codes) is int and codes == code


# an all-f32 plane has no codes at all, so no static 0
@pytest.mark.parametrize("code,static", [(0, False), (1, False), (2, False),
                                         (1, True), (2, True)])
def test_kernel_round_codes_bit_exact(code, static):
    """The kernels round through f16 in f32 arithmetic (Mosaic has no f16
    vectors on v5e); that emulation equals ``astype`` bit for bit — over
    normals, f16 subnormals, ties, overflow to inf and f32 denormals —
    whether the code is a per-column row or a one-dtype static int."""
    from repro.kernels.avg_disp import round_codes
    from repro.kernels.ref import round_to_codes
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(65536)
         * 10.0 ** rng.uniform(-12, 6, 65536)).astype(np.float32)
    edges = np.array([0.0, -0.0, 65504, 65505, 65519.99, 65520, -65520,
                      65536, 1e30, np.inf, -np.inf, 2 ** -24, 2 ** -25,
                      3 * 2 ** -25, 2 ** -14, 1.5 * 2 ** -15, 6e-8, 1e-40,
                      -1e-40, 1.5 * 2 ** -24, 2.5 * 2 ** -24, 1 + 2 ** -11,
                      1 + 3 * 2 ** -11, 3.4e38], np.float32)
    x = jnp.asarray(np.concatenate([x, edges]).reshape(2, -1))
    codes = code if static else jnp.full((x.shape[1],), code, jnp.float32)
    got = jax.jit(lambda v: round_codes(v, codes))(x)
    dt = {0: jnp.float32, 1: jnp.bfloat16, 2: jnp.float16}[code]
    want = x.astype(dt).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  np.asarray(want).view(np.int32))
    np.testing.assert_array_equal(
        np.asarray(round_to_codes(x, codes)).view(np.int32),
        np.asarray(want).view(np.int32))


# --------------------------------------------------------------------------
# 2. plane update == pytree optimizer.apply, bit-exact
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("lr_name", list(LRS))
@pytest.mark.parametrize("opt_name", list(OPTIMIZERS))
def test_plane_update_bit_exact(dt, lr_name, opt_name):
    opt = OPTIMIZERS[opt_name](LRS[lr_name])
    tree = _worker_tree(dt)
    spec = FlatSpec.of(tree)
    state = jax.vmap(opt.init)(tree)
    ospec = FlatOptSpec.of(spec, state)
    grads = jax.tree.map(
        lambda x: (jax.random.normal(jax.random.fold_in(KEY, 1),
                                     x.shape) * 0.1).astype(x.dtype), tree)
    plane, planes = spec.pack(tree), ospec.pack(state)
    for step in (1, 2, 3):  # multi-step: moments accumulate
        step_j = jnp.asarray(step, jnp.int32)
        tree, state = opt.apply(tree, grads, state, step_j)
        plane, planes = plane_update_ref(
            plane, spec.pack(grads), planes, opt.plane_scalars(step_j),
            kind=opt.plane_kind, codes=spec.rounding_codes(),
            **opt.plane_hypers())
    for a, b in zip(jax.tree.leaves(spec.unpack(plane)),
                    jax.tree.leaves(tree)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    for a, b in zip(jax.tree.leaves(ospec.unpack(planes)),
                    jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# 3. opt_step Pallas kernel == jnp ref twin
# --------------------------------------------------------------------------

KERNEL_CASES = [
    ("sgd", 0, {}),
    ("momentum", 1, dict(mu=0.9, nesterov=True)),
    ("momentum", 1, dict(mu=0.9, nesterov=False)),
    ("adamw", 2, dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.01)),
]


@pytest.mark.parametrize("m,p,bp,groups", [
    (4, 300, 128, 1),    # padding path
    (8, 1024, 256, 2),
    (16, 33, 1024, 4),   # single partial block
])
@pytest.mark.parametrize("kind,nstate,hyp", KERNEL_CASES,
                         ids=[f"{k}{i}" for i, (k, _, _)
                              in enumerate(KERNEL_CASES)])
def test_opt_step_kernel_matches_ref(kind, nstate, hyp, m, p, bp, groups):
    ks = jax.random.split(jax.random.PRNGKey(p), 3 + nstate)
    x = jax.random.normal(ks[0], (m, p))
    g = jax.random.normal(ks[1], (m, p)) * 0.1
    # second moments must stay >= 0 for adamw
    planes = tuple(jnp.abs(jax.random.normal(ks[3 + i], (m, p))) * 0.01
                   for i in range(nstate))
    scal = jnp.asarray([0.05, 1 - 0.9 ** 3, 1 - 0.95 ** 3, 0.0],
                       jnp.float32)
    codes = np.zeros(p, np.float32)
    codes[p // 3:2 * p // 3] = 1
    codes[2 * p // 3:] = 2
    for mode in ("none", "mean", "group"):
        for cd in (None, codes):
            got = opt_step(x, g, planes, scal, kind=kind, mode=mode,
                           groups=groups, codes=cd, block_p=bp, **hyp)
            want = opt_step_ref(
                x, g, planes, scal, kind=kind, mode=mode, groups=groups,
                codes=None if cd is None else jnp.asarray(cd), **hyp)
            for a, b in zip([got[0], *got[1], got[2]],
                            [want[0], *want[1], want[2]]):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# 4. engine: flat-native == PR 2 flat == tree across schedules
# --------------------------------------------------------------------------

def _convex_problem(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((SAMPLES, DIM))
    y = X @ rng.standard_normal(DIM) + 0.1 * rng.standard_normal(SAMPLES)
    return jnp.asarray(X), jnp.asarray(y)


def _loss_fn(params, batch, rng):
    r = batch["x"] @ params["w"] - batch["y"]
    return 0.5 * jnp.mean(r * r), {}


def _batches(X, y, seed=1, steps=STEPS):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, SAMPLES, (steps, WORKERS, 8))
    return [{"x": X[idx[t]], "y": y[idx[t]]} for t in range(steps)]


ENGINE_SCHEDULES = {
    "minibatch": AveragingSchedule("minibatch"),
    "periodic": AveragingSchedule("periodic", 8),
    "stochastic": AveragingSchedule("stochastic", zeta=0.2),
    "hierarchical": AveragingSchedule("hierarchical", inner_phase_len=5,
                                      outer_phase_len=20, inner_groups=2),
    "adaptive_threshold": AveragingSchedule("adaptive_threshold",
                                            disp_threshold=0.05,
                                            disp_ema_beta=0.5),
    "adaptive_budget": AveragingSchedule("adaptive_budget", comm_budget=6,
                                         budget_horizon=STEPS),
}


@pytest.mark.parametrize("sched", list(ENGINE_SCHEDULES))
@pytest.mark.parametrize("opt_name", ["nesterov", "adamw"])
def test_flat_native_engine_matches_flat_and_tree(opt_name, sched):
    X, y = _convex_problem()
    kw = dict(num_workers=WORKERS, seed=3, record_every=1)
    mk = lambda **e: PhaseEngine(
        _loss_fn, OPTIMIZERS[opt_name](schedules.inverse(2.0, 20.0)),
        ENGINE_SCHEDULES[sched], **e)
    f_nat, h_nat = mk().run({"w": jnp.zeros(DIM)}, _batches(X, y), **kw)
    f_pr2, h_pr2 = mk(fused_opt=False).run({"w": jnp.zeros(DIM)},
                                           _batches(X, y), **kw)
    f_tree, h_tree = mk(flat=False).run({"w": jnp.zeros(DIM)},
                                        _batches(X, y), **kw)
    # flat-native vs PR 2 flat: identical f32 plane math -> bit-exact
    np.testing.assert_array_equal(np.asarray(f_nat["w"]),
                                  np.asarray(f_pr2["w"]))
    np.testing.assert_allclose(np.asarray(f_nat["w"]),
                               np.asarray(f_tree["w"]),
                               rtol=1e-6, atol=1e-7)
    for h in (h_pr2, h_tree):
        assert h_nat["averages"] == h["averages"]
        assert [t for t, _ in h_nat["dispersion"]] == \
            [t for t, _ in h["dispersion"]]
        np.testing.assert_allclose([v for _, v in h_nat["loss"]],
                                   [v for _, v in h["loss"]],
                                   rtol=1e-6, atol=1e-7)


def test_flat_native_engine_bf16_matches_tree():
    """Mixed-dtype trees: the plane path rounds through the leaf dtypes
    after every update AND at averaging events, tracking the tree path
    to f32 roundoff (the update math itself is bit-exact — see
    test_plane_update_bit_exact — residual ulps come from XLA fusing
    the two vjp programs differently)."""
    X, y = _convex_problem()

    def loss(params, batch, rng):
        w = params["w"].astype(jnp.float32) + params["wb"].astype(jnp.float32)
        r = batch["x"].astype(jnp.float32) @ w - batch["y"]
        return 0.5 * jnp.mean(r * r), {}

    p0 = {"w": jnp.zeros(DIM), "wb": jnp.zeros(DIM, jnp.bfloat16)}
    kw = dict(num_workers=WORKERS, seed=3, record_every=1)
    mk = lambda **e: PhaseEngine(loss, Momentum(lr=0.05, mu=0.9),
                                 AveragingSchedule("periodic", 8), **e)
    f_nat, h_nat = mk().run(p0, _batches(X, y), **kw)
    f_tree, h_tree = mk(flat=False).run(p0, _batches(X, y), **kw)
    for k in p0:
        np.testing.assert_allclose(np.asarray(f_nat[k], np.float32),
                                   np.asarray(f_tree[k], np.float32),
                                   rtol=1e-5, atol=1e-6)
    assert h_nat["averages"] == h_tree["averages"]


def test_flat_native_engine_bf16_outer_matches_tree():
    """Mixed-dtype params + OuterOptimizer: the outer averaging event
    must round the consensus target and the updated average through the
    leaf dtypes like ``OuterOptimizer.apply`` does — without it the
    flat path drifts from the tree path a little more at every
    averaging event (review regression)."""
    X, y = _convex_problem()

    def loss(params, batch, rng):
        w = params["w"].astype(jnp.float32) + params["wb"].astype(jnp.float32)
        r = batch["x"].astype(jnp.float32) @ w - batch["y"]
        return 0.5 * jnp.mean(r * r), {}

    p0 = {"w": jnp.zeros(DIM), "wb": jnp.zeros(DIM, jnp.bfloat16)}
    kw = dict(num_workers=WORKERS, seed=3, record_every=1)
    mk = lambda **e: PhaseEngine(
        loss, Momentum(lr=0.05, mu=0.9), AveragingSchedule("periodic", 4),
        outer=OuterOptimizer(lr=0.9, momentum=0.5), **e)
    f_nat, h_nat = mk().run(p0, _batches(X, y), **kw)
    f_tree, h_tree = mk(flat=False).run(p0, _batches(X, y), **kw)
    for k in p0:
        np.testing.assert_allclose(np.asarray(f_nat[k], np.float32),
                                   np.asarray(f_tree[k], np.float32),
                                   rtol=1e-5, atol=1e-6)
    assert h_nat["averages"] == h_tree["averages"]


def test_flat_native_with_outer_matches_pr2():
    X, y = _convex_problem()
    kw = dict(num_workers=WORKERS, seed=5, record_every=1)
    mk = lambda **e: PhaseEngine(
        _loss_fn, Momentum(lr=0.05, mu=0.9),
        AveragingSchedule("periodic", 8),
        outer=OuterOptimizer(lr=0.8, momentum=0.5), **e)
    f_a, h_a = mk().run({"w": jnp.zeros(DIM)}, _batches(X, y), **kw)
    f_b, h_b = mk(fused_opt=False).run({"w": jnp.zeros(DIM)},
                                       _batches(X, y), **kw)
    np.testing.assert_array_equal(np.asarray(f_a["w"]),
                                  np.asarray(f_b["w"]))
    assert h_a == h_b


def test_unsupported_optimizer_falls_back():
    """An optimizer without the plane protocol still runs under
    flat=True (per-step pack/unpack path)."""
    class Plain:
        def init(self, params):
            return ()

        def apply(self, params, grads, state, step):
            return jax.tree.map(lambda p, g: p - 0.05 * g, params,
                                grads), state

    X, y = _convex_problem()
    eng = PhaseEngine(_loss_fn, Plain(), AveragingSchedule("periodic", 8))
    f, hist = eng.run({"w": jnp.zeros(DIM)}, _batches(X, y),
                      num_workers=WORKERS, seed=0)
    assert hist["averages"] == STEPS // 8
    assert np.isfinite(np.asarray(f["w"])).all()


# --------------------------------------------------------------------------
# Satellites: schedule dtypes, prefetch auto-select
# --------------------------------------------------------------------------

def test_schedules_cast_python_int_step_to_strong_f32():
    """Host-path calls (Python int step) must produce the same strong
    float32 value as the engine's traced int32 step — no weak types, no
    float64 promotion."""
    for fn in (schedules.constant(0.1), schedules.inverse(1.0, 10.0),
               schedules.exponential_epoch(0.1, 0.9, 5)):
        host = fn(7)
        assert host.dtype == jnp.float32 and not host.weak_type
        traced = fn(jnp.asarray(7, jnp.int32))
        assert traced.dtype == jnp.float32 and not traced.weak_type
        np.testing.assert_array_equal(np.asarray(host), np.asarray(traced))


def test_list_source_skips_prefetcher(monkeypatch):
    """run(prefetch=True) must not spawn a Prefetcher thread for a
    materialized list source — only true streams pay for staging."""
    X, y = _convex_problem()
    batches = _batches(X, y, steps=16)

    def boom(*a, **k):
        raise AssertionError("Prefetcher built for an in-memory list")

    monkeypatch.setattr(engine_mod, "Prefetcher", boom)
    eng = PhaseEngine(_loss_fn, SGD(lr=0.05), AveragingSchedule("periodic", 8))
    f, hist = eng.run({"w": jnp.zeros(DIM)}, batches, num_workers=WORKERS,
                      seed=0, prefetch=True)
    assert hist["averages"] == 2
    # a generator source still uses it
    used = {}
    monkeypatch.undo()

    class Spy(engine_mod.Prefetcher):
        def __init__(self, it, **kw):
            used["yes"] = True
            super().__init__(it, **kw)

    monkeypatch.setattr(engine_mod, "Prefetcher", Spy)
    f2, h2 = eng.run({"w": jnp.zeros(DIM)}, iter(batches),
                     num_workers=WORKERS, seed=0, prefetch=True)
    assert used.get("yes")
    np.testing.assert_array_equal(np.asarray(f["w"]), np.asarray(f2["w"]))
    assert hist == h2
