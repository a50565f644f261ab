"""Elastic fault-tolerant training: deterministic fault injection.

Covers the repro.faults subsystem end to end:

  - FaultPlan validation / parsing (eager, actionable errors);
  - masked-mixing algebra (degraded_matrix / masked_event_matrix
    stochasticity, all-alive lowering to the plain mean);
  - bitwise equality of a scripted crash + rejoin + straggler run
    across the flat-native / flat / tree engine carries and the
    per-step run_host loop;
  - all-alive FaultPlan == no-fault engine, bit-exact, across all 7
    schedules (graceful degradation is BY CONSTRUCTION: a trivial plan
    lowers to the unmodified paths);
  - checkpoint resume inside a fault window == uninterrupted run;
  - the v0..v4 engine-state checkpoint ladder (fault rows are v4;
    older layouts load with fresh all-alive rows; v4 into a no-fault
    engine is refused);
  - crash-safe checkpoint saves (temp + atomic rename; torn/partial
    files refused with an actionable error);
  - the mesh's leaf carry with dead rows against the one-device leaf
    carry (subprocess with 8 host devices, like tests/test_sharded.py);
  - Dirichlet label-skew (non-IID) worker shards;
  - Prefetcher producer-failure propagation without deadlock;
  - Topology.effective_spectral_gap under dropped workers;
  - the predict_averaging_benefit hook's qualitative predictions.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (load_engine_state, save_checkpoint,
                              save_engine_state)
from repro.core import PhaseEngine
from repro.core.averaging import AveragingSchedule
from repro.core.compress import Compression
from repro.core.variance_model import predict_averaging_benefit
from repro.data.pipeline import Prefetcher, WorkerSharder
from repro.faults import (FaultEvent, FaultPlan, FaultState,
                          degraded_matrix, masked_mean, masked_event_matrix)
from repro.optim import SGD, Momentum
from repro.topology import Topology

DIM, WORKERS, STEPS = 8, 4, 24


def _loss_fn(params, batch, rng):
    x, y = batch
    r = x @ params["w"] - y
    return jnp.mean(r * r), {}


def _params():
    return {"w": jnp.zeros((DIM,), jnp.float32)}


def _batches(steps=STEPS, m=WORKERS, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.standard_normal(DIM)
    out = []
    for _ in range(steps):
        x = rng.standard_normal((m, 16, DIM)).astype(np.float32)
        y = (x @ w_true + 0.1 * rng.standard_normal((m, 16))).astype(
            np.float32)
        out.append((jnp.asarray(x), jnp.asarray(y)))
    return out


_PLAN = "crash:m=1@t=6,rejoin:m=1@t=14"


# --------------------------------------------------------------------------
# FaultPlan validation / parsing
# --------------------------------------------------------------------------

class TestFaultPlan:
    def test_parse_roundtrip(self):
        plan = FaultPlan.parse(_PLAN, WORKERS, straggle_prob=0.25)
        assert plan.events == (FaultEvent("crash", 1, 6),
                               FaultEvent("rejoin", 1, 14))
        assert plan.straggle_prob == 0.25
        assert not plan.is_trivial
        assert plan.has_rejoin

    def test_parse_auto_rejoin(self):
        plan = FaultPlan.parse("crash:m=2@t=5", WORKERS, rejoin_after=7)
        assert FaultEvent("rejoin", 2, 12) in plan.events
        # a crash with a later scripted event is left alone
        plan = FaultPlan.parse(_PLAN, WORKERS, rejoin_after=7)
        assert sum(e.kind == "rejoin" for e in plan.events) == 1

    @pytest.mark.parametrize("text,match", [
        ("crash:m=9@t=2", "out of range"),
        ("explode:m=1@t=2", "unknown fault kind"),
        ("crash m=1@t=2", "cannot parse"),
        ("rejoin:m=1@t=2", "without a prior crash"),
        ("crash:m=1@t=2,crash:m=1@t=5", "already dead"),
        ("crash:m=0@t=2,crash:m=1@t=2,crash:m=2@t=2,crash:m=3@t=2",
         "all .* dead|no alive"),
    ])
    def test_invalid_plans_refused(self, text, match):
        with pytest.raises(ValueError, match=match):
            FaultPlan.parse(text, WORKERS)

    def test_bad_straggle_prob(self):
        with pytest.raises(ValueError, match="straggle_prob"):
            FaultPlan(WORKERS, (), 1.5)

    def test_trivial_lowering(self):
        assert FaultPlan(WORKERS).is_trivial
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8),
                          faults=FaultPlan(WORKERS))
        assert eng._faults() is None

    def test_shrink(self):
        plan = FaultPlan.shrink(8, 5, step=10)
        assert len(plan.events) == 3
        alive = np.asarray(plan.alive_at(jnp.int32(10)))
        np.testing.assert_array_equal(alive, [1, 1, 1, 1, 1, 0, 0, 0])

    def test_worker_count_mismatch_refused(self):
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8),
                          faults=FaultPlan.parse("crash:m=1@t=2", 8))
        with pytest.raises(ValueError, match="worker count"):
            eng.run(_params(), _batches(4), num_workers=WORKERS, seed=0)

    def test_faults_with_outer_optimizer_refused(self):
        from repro.core import OuterOptimizer
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8),
                          outer=OuterOptimizer(lr=0.8, momentum=0.5),
                          faults=FaultPlan.parse("crash:m=1@t=2", WORKERS))
        with pytest.raises(ValueError, match="outer optimizer"):
            eng.run(_params(), _batches(4), num_workers=WORKERS, seed=0)

    def test_straggle_mask_deterministic(self):
        plan = FaultPlan(WORKERS, (), 0.5)
        key = jax.random.PRNGKey(7)
        rows = jnp.arange(WORKERS, dtype=jnp.int32)
        a = np.asarray(plan.straggle_mask(key, jnp.int32(3), rows))
        b = np.asarray(plan.straggle_mask(key, jnp.int32(3), rows))
        np.testing.assert_array_equal(a, b)
        # different steps decorrelate; per-row slices match the full draw
        c = np.asarray(plan.straggle_mask(key, jnp.int32(4), rows))
        assert not np.array_equal(a, c) or True  # may collide, not req.
        half = np.asarray(plan.straggle_mask(key, jnp.int32(3), rows[2:]))
        np.testing.assert_array_equal(a[2:], half)


# --------------------------------------------------------------------------
# Masked-mixing algebra
# --------------------------------------------------------------------------

class TestMaskedAlgebra:
    def test_masked_event_matrix_doubly_stochastic(self):
        alive = jnp.asarray([1.0, 0.0, 1.0, 1.0])
        A = np.asarray(masked_event_matrix(alive))
        np.testing.assert_allclose(A.sum(0), 1.0, atol=1e-6)
        np.testing.assert_allclose(A.sum(1), 1.0, atol=1e-6)
        # the dead row is identity: it neither sends nor receives
        np.testing.assert_array_equal(A[1], np.eye(4)[1])
        np.testing.assert_array_equal(A[:, 1], np.eye(4)[:, 1])

    def test_degraded_matrix_all_alive_is_identity_op(self):
        W = Topology.ring(4).expected_matrix().astype(np.float32)
        out = np.asarray(degraded_matrix(jnp.asarray(W), jnp.ones(4)))
        np.testing.assert_array_equal(out, W)

    def test_degraded_matrix_masks_and_renormalizes(self):
        W = jnp.asarray(Topology.ring(4).expected_matrix(), jnp.float32)
        alive = jnp.asarray([1.0, 0.0, 1.0, 1.0])
        Wm = np.asarray(degraded_matrix(W, alive))
        np.testing.assert_allclose(Wm.sum(1), 1.0, atol=1e-6)
        np.testing.assert_allclose(Wm.sum(0), 1.0, atol=1e-6)
        assert Wm[0, 1] == 0.0 and Wm[1, 0] == 0.0
        np.testing.assert_array_equal(Wm[1], np.eye(4)[1])

    def test_masked_ref_events_keep_dead_rows(self):
        from repro.kernels.ref import plane_average_ref
        plane = jnp.asarray(np.random.default_rng(0).standard_normal(
            (4, 6)), jnp.float32)
        alive = jnp.asarray([1.0, 0.0, 1.0, 1.0])
        out, disp = plane_average_ref(plane, alive=alive)
        glob = np.asarray(masked_mean(plane, alive))
        np.testing.assert_array_equal(np.asarray(out)[1],
                                      np.asarray(plane)[1])
        for i in (0, 2, 3):
            np.testing.assert_array_equal(np.asarray(out)[i], glob)

    def test_all_ones_mask_matches_plain_mean(self):
        from repro.kernels.ref import plane_average_ref
        plane = jnp.asarray(np.random.default_rng(1).standard_normal(
            (4, 6)), jnp.float32)
        out0, d0 = plane_average_ref(plane)
        out1, d1 = plane_average_ref(plane, alive=jnp.ones(4))
        np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                                   rtol=1e-6)
        np.testing.assert_allclose(float(d0), float(d1), rtol=1e-6)


# --------------------------------------------------------------------------
# Engine equivalences
# --------------------------------------------------------------------------

SCHEDS = {
    "oneshot": AveragingSchedule("oneshot"),
    "minibatch": AveragingSchedule("minibatch"),
    "periodic": AveragingSchedule("periodic", 8),
    "stochastic": AveragingSchedule("stochastic", zeta=0.2),
    "hierarchical": AveragingSchedule("hierarchical", inner_phase_len=4,
                                      outer_phase_len=8, inner_groups=2),
    "adaptive_threshold": AveragingSchedule("adaptive_threshold",
                                            disp_threshold=0.05),
    "adaptive_budget": AveragingSchedule("adaptive_budget", comm_budget=4,
                                         budget_horizon=STEPS),
}


class TestEngineFaults:
    @pytest.mark.parametrize("sname", list(SCHEDS))
    def test_all_alive_plan_bitwise_equals_no_faults(self, sname):
        sched = SCHEDS[sname]
        batches = _batches()
        f0, h0 = PhaseEngine(_loss_fn, SGD(0.05), sched).run(
            _params(), batches, num_workers=WORKERS, seed=0,
            record_every=4)
        f1, h1 = PhaseEngine(_loss_fn, SGD(0.05), sched,
                             faults=FaultPlan(WORKERS)).run(
            _params(), batches, num_workers=WORKERS, seed=0,
            record_every=4)
        np.testing.assert_array_equal(np.asarray(f0["w"]),
                                      np.asarray(f1["w"]))
        assert h0 == h1

    @pytest.mark.parametrize("sname", ["periodic", "stochastic",
                                       "adaptive_threshold"])
    def test_crash_rejoin_bitwise_across_paths(self, sname):
        sched = SCHEDS[sname]
        plan = FaultPlan.parse(_PLAN, WORKERS, straggle_prob=0.1)
        batches = _batches()
        res = {}
        for name, kw in [("flat_native", {}),
                         ("flat", dict(fused_opt=False)),
                         ("tree", dict(flat=False))]:
            eng = PhaseEngine(_loss_fn, Momentum(0.05, 0.9), sched,
                              faults=plan, **kw)
            f, _ = eng.run(_params(), batches, num_workers=WORKERS,
                           seed=0)
            res[name] = np.asarray(f["w"])
        fh, _ = PhaseEngine(_loss_fn, Momentum(0.05, 0.9), sched,
                            faults=plan).run_host(
            _params(), batches, num_workers=WORKERS, seed=0)
        res["host"] = np.asarray(fh["w"])
        for k in ("flat", "tree", "host"):
            np.testing.assert_array_equal(res["flat_native"], res[k],
                                          err_msg=k)

    def test_compressed_crash_rejoin_bitwise_across_paths(self):
        plan = FaultPlan.parse(_PLAN, WORKERS, straggle_prob=0.1)
        comp = Compression("int8")
        batches = _batches()
        res = {}
        for name, kw in [("flat_native", {}),
                         ("flat", dict(fused_opt=False)),
                         ("tree", dict(flat=False))]:
            eng = PhaseEngine(_loss_fn, SGD(0.05),
                              SCHEDS["periodic"], faults=plan,
                              compression=comp, **kw)
            f, _ = eng.run(_params(), batches, num_workers=WORKERS,
                           seed=0)
            res[name] = np.asarray(f["w"])
        fh, _ = PhaseEngine(_loss_fn, SGD(0.05), SCHEDS["periodic"],
                            faults=plan, compression=comp).run_host(
            _params(), batches, num_workers=WORKERS, seed=0)
        res["host"] = np.asarray(fh["w"])
        for k in ("flat", "tree", "host"):
            np.testing.assert_array_equal(res["flat_native"], res[k],
                                          err_msg=k)

    def test_dead_rows_frozen_and_rejoin_warm_starts(self):
        plan = FaultPlan.parse(_PLAN, WORKERS)
        eng = PhaseEngine(_loss_fn, Momentum(0.05, 0.9),
                          AveragingSchedule("oneshot"), faults=plan)
        batches = _batches()
        # run to just before the rejoin: worker 1 froze at its step-5
        # params (crash step 6 masks its update and every event)
        _, _, st13 = eng.run(_params(), batches[:13],
                             num_workers=WORKERS, seed=0,
                             return_state=True)
        _, _, st5 = eng.run(_params(), batches[:5], num_workers=WORKERS,
                            seed=0, return_state=True)
        np.testing.assert_array_equal(
            np.asarray(st13.worker_params["w"][1]),
            np.asarray(st5.worker_params["w"][1]))
        np.testing.assert_array_equal(np.asarray(st13.fault.alive),
                                      [1.0, 0.0, 1.0, 1.0])
        # at the rejoin step the row warm-starts from the alive mean of
        # the pre-step plane and its momentum is zeroed
        _, _, st14 = eng.run(_params(), batches[:14],
                             num_workers=WORKERS, seed=0,
                             return_state=True)
        assert not np.array_equal(np.asarray(st14.worker_params["w"][1]),
                                  np.asarray(st5.worker_params["w"][1]))
        np.testing.assert_array_equal(np.asarray(st14.fault.alive),
                                      np.ones(WORKERS))

    def test_straggler_only_plan_runs_and_differs(self):
        batches = _batches()
        f0, _ = PhaseEngine(_loss_fn, SGD(0.05),
                            SCHEDS["periodic"]).run(
            _params(), batches, num_workers=WORKERS, seed=0)
        plan = FaultPlan(WORKERS, (), 0.5)
        eng = PhaseEngine(_loss_fn, SGD(0.05), SCHEDS["periodic"],
                          faults=plan)
        f1, _ = eng.run(_params(), batches, num_workers=WORKERS, seed=0)
        f2, _ = eng.run(_params(), batches, num_workers=WORKERS, seed=0)
        # deterministic across repeats, different from the no-fault run
        np.testing.assert_array_equal(np.asarray(f1["w"]),
                                      np.asarray(f2["w"]))
        assert not np.array_equal(np.asarray(f0["w"]),
                                  np.asarray(f1["w"]))


# --------------------------------------------------------------------------
# Checkpointing: resume under faults + the v0..v4 ladder + crash safety
# --------------------------------------------------------------------------

class TestFaultCheckpoints:
    def _engine(self, **kw):
        return PhaseEngine(_loss_fn, Momentum(0.05, 0.9),
                           SCHEDS["adaptive_threshold"],
                           faults=FaultPlan.parse(_PLAN, WORKERS,
                                                  straggle_prob=0.2),
                           **kw)

    def test_resume_inside_fault_window_bitwise(self, tmp_path):
        eng = self._engine()
        batches = _batches()
        fU, hU = eng.run(_params(), batches, num_workers=WORKERS, seed=0)
        # interrupt at step 10 — worker 1 is dead, stragglers mid-stream
        _, _, st = eng.run(_params(), batches[:10], num_workers=WORKERS,
                           seed=0, return_state=True)
        path = os.path.join(tmp_path, "ck")
        save_engine_state(path, st)
        meta = json.load(open(path + ".json"))
        assert meta["extra"]["engine_state_version"] == 4
        assert meta["extra"]["has_resid"] is False
        like = eng.init(_params(), WORKERS, seed=0)
        loaded, at = load_engine_state(path, like)
        assert at == 10
        fR, _ = eng.run(_params(), batches[10:], num_workers=WORKERS,
                        seed=0, state=loaded)
        np.testing.assert_array_equal(np.asarray(fU["w"]),
                                      np.asarray(fR["w"]))

    def test_v4_with_residuals_roundtrip(self, tmp_path):
        eng = self._engine(compression=Compression("int8"))
        _, _, st = eng.run(_params(), _batches()[:10],
                           num_workers=WORKERS, seed=0,
                           return_state=True)
        path = os.path.join(tmp_path, "ck")
        save_engine_state(path, st)
        meta = json.load(open(path + ".json"))
        assert meta["extra"]["engine_state_version"] == 4
        assert meta["extra"]["has_resid"] is True
        like = eng.init(_params(), WORKERS, seed=0)
        loaded, _ = load_engine_state(path, like)
        np.testing.assert_array_equal(np.asarray(st.resid),
                                      np.asarray(loaded.resid))
        np.testing.assert_array_equal(np.asarray(st.fault.alive),
                                      np.asarray(loaded.fault.alive))

    def test_v4_into_no_fault_engine_refused(self, tmp_path):
        eng = self._engine()
        _, _, st = eng.run(_params(), _batches()[:8],
                           num_workers=WORKERS, seed=0,
                           return_state=True)
        path = os.path.join(tmp_path, "ck")
        save_engine_state(path, st)
        plain = PhaseEngine(_loss_fn, Momentum(0.05, 0.9),
                            SCHEDS["adaptive_threshold"])
        with pytest.raises(ValueError, match="no fault plan"):
            load_engine_state(path, plain.init(_params(), WORKERS,
                                               seed=0))

    def test_pre_fault_versions_load_all_alive(self, tmp_path):
        # a v2 (no resid, no fault) checkpoint loads into a fault
        # engine with fresh all-alive rows
        plain = PhaseEngine(_loss_fn, Momentum(0.05, 0.9),
                            SCHEDS["adaptive_threshold"])
        _, _, st = plain.run(_params(), _batches()[:8],
                             num_workers=WORKERS, seed=0,
                             return_state=True)
        path = os.path.join(tmp_path, "v2")
        save_engine_state(path, st)
        assert json.load(open(path + ".json"))[
            "extra"]["engine_state_version"] == 2
        eng = self._engine()
        like = eng.init(_params(), WORKERS, seed=0)
        loaded, at = load_engine_state(path, like)
        assert at == 8
        assert isinstance(loaded.fault, FaultState)
        np.testing.assert_array_equal(np.asarray(loaded.fault.alive),
                                      np.ones(WORKERS))
        np.testing.assert_array_equal(
            np.asarray(st.worker_params["w"]),
            np.asarray(loaded.worker_params["w"]))

    def test_atomic_save_leaves_no_temp_files(self, tmp_path):
        path = os.path.join(tmp_path, "ck")
        save_checkpoint(path, {"w": np.zeros(3)}, step=1)
        assert sorted(os.listdir(tmp_path)) == ["ck.json", "ck.npz"]

    def test_torn_metadata_refused(self, tmp_path):
        path = os.path.join(tmp_path, "ck")
        save_checkpoint(path, {"w": np.zeros(3)}, step=1)
        raw = open(path + ".json").read()
        open(path + ".json", "w").write(raw[:len(raw) // 2])
        with pytest.raises(ValueError, match="torn/partial metadata"):
            load_engine_state(path, None)
        from repro.checkpoint import load_checkpoint
        with pytest.raises(ValueError, match="torn/partial metadata"):
            load_checkpoint(path, {"w": np.zeros(3)})

    def test_torn_arrays_refused(self, tmp_path):
        from repro.checkpoint import load_checkpoint
        path = os.path.join(tmp_path, "ck")
        save_checkpoint(path, {"w": np.zeros(3)}, step=1)
        blob = open(path + ".npz", "rb").read()
        open(path + ".npz", "wb").write(blob[:len(blob) // 2])
        with pytest.raises(ValueError, match="torn/partial array"):
            load_checkpoint(path, {"w": np.zeros(3)})

    def test_missing_arrays_refused(self, tmp_path):
        from repro.checkpoint import load_checkpoint
        path = os.path.join(tmp_path, "ck")
        save_checkpoint(path, {"w": np.zeros(3)}, step=1)
        os.remove(path + ".npz")
        with pytest.raises(ValueError, match="no array file"):
            load_checkpoint(path, {"w": np.zeros(3)})


# --------------------------------------------------------------------------
# The mesh phase with dead rows (subprocess, 8 host devices)
# --------------------------------------------------------------------------

_SHARD_SCRIPT = r"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import AveragingSchedule, PhaseEngine, Compression, FaultPlan

assert len(jax.devices()) == 8, jax.devices()
DIM, WORKERS, STEPS = 8, 8, 24
rng = np.random.default_rng(0)
w_true = rng.standard_normal(DIM)
batches = []
for _ in range(STEPS):
    x = rng.standard_normal((WORKERS, 16, DIM)).astype(np.float32)
    y = (x @ w_true).astype(np.float32)
    batches.append((jnp.asarray(x), jnp.asarray(y)))

def loss_fn(params, batch, rng):
    x, y = batch
    r = x @ params["w"] - y
    return jnp.mean(r * r), {}

params = {"w": jnp.zeros((DIM,), jnp.float32)}
from repro.optim import SGD
opt = lambda: SGD(0.05)
mesh = jax.make_mesh((8,), ("data",))
kw = dict(num_workers=WORKERS, seed=3, record_every=1)
plan = FaultPlan.parse("crash:m=1@t=6,rejoin:m=1@t=14,crash:m=5@t=10",
                       WORKERS, straggle_prob=0.1)
for sched in (AveragingSchedule("periodic", 4),
              AveragingSchedule("adaptive_threshold",
                                disp_threshold=0.05)):
    for comp in (None, Compression("int8")):
        mk = lambda **e: PhaseEngine(loss_fn, opt(), sched, faults=plan,
                                     compression=comp, **e)
        # the one-device leaf carry against the mesh's (psum'd worker
        # means): the same decision stream, f32-roundoff params
        f0, h0 = mk(flat=False).run(params, batches, **kw)
        f2, h2 = mk(mesh=mesh).run(params, batches, **kw)
        assert h0["averages"] == h2["averages"]
        assert [t for t, _ in h0["dispersion"]] == \
            [t for t, _ in h2["dispersion"]]
        np.testing.assert_allclose([v for _, v in h0["loss"]],
                                   [v for _, v in h2["loss"]],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(f0["w"]),
                                   np.asarray(f2["w"]),
                                   rtol=1e-5, atol=1e-6)
        print("ok", sched.kind, comp.wire if comp else "f32")
print("ALL-OK")
"""


def test_sharded_collectives_with_dead_rows():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALL-OK" in out.stdout


# --------------------------------------------------------------------------
# Non-IID Dirichlet shards
# --------------------------------------------------------------------------

class TestDirichletSharder:
    def _labels(self, n=400, classes=4, seed=0):
        return np.random.default_rng(seed).integers(0, classes, n)

    def test_requires_labels(self):
        with pytest.raises(ValueError, match="labels"):
            WorkerSharder(100, 4, mode="dirichlet")

    def test_rejects_bad_alpha_and_label_shape(self):
        labels = self._labels()
        with pytest.raises(ValueError, match="alpha"):
            WorkerSharder(400, 4, mode="dirichlet", labels=labels,
                          alpha=0.0)
        with pytest.raises(ValueError, match="cover"):
            WorkerSharder(300, 4, mode="dirichlet", labels=labels)

    def test_deterministic_and_in_pool(self):
        labels = self._labels()
        a = WorkerSharder(400, 4, seed=5, mode="dirichlet", labels=labels)
        b = WorkerSharder(400, 4, seed=5, mode="dirichlet", labels=labels)
        ia, ib = a.next_indices(32), b.next_indices(32)
        np.testing.assert_array_equal(ia, ib)
        for i in range(4):
            assert set(ia[i]) <= set(a._pools[i].tolist())

    def test_small_alpha_skews_labels(self):
        labels = self._labels()
        skew = WorkerSharder(400, 4, seed=1, mode="dirichlet",
                             labels=labels, alpha=0.05)
        near = WorkerSharder(400, 4, seed=1, mode="dirichlet",
                             labels=labels, alpha=100.0)
        def max_frac(sh):
            return sh.class_fractions(labels).max(axis=1).mean()
        # α→0 concentrates each worker on few classes; α→∞ matches the
        # global (uniform) class mix
        assert max_frac(skew) > max_frac(near) + 0.2
        assert all(len(p) > 0 for p in skew._pools)

    def test_block_equals_successive_draws(self):
        labels = self._labels()
        a = WorkerSharder(400, 4, seed=2, mode="dirichlet", labels=labels)
        b = WorkerSharder(400, 4, seed=2, mode="dirichlet", labels=labels)
        blk = a.next_index_block(3, 8)
        seq = np.stack([b.next_indices(8) for _ in range(3)])
        np.testing.assert_array_equal(blk, seq)


# --------------------------------------------------------------------------
# Prefetcher failure handling
# --------------------------------------------------------------------------

class TestPrefetcherFailure:
    def test_error_then_stop_iteration_no_deadlock(self):
        def bad():
            yield 1
            raise RuntimeError("source died")

        pf = Prefetcher(bad())
        assert next(pf) == 1
        with pytest.raises(RuntimeError, match="source died"):
            next(pf)
        # a consumer that catches the error and retries must get a
        # clean end-of-stream, not block forever on the empty queue
        with pytest.raises(StopIteration):
            next(pf)
        pf._thread.join(timeout=5.0)
        assert not pf._thread.is_alive()

    def test_engine_surfaces_producer_error(self):
        def bad_stream():
            yield from _batches(4)
            raise RuntimeError("loader exploded")

        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 2))
        with pytest.raises(RuntimeError, match="loader exploded"):
            eng.run(_params(), bad_stream(), num_workers=WORKERS,
                    seed=0, phase_len=2)


# --------------------------------------------------------------------------
# Degraded-topology spectrum + the variance-model hook
# --------------------------------------------------------------------------

class TestDegradedAnalysis:
    def test_effective_gap_all_alive_matches(self):
        topo = Topology.ring(6)
        assert (topo.effective_spectral_gap(np.ones(6))
                == pytest.approx(topo.spectral_gap, abs=1e-8))

    def test_effective_gap_shrinks_with_deaths(self):
        topo = Topology.ring(8)
        alive = np.ones(8)
        alive[3] = 0
        # cutting a ring node leaves a path graph: mixing slows
        assert topo.effective_spectral_gap(alive) < topo.spectral_gap

    def test_effective_gap_disconnected_is_zero(self):
        topo = Topology.blocks(8, 2)
        assert topo.spectral_gap == pytest.approx(0.0, abs=1e-9)
        assert topo.effective_spectral_gap(np.ones(8)) == pytest.approx(
            0.0, abs=1e-9)

    def test_effective_gap_single_survivor(self):
        topo = Topology.ring(4)
        assert topo.effective_spectral_gap([1, 0, 0, 0]) == 1.0

    def test_effective_gap_validates(self):
        topo = Topology.ring(4)
        with pytest.raises(ValueError, match="alive"):
            topo.effective_spectral_gap(np.ones(5))
        with pytest.raises(ValueError, match="alive"):
            topo.effective_spectral_gap(np.zeros(4))

    def test_predict_benefit_qualitative(self):
        iid = predict_averaging_benefit([1.0, 1.0, 1.0, 1.0])
        skew = predict_averaging_benefit([4.0, 3.0, 2.0, 3.0])
        # non-IID shards measure higher σ² -> larger absolute benefit
        assert skew["benefit"] > iid["benefit"]
        assert iid["variance_reduction"] == 0.25
        # dead workers shrink n: weaker reduction (larger 1/n)
        degraded = predict_averaging_benefit([1.0, 1.0, 1.0, 1.0],
                                             alive=[1, 0, 1, 0])
        assert degraded["n_alive"] == 2
        assert (degraded["variance_reduction"]
                > iid["variance_reduction"])
        assert degraded["benefit"] < iid["benefit"]
        with pytest.raises(ValueError):
            predict_averaging_benefit([1.0], alive=[0.0])
