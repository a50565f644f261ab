"""Elastic membership: live plane resize, rejoin curricula,
straggle-aware scheduling.

Covers the repro.elastic subsystem end to end:

  - ElasticPlan validation / parsing (eager, actionable errors);
  - row repacking is a permutation-exact pack/unpack (property test,
    hypothesis-optional with an always-on numpy fallback);
  - a no-op resize plan (M' = M, no curriculum) lowers to the PR 7
    fault engine bit-exactly across all 7 schedules;
  - a shrink + grow mid-run is bitwise identical across the scan
    triple (flat-native / flat / tree carries);
  - resume-across-resize (through a v5 checkpoint) == uninterrupted;
  - a shrink-then-grow round trip restores a bit-identical layout;
  - grow curricula: grown rows train solo, out of the consensus, until
    their window closes;
  - straggle-aware adaptive scheduling discounts straggler-widened
    dispersion (fires <= unaware; bit-exact no-op without stragglers;
    refused for non-adaptive kinds);
  - checkpoint v0-v5 ladder round-trip for the resized case, plane-M
    mismatch refused with both Ms named and the resize API pointed at;
  - the calibrated post-resize dispersion prediction
    (variance_model.predict_post_resize_dispersion) against a
    simulated K-step window;
  - sharded resize under shard_map, the mesh's leaf carry with psum'd
    worker means (subprocess with 8 host devices, like test_faults).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_engine_state, save_engine_state
from repro.checkpoint.io import ENGINE_STATE_VERSION
from repro.core import PhaseEngine
from repro.core.averaging import AveragingSchedule
from repro.core.compress import Compression
from repro.core.variance_model import (predict_averaging_benefit,
                                       predict_post_resize_dispersion)
from repro.elastic import (ElasticPlan, ResizeEvent, grow_state,
                           resize_engine, run_elastic, segment_engine,
                           shrink_state)
from repro.faults import FaultPlan, FaultState
from repro.optim import SGD, Momentum
from repro.topology import Topology

DIM, WORKERS, STEPS = 8, 4, 24

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _loss_fn(params, batch, rng):
    x, y = batch
    r = x @ params["w"] - y
    return jnp.mean(r * r), {}


def _params():
    return {"w": jnp.zeros((DIM,), jnp.float32)}


def _block(steps=STEPS, m=WORKERS, seed=0):
    """One fixed (steps, m, batch, ...) data block; every engine and
    every segment slices the same arrays, so comparisons are over
    identical batches."""
    rng = np.random.default_rng(seed)
    w_true = rng.standard_normal(DIM)
    x = rng.standard_normal((steps, m, 16, DIM)).astype(np.float32)
    y = (x @ w_true + 0.1 * rng.standard_normal(
        (steps, m, 16))).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def _factory(block):
    x, y = block

    def data(m, t0, k):
        return [(x[t, :m], y[t, :m]) for t in range(t0 - 1, t0 - 1 + k)]
    return data


def _batches(block, m=WORKERS):
    return _factory(block)(m, 1, block[0].shape[0])


_PLAN = "crash:m=1@t=6,rejoin:m=1@t=14"

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


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# ElasticPlan validation / parsing
# --------------------------------------------------------------------------

class TestElasticPlan:
    def test_parse_roundtrip(self):
        plan = ElasticPlan.parse(4, shrink_at=["8:3"], grow_at=["16:4"],
                                 curriculum=2)
        assert plan.resizes == (ResizeEvent(8, 3), ResizeEvent(16, 4))
        assert plan.curriculum == 2
        assert not plan.is_trivial
        assert plan.sizes() == (4, 3, 4)

    def test_noop_plan_is_trivial(self):
        plan = ElasticPlan(4, ((10, 4),))
        assert plan.is_trivial
        assert plan.sizes() == (4,)

    @pytest.mark.parametrize("kw,match", [
        (dict(shrink_at=["8:6"]), "would grow"),
        (dict(grow_at=["8:2"]), "would shrink"),
        (dict(shrink_at=["bogus"]), "cannot parse"),
        (dict(shrink_at=["8:3"], grow_at=["8:4"]), "strictly increasing"),
        (dict(shrink_at=["1:3"]), "strictly increasing|>= 2"),
        (dict(shrink_at=["8:0"]), "must be >= 1"),
        (dict(shrink_at=["8:3"], curriculum=-1), "curriculum"),
    ])
    def test_invalid_plans_refused(self, kw, match):
        with pytest.raises(ValueError, match=match):
            ElasticPlan.parse(4, **kw)

    def test_segments(self):
        plan = ElasticPlan(4, ((8, 3), (16, 4)))
        segs = plan.segments(24)
        assert [(s.start, s.stop, s.num_workers) for s in segs] == \
            [(1, 8, 4), (8, 16, 3), (16, 25, 4)]
        # resizes beyond the horizon are ignored
        assert len(plan.segments(7)) == 1

    def test_solo_windows(self):
        plan = ElasticPlan(4, ((8, 3), (16, 4)), curriculum=3)
        assert plan.solo_windows() == ((3, 16, 19),)
        assert ElasticPlan(4, ((8, 3), (16, 4))).solo_windows() == ()

    def test_segment_faults_compose_with_base(self):
        base = FaultPlan.parse(_PLAN, 4, straggle_prob=0.1)
        plan = ElasticPlan(4, ((8, 3), (16, 4)), curriculum=2)
        fp3 = plan.segment_faults(base, 3, 8, 16)
        assert fp3.num_workers == 3
        assert all(ev.worker < 3 for ev in fp3.events)
        assert fp3.straggle_prob == 0.1
        fp4 = plan.segment_faults(base, 4, 16, 25)
        assert (3, 16, 18) in fp4.solo
        # a window from another segment's grow is not dragged along
        fp_pre = plan.segment_faults(base, 4, 1, 8)
        assert fp_pre.solo == ()

    def test_segment_faults_trivial_lowering(self):
        plan = ElasticPlan(4, ((8, 3),))
        assert plan.segment_faults(None, 3, 8, 25) is None

    def test_base_plan_m_mismatch_refused(self):
        plan = ElasticPlan(4, ((8, 3),))
        with pytest.raises(ValueError, match="elastic plan starts at"):
            plan.segment_faults(FaultPlan(8), 3)


# --------------------------------------------------------------------------
# Row repacking: permutation-exact pack/unpack
# --------------------------------------------------------------------------

def _rand_state(rng, m):
    """A fake EngineState-shaped carrier with random bit patterns."""
    eng = PhaseEngine(_loss_fn, Momentum(0.05, 0.9),
                      AveragingSchedule("periodic", 8),
                      compression=Compression("int8"),
                      faults=FaultPlan.parse(_PLAN, m))
    state = eng.init(_params(), m, 0)
    noise = lambda x: jnp.asarray(
        rng.standard_normal(x.shape).astype(np.asarray(x).dtype))
    return state._replace(
        worker_params=jax.tree.map(noise, state.worker_params),
        opt_state=jax.tree.map(noise, state.opt_state),
        resid=noise(state.resid))


def _check_repack(state, new_m, old_m):
    small = shrink_state(state, new_m)
    for a, b in zip(jax.tree.leaves(small.worker_params)
                    + jax.tree.leaves(small.opt_state)
                    + [small.resid],
                    jax.tree.leaves(state.worker_params)
                    + jax.tree.leaves(state.opt_state)
                    + [state.resid]):
        assert np.asarray(a).shape[0] == new_m
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b)[:new_m])
    big = grow_state(small, old_m, optimizer=Momentum(0.05, 0.9))
    for a, b in zip(jax.tree.leaves(big.worker_params),
                    jax.tree.leaves(small.worker_params)):
        a = np.asarray(a)
        assert a.shape[0] == old_m
        np.testing.assert_array_equal(a[:new_m], np.asarray(b))
        # every appended row is the same consensus vector
        for r in range(new_m, old_m):
            np.testing.assert_array_equal(a[r], a[new_m] if new_m < old_m
                                          else a[r])
    for s in jax.tree.leaves(big.opt_state) + [big.resid]:
        assert not np.asarray(s)[new_m:].any()  # zeroed for new rows


class TestRepack:
    def test_repack_numpy_cases(self):
        rng = np.random.default_rng(0)
        for new_m in (1, 2, 3, 4):
            _check_repack(_rand_state(rng, 4), new_m, 4)

    @pytest.mark.skipif(not HAVE_HYPOTHESIS,
                        reason="hypothesis not installed")
    def test_repack_property(self):
        @settings(max_examples=20, deadline=None)
        @given(st.integers(2, 6), st.data())
        def prop(old_m, data):
            new_m = data.draw(st.integers(1, old_m))
            rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
            _check_repack(_rand_state(rng, old_m), new_m, old_m)
        prop()

    def test_shrink_refuses_all_dead(self):
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8),
                          faults=FaultPlan.parse(_PLAN, 4))
        state = eng.init(_params(), 4, 0)
        dead = state._replace(fault=FaultState(
            jnp.asarray([0.0, 0.0, 1.0, 1.0]),
            state.fault.staleness))
        with pytest.raises(ValueError, match="no alive worker"):
            shrink_state(dead, 2)

    def test_shrink_grow_bounds(self):
        state = PhaseEngine(_loss_fn, SGD(0.05),
                            AveragingSchedule("periodic", 8)).init(
                                _params(), 4, 0)
        with pytest.raises(ValueError, match="cannot shrink"):
            shrink_state(state, 5)
        with pytest.raises(ValueError, match="cannot grow"):
            grow_state(state, 3, optimizer=SGD(0.05))


# --------------------------------------------------------------------------
# Engine integration
# --------------------------------------------------------------------------

class TestElasticEngine:
    @pytest.mark.parametrize("sname", list(SCHEDS))
    def test_noop_resize_bitwise_equals_fault_engine(self, sname):
        """A no-op resize (M' = M, no curriculum) lowers to the PR 7
        fault engine bit-exactly: segment boundaries are phase cuts."""
        block = _block()
        plan = FaultPlan.parse(_PLAN, WORKERS, straggle_prob=0.1)
        eng = PhaseEngine(_loss_fn, SGD(0.05), SCHEDS[sname],
                          faults=plan)
        f0, h0 = eng.run(_params(), _batches(block), num_workers=WORKERS,
                         seed=0, record_every=1)
        f1, h1 = run_elastic(eng, _params(), _factory(block),
                             ElasticPlan(WORKERS, ((10, WORKERS),)),
                             steps=STEPS, seed=0, record_every=1)
        _leaves_equal(f0, f1)
        assert h1["resizes"] == []
        assert h0["loss"] == h1["loss"]
        assert h0["dispersion"] == h1["dispersion"]
        assert h0["averages"] == h1["averages"]

    def test_resize_bitwise_across_scan_triple(self):
        """shrink 4->3 @8 then grow ->4 @16 (curriculum 2, straggle,
        base faults) is bitwise identical across the flat-native, flat
        and tree carries."""
        block = _block()
        base = FaultPlan.parse(_PLAN, WORKERS, straggle_prob=0.1)
        plan = ElasticPlan(WORKERS, ((8, 3), (16, 4)), curriculum=2)
        outs = []
        for kw in ({}, dict(fused_opt=False), dict(flat=False)):
            eng = PhaseEngine(_loss_fn, Momentum(0.05, 0.9),
                              AveragingSchedule("periodic", 8),
                              faults=base, **kw)
            outs.append(run_elastic(eng, _params(), _factory(block),
                                    plan, steps=STEPS, seed=0,
                                    record_every=1, return_state=True))
        for f, h, st_ in outs[1:]:
            _leaves_equal(outs[0][0], f)
            _leaves_equal(outs[0][2].worker_params, st_.worker_params)
            assert h["loss"] == outs[0][1]["loss"]
            assert h["resizes"] == [(8, 4, 3), (16, 3, 4)]

    def test_hierarchical_resize(self):
        """Hierarchical inner groups keep dividing every segment M."""
        block = _block()
        plan = ElasticPlan(WORKERS, ((8, 2), (16, 4)), curriculum=2)
        eng = PhaseEngine(_loss_fn, SGD(0.05), SCHEDS["hierarchical"])
        f, h = run_elastic(eng, _params(), _factory(block), plan,
                           steps=STEPS, seed=0, record_every=4)
        assert h["resizes"] == [(8, 4, 2), (16, 2, 4)]
        assert np.isfinite(h["loss"][-1][1])
        bad = ElasticPlan(WORKERS, ((8, 3),))
        with pytest.raises(ValueError, match="inner_groups"):
            run_elastic(eng, _params(), _factory(block), bad,
                        steps=STEPS)

    def test_resume_across_resize_bitwise(self, tmp_path):
        """Checkpoint mid-segment (after a resize), resume through a
        v5 save: bitwise == uninterrupted, including the grow-back."""
        block = _block()
        base = FaultPlan.parse(_PLAN, WORKERS, straggle_prob=0.1)
        plan = ElasticPlan(WORKERS, ((8, 3), (16, 4)), curriculum=2)
        eng = PhaseEngine(_loss_fn, Momentum(0.05, 0.9),
                          AveragingSchedule("periodic", 8), faults=base)
        fac = _factory(block)
        f_full, h_full, st_full = run_elastic(
            eng, _params(), fac, plan, steps=STEPS, seed=0,
            record_every=1, return_state=True)
        for cut in (8, 12, 16):  # boundary, mid-segment, boundary
            _, _, st_mid = run_elastic(eng, _params(), fac, plan,
                                       steps=cut, seed=0,
                                       return_state=True)
            path = str(tmp_path / f"ck{cut}")
            save_engine_state(path, st_mid, elastic=True)
            seg_eng, m = segment_engine(eng, plan, cut, STEPS)
            loaded, at = load_engine_state(
                path, seg_eng.init(_params(), m, 0))
            assert at == cut
            f_res, _, st_res = run_elastic(
                eng, _params(), fac, plan, steps=STEPS, seed=0,
                record_every=1, state=loaded, return_state=True)
            _leaves_equal(f_full, f_res)
            _leaves_equal(st_full.worker_params, st_res.worker_params)
            _leaves_equal(st_full.opt_state, st_res.opt_state)

    def test_shrink_grow_round_trip_restores_layout(self):
        """A shrink-then-grow round trip restores a bit-identical
        layout: same treedef, shapes, dtypes as the never-resized
        state, kept rows bitwise preserved through the trip."""
        rng = np.random.default_rng(1)
        state = _rand_state(rng, WORKERS)
        trip = grow_state(shrink_state(state, 3), WORKERS,
                          optimizer=Momentum(0.05, 0.9))
        assert (jax.tree.structure(trip._asdict())
                == jax.tree.structure(state._asdict()))
        for a, b in zip(jax.tree.leaves(trip), jax.tree.leaves(state)):
            assert np.asarray(a).shape == np.asarray(b).shape
            assert np.asarray(a).dtype == np.asarray(b).dtype
        for a, b in zip(jax.tree.leaves(trip.worker_params),
                        jax.tree.leaves(state.worker_params)):
            np.testing.assert_array_equal(np.asarray(a)[:3],
                                          np.asarray(b)[:3])

    def test_grow_curriculum_masks_consensus(self):
        """During its curriculum window a grown row trains (its iterate
        moves) but stays out of the consensus."""
        block = _block()
        plan = ElasticPlan(WORKERS, ((8, 3), (16, 4)), curriculum=6)
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 4))
        # stop inside the window: steps 16..18 done, window is [16, 22)
        f, h, st_ = run_elastic(eng, _params(), _factory(block), plan,
                                steps=18, seed=0, return_state=True)
        wp = np.asarray(st_.worker_params["w"])
        grown_at_16 = np.asarray(  # row 3's warm-start == consensus @15
            grow_state(run_elastic(eng, _params(), _factory(block),
                                   plan, steps=15, seed=0,
                                   return_state=True)[2],
                       WORKERS, optimizer=SGD(0.05)).worker_params["w"])[3]
        assert not np.array_equal(wp[3], grown_at_16)  # it trained
        np.testing.assert_array_equal(np.asarray(f["w"]),
                                      wp[:3].mean(axis=0))  # excluded

    def test_straggle_aware_discounts_dispersion(self):
        block = _block()
        base = FaultPlan(WORKERS, (), 0.4)
        naive = AveragingSchedule("adaptive_threshold",
                                  disp_threshold=0.05)
        aware = AveragingSchedule("adaptive_threshold",
                                  disp_threshold=0.05,
                                  straggle_aware=True)
        runs = {}
        for name, sched in (("naive", naive), ("aware", aware)):
            eng = PhaseEngine(_loss_fn, SGD(0.05), sched, faults=base)
            runs[name] = eng.run(_params(), _batches(block),
                                 num_workers=WORKERS, seed=0,
                                 record_every=1)
        assert runs["aware"][1]["averages"] <= \
            runs["naive"][1]["averages"]
        # the recorded dispersion trace is the TRUE diagnostic, not the
        # discounted one — identical wherever both runs took the same
        # averaging decisions
        t_aware = dict(runs["aware"][1]["disp_trace"])
        t_naive = dict(runs["naive"][1]["disp_trace"])
        assert t_aware[1] == t_naive[1]

    def test_straggle_aware_without_stragglers_is_noop(self):
        """No straggle probability -> disp_scale is exactly 1, and the
        aware run is bit-identical to the unaware one."""
        block = _block()
        base = FaultPlan.parse(_PLAN, WORKERS)  # events, no straggle
        outs = []
        for flag in (False, True):
            sched = AveragingSchedule("adaptive_threshold",
                                      disp_threshold=0.05,
                                      straggle_aware=flag)
            eng = PhaseEngine(_loss_fn, SGD(0.05), sched, faults=base)
            outs.append(eng.run(_params(), _batches(block),
                                num_workers=WORKERS, seed=0,
                                record_every=1))
        _leaves_equal(outs[0][0], outs[1][0])
        assert outs[0][1]["loss"] == outs[1][1]["loss"]

    def test_straggle_aware_refused_for_static_kinds(self):
        with pytest.raises(ValueError, match="straggle_aware"):
            AveragingSchedule("periodic", 8, straggle_aware=True)

    def test_elastic_with_outer_refused(self):
        from repro.core import OuterOptimizer
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8),
                          outer=OuterOptimizer(lr=1.0, momentum=0.5))
        with pytest.raises(ValueError, match="outer"):
            run_elastic(eng, _params(), _factory(_block()),
                        ElasticPlan(WORKERS, ((8, 3),)), steps=STEPS)

    def test_fault_plan_m_mismatch_refused(self):
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8),
                          faults=FaultPlan(8))
        with pytest.raises(ValueError, match="elastic plan starts at"):
            run_elastic(eng, _params(), _factory(_block()),
                        ElasticPlan(WORKERS, ((8, 3),)), steps=STEPS)

    def test_completed_state_refused(self):
        block = _block()
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8))
        plan = ElasticPlan(WORKERS, ((8, 3),))
        _, _, st_ = run_elastic(eng, _params(), _factory(block), plan,
                                steps=STEPS, seed=0, return_state=True)
        with pytest.raises(ValueError, match="already completed"):
            run_elastic(eng, _params(), _factory(block), plan,
                        steps=STEPS, state=st_)

    def test_resize_engine_rebuilds_topology(self):
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8),
                          topology=Topology.full(WORKERS))
        small = resize_engine(eng, 3)
        assert small.topology.num_workers == 3
        assert small.topology.kind == "full"
        with pytest.raises(ValueError, match="ring"):
            resize_engine(PhaseEngine(
                _loss_fn, SGD(0.05), AveragingSchedule("periodic", 8),
                topology=Topology.ring(WORKERS)), 2)


# --------------------------------------------------------------------------
# Checkpoints: v5 + the M-mismatch refusal + the resized ladder
# --------------------------------------------------------------------------

class TestElasticCheckpoint:
    def _resized_state(self):
        block = _block()
        base = FaultPlan.parse(_PLAN, WORKERS, straggle_prob=0.1)
        plan = ElasticPlan(WORKERS, ((8, 3),), curriculum=2)
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8), faults=base,
                          compression=Compression("int8"))
        _, _, st_ = run_elastic(eng, _params(), _factory(block), plan,
                                steps=12, seed=0, return_state=True)
        seg_eng, m = segment_engine(eng, plan, 12, STEPS)
        assert m == 3
        return st_, seg_eng, m

    def test_elastic_save_is_v5(self, tmp_path):
        import json
        st_, seg_eng, m = self._resized_state()
        path = str(tmp_path / "ck")
        save_engine_state(path, st_, elastic=True)
        meta = json.load(open(path + ".json"))["extra"]
        assert meta["engine_state_version"] == ENGINE_STATE_VERSION == 5
        assert meta["num_workers"] == 3
        assert meta["has_fault"] and meta["has_resid"]
        loaded, at = load_engine_state(path,
                                       seg_eng.init(_params(), m, 0))
        assert at == 12
        _leaves_equal(loaded.worker_params, st_.worker_params)

    def test_fixed_membership_saves_keep_v4(self, tmp_path):
        """Non-elastic fault saves still write the lowest version that
        describes their layout (v4) — loadable by older builds."""
        import json
        block = _block()
        eng = PhaseEngine(_loss_fn, SGD(0.05),
                          AveragingSchedule("periodic", 8),
                          faults=FaultPlan.parse(_PLAN, WORKERS))
        _, _, st_ = eng.run(_params(), _batches(block),
                            num_workers=WORKERS, seed=0,
                            return_state=True)
        path = str(tmp_path / "ck")
        save_engine_state(path, st_)
        assert json.load(open(path + ".json"))["extra"][
            "engine_state_version"] == 4

    def test_m_mismatch_refused_with_both_ms(self, tmp_path):
        st_, seg_eng, m = self._resized_state()
        path = str(tmp_path / "ck")
        save_engine_state(path, st_, elastic=True)
        full_eng = PhaseEngine(_loss_fn, SGD(0.05),
                               AveragingSchedule("periodic", 8))
        with pytest.raises(ValueError) as e:
            load_engine_state(path, full_eng.init(_params(), WORKERS, 0))
        msg = str(e.value)
        assert "3-row" in msg and "4 rows" in msg
        assert "repro.elastic" in msg

    def test_m_mismatch_refused_for_pre_v5_saves(self, tmp_path):
        """Older checkpoints carry no num_workers metadata — the shape
        table still names both Ms instead of an opaque assert."""
        import json
        st_, _, _ = self._resized_state()
        path = str(tmp_path / "ck")
        save_engine_state(path, st_)  # v4: no num_workers guarantee
        meta = json.load(open(path + ".json"))
        meta["extra"].pop("num_workers", None)
        json.dump(meta, open(path + ".json", "w"))
        full_eng = PhaseEngine(
            _loss_fn, SGD(0.05), AveragingSchedule("periodic", 8),
            faults=FaultPlan.parse(_PLAN, WORKERS),
            compression=Compression("int8"))
        with pytest.raises(ValueError, match="repro.elastic"):
            load_engine_state(path, full_eng.init(_params(), WORKERS, 0))

    def test_version_ladder_round_trip_resized(self, tmp_path):
        """v0-v5 ladder for the RESIZED (M=3) case: every stripped
        layout loads back into the resized like-state, missing fields
        starting fresh."""
        import json
        st_, seg_eng, m = self._resized_state()
        like = seg_eng.init(_params(), m, 0)
        cases = {
            0: st_._replace(sched=(), resid=(), fault=()),
            2: st_._replace(resid=(), fault=()),
            3: st_._replace(fault=()),
            4: st_,
        }
        for want_version, stripped in cases.items():
            path = str(tmp_path / f"v{want_version}")
            save_engine_state(path, stripped)
            meta = json.load(open(path + ".json"))["extra"]
            assert meta["engine_state_version"] == want_version
            loaded, at = load_engine_state(path, like)
            assert at == 12
            _leaves_equal(loaded.worker_params, st_.worker_params)
        path = str(tmp_path / "v5")
        save_engine_state(path, st_, elastic=True)
        loaded, at = load_engine_state(path, like)
        _leaves_equal(loaded.opt_state, st_.opt_state)
        _leaves_equal(loaded.fault, st_.fault)


class TestTrainCliElastic:
    """train.py elastic/straggle flags fail at parse time (argparse
    error, exit code 2) instead of deep inside a trace."""

    def _error(self, argv):
        from repro.launch.train import main
        with pytest.raises(SystemExit) as e:
            main(argv)
        assert e.value.code == 2

    def test_bad_resize_terms(self):
        self._error(["--shrink-at", "bogus"])
        self._error(["--workers", "4", "--shrink-at", "8:6"])
        self._error(["--workers", "4", "--grow-at", "8:2"])
        self._error(["--workers", "4", "--shrink-at", "8:3",
                     "--grow-at", "8:4"])

    def test_elastic_outer_conflict(self):
        self._error(["--workers", "4", "--shrink-at", "8:3",
                     "--outer-momentum", "0.5"])

    def test_resize_target_vs_schedule_and_topology(self):
        self._error(["--workers", "4", "--shrink-at", "8:3",
                     "--avg", "hierarchical", "--phase-len", "4",
                     "--outer-phase-len", "8", "--inner-groups", "2"])
        self._error(["--workers", "4", "--shrink-at", "8:2",
                     "--topology", "ring"])

    def test_orphan_rejoin_curriculum(self):
        self._error(["--rejoin-curriculum", "-1"])
        self._error(["--workers", "4", "--rejoin-curriculum", "3"])

    def test_straggle_aware_needs_adaptive_and_stragglers(self):
        self._error(["--straggle-aware", "--avg", "periodic",
                     "--straggle-prob", "0.1"])
        self._error(["--straggle-aware", "--avg", "adaptive_threshold",
                     "--disp-threshold", "0.05"])


# --------------------------------------------------------------------------
# Calibrated post-resize dispersion prediction
# --------------------------------------------------------------------------

class TestPostResizePrediction:
    def test_sgd_noise_window(self):
        """Pure-noise SGD from a shared start: measured K-step
        dispersion within 2x of the K-weighted prediction."""
        rng = np.random.default_rng(0)
        n, dim, k, lr, sigma = 8, 512, 8, 0.1, 0.7
        w = np.zeros((n, dim))
        for _ in range(k):
            w -= lr * sigma * rng.standard_normal((n, dim))
        disp = float((np.linalg.norm(w - w.mean(0), axis=1) ** 2).mean())
        pred = predict_post_resize_dispersion(
            [sigma * sigma * dim] * n, lr=lr, steps=k)
        assert pred["k"] == k
        assert pred["drift_dispersion"] == 0.0
        assert 0.5 < disp / pred["predicted_dispersion"] < 2.0

    def test_drift_term_quadratic_in_k(self):
        p4 = predict_post_resize_dispersion([0.0] * 4, lr=0.1, steps=4,
                                            drift2=1.0)
        p8 = predict_post_resize_dispersion([0.0] * 4, lr=0.1, steps=8,
                                            drift2=1.0)
        assert p8["drift_dispersion"] == pytest.approx(
            4.0 * p4["drift_dispersion"])
        # noise term is linear in K instead
        n4 = predict_post_resize_dispersion([1.0] * 4, lr=0.1, steps=4)
        n8 = predict_post_resize_dispersion([1.0] * 4, lr=0.1, steps=8)
        assert n8["noise_dispersion"] == pytest.approx(
            2.0 * n4["noise_dispersion"])

    def test_curvature_discounts_drift(self):
        """A positive curvature contracts the coherent drift (each
        local step descends the shard objective); curvature 0 keeps
        the raw quadratic budget, and the noise term never changes."""
        raw = predict_post_resize_dispersion([1.0] * 4, lr=0.1, steps=8,
                                             drift2=1.0)
        disc = predict_post_resize_dispersion([1.0] * 4, lr=0.1, steps=8,
                                              drift2=1.0, curvature=2.0)
        assert disc["drift_dispersion"] < raw["drift_dispersion"]
        assert disc["noise_dispersion"] == raw["noise_dispersion"]
        with pytest.raises(ValueError, match="curvature"):
            predict_post_resize_dispersion([1.0], lr=0.1, steps=4,
                                           curvature=11.0)

    def test_momentum_weights_exceed_sgd(self):
        sgd = predict_post_resize_dispersion([1.0] * 4, lr=0.1, steps=8)
        mom = predict_post_resize_dispersion([1.0] * 4, lr=0.1, steps=8,
                                             momentum=0.9)
        assert mom["predicted_dispersion"] > sgd["predicted_dispersion"]

    def test_merged_into_predict_averaging_benefit(self):
        out = predict_averaging_benefit([1.0] * 4, lr=0.1, steps=8,
                                        drift2=0.5)
        assert "predicted_dispersion" in out and "benefit" in out

    def test_validation(self):
        with pytest.raises(ValueError, match="steps"):
            predict_post_resize_dispersion([1.0], lr=0.1, steps=0)
        with pytest.raises(ValueError, match="momentum"):
            predict_post_resize_dispersion([1.0], lr=0.1, steps=4,
                                           momentum=1.0)


# --------------------------------------------------------------------------
# Sharded resize (subprocess, 8 host devices)
# --------------------------------------------------------------------------

_SHARD_SCRIPT = r"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import AveragingSchedule, PhaseEngine, FaultPlan
from repro.elastic import ElasticPlan, run_elastic
from repro.optim import SGD

assert len(jax.devices()) == 8, jax.devices()
DIM, WORKERS, STEPS = 8, 4, 16
rng = np.random.default_rng(0)
w_true = rng.standard_normal(DIM)
bx = jnp.asarray(rng.standard_normal(
    (STEPS, WORKERS, 16, DIM)).astype(np.float32))
by = jnp.asarray((np.asarray(bx) @ w_true).astype(np.float32))

def loss_fn(params, batch, rng):
    x, y = batch
    r = x @ params["w"] - y
    return jnp.mean(r * r), {}

def factory(m, t0, k):
    return [(bx[t, :m], by[t, :m]) for t in range(t0 - 1, t0 - 1 + k)]

params = {"w": jnp.zeros((DIM,), jnp.float32)}
plan = FaultPlan.parse("crash:m=1@t=4,rejoin:m=1@t=10", WORKERS,
                       straggle_prob=0.1)
kw = dict(steps=STEPS, seed=3, record_every=1)
noop = ElasticPlan(WORKERS, ((8, WORKERS),))

# a no-op plan runs the plain run's shard_map program, cut at segment
# boundaries (the elastic layer only adds phase cuts and host-side row
# repacks): bitwise
from repro.launch.mesh import make_worker_mesh
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
for sname, sched in SCHEDS.items():
    mesh = make_worker_mesh(WORKERS)
    eng = PhaseEngine(loss_fn, SGD(0.05), sched, faults=plan, mesh=mesh)
    f0, h0 = eng.run(params, factory(WORKERS, 1, STEPS),
                     num_workers=WORKERS, seed=3, record_every=1)
    f1, h1 = run_elastic(eng, params, factory, noop, **kw)
    np.testing.assert_array_equal(np.asarray(f0["w"]),
                                  np.asarray(f1["w"]))
    assert h0["loss"] == h1["loss"], sname
    assert h0["averages"] == h1["averages"]
    print("noop-ok", sname)

# a real resize on the mesh agrees with the unsharded elastic run to f32
# roundoff, with the same decision stream
resize = ElasticPlan(WORKERS, ((6, 3), (12, 4)), curriculum=2)
eng0 = PhaseEngine(loss_fn, SGD(0.05), AveragingSchedule("periodic", 4),
                   faults=plan)
fu, hu = run_elastic(eng0, params, factory, resize, **kw)
eng = PhaseEngine(loss_fn, SGD(0.05), AveragingSchedule("periodic", 4),
                  faults=plan, mesh=make_worker_mesh(WORKERS))
fs, hs = run_elastic(eng, params, factory, resize, **kw)
assert hs["resizes"] == [(6, 4, 3), (12, 3, 4)]
np.testing.assert_allclose(np.asarray(fu["w"]), np.asarray(fs["w"]),
                           rtol=1e-5, atol=1e-6)
assert hu["averages"] == hs["averages"]
print("resize-ok")
print("ALL-OK")
"""


def test_sharded_resize():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ALL-OK" in out.stdout
