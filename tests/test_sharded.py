"""Sharded (M, P) plane: shard_map phase over the mesh worker axes.

The heavyweight validation runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (jax fixes its
device count at import, so the parent process can't flip it):

  - gather collective: bit-identical params AND history vs the
    single-device engine for the paper's Momentum recipe, across all
    5 static + 2 adaptive (dispersion-driven, stateful) averaging
    schedules (+ the outer optimizer, the indexed on-device data
    plane, and the sparse mixing topologies — ring / torus / random
    gossip pairs — whose W-mix events all_gather the row shards);
  - psum collective: identical decision streams / averaging counts —
    including the adaptive kinds, whose decisions consume the psum'd
    per-step dispersion — params and traces equal to f32 roundoff.

In-process tests cover the sharding spec helpers.
"""
import os
import subprocess
import sys

import jax
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.sharding.specs import (engine_state_sharding, mesh_worker_axes,
                                  plane_sharding)

_SCRIPT = r"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import AveragingSchedule, PhaseEngine, OuterOptimizer
from repro.data.pipeline import DeviceDataset
from repro.optim import Momentum

assert len(jax.devices()) == 8, jax.devices()
DIM, SAMPLES, WORKERS, STEPS = 12, 256, 16, 41
rng = np.random.default_rng(0)
X = rng.standard_normal((SAMPLES, DIM))
y = X @ rng.standard_normal(DIM)
Xj, yj = jnp.asarray(X), jnp.asarray(y)
idx = rng.integers(0, SAMPLES, (STEPS, WORKERS, 8))

def loss_fn(params, batch, rng):
    r = batch["x"] @ params["w"] - batch["y"]
    return 0.5 * jnp.mean(r * r), {}

params = {"w": jnp.zeros(DIM)}
batches = lambda: [{"x": Xj[idx[t]], "y": yj[idx[t]]} for t in range(STEPS)]
mesh = jax.make_mesh((8,), ("data",))
kw = dict(num_workers=WORKERS, seed=3, record_every=1)
opt = lambda: Momentum(lr=0.05, mu=0.9)

scheds = {
    "oneshot": AveragingSchedule("oneshot"),
    "minibatch": AveragingSchedule("minibatch"),
    "periodic": AveragingSchedule("periodic", 8),
    "stochastic": AveragingSchedule("stochastic", zeta=0.2),
    "hierarchical": AveragingSchedule("hierarchical", inner_phase_len=5,
                                      outer_phase_len=20, inner_groups=2),
    # stateful kinds: decisions ride SchedState on the per-step
    # dispersion, which the psum collective reduces with one extra psum
    "adaptive_threshold": AveragingSchedule("adaptive_threshold",
                                            disp_threshold=0.5,
                                            disp_ema_beta=0.5),
    "adaptive_budget": AveragingSchedule("adaptive_budget", comm_budget=6,
                                         budget_horizon=STEPS),
}
for name, sch in scheds.items():
    f0, h0 = PhaseEngine(loss_fn, opt(), sch).run(params, batches(), **kw)
    # gather collective: bit-identical
    f1, h1 = PhaseEngine(loss_fn, opt(), sch, mesh=mesh,
                         collective="gather").run(params, batches(), **kw)
    np.testing.assert_array_equal(np.asarray(f0["w"]), np.asarray(f1["w"]))
    assert h0 == h1, name
    # psum collective: same decisions, f32-roundoff params/traces
    f2, h2 = PhaseEngine(loss_fn, opt(), sch, mesh=mesh,
                         collective="psum").run(params, batches(), **kw)
    np.testing.assert_allclose(np.asarray(f0["w"]), np.asarray(f2["w"]),
                               rtol=1e-5, atol=1e-7)
    assert h0["averages"] == h2["averages"], name
    assert [t for t, _ in h0["dispersion"]] == \
        [t for t, _ in h2["dispersion"]], name
    np.testing.assert_allclose([v for _, v in h0["loss"]],
                               [v for _, v in h2["loss"]],
                               rtol=1e-5, atol=1e-7)
    print("ok", name)

# outer optimizer, sharded
sch = AveragingSchedule("periodic", 8)
mk = lambda **e: PhaseEngine(loss_fn, opt(), sch,
                             outer=OuterOptimizer(lr=0.8, momentum=0.5), **e)
f0, h0 = mk().run(params, batches(), **kw)
f1, h1 = mk(mesh=mesh, collective="gather").run(params, batches(), **kw)
np.testing.assert_array_equal(np.asarray(f0["w"]), np.asarray(f1["w"]))
assert h0 == h1
print("ok outer")

# indexed on-device data plane, sharded
f0, h0 = PhaseEngine(loss_fn, opt(), sch).run(
    params, DeviceDataset({"x": Xj, "y": yj}, WORKERS, indices=idx), **kw)
f1, h1 = PhaseEngine(loss_fn, opt(), sch, mesh=mesh,
                     collective="gather").run(
    params, DeviceDataset({"x": Xj, "y": yj}, WORKERS, indices=idx), **kw)
np.testing.assert_array_equal(np.asarray(f0["w"]), np.asarray(f1["w"]))
assert h0 == h1
print("ok indexed")

# gossip-topology mixing events (repro.topology): gather bit-identical,
# psum same decisions / f32-roundoff params — incl. the per-event
# random gossip matching, replayed identically on every shard from the
# replicated (dec_key, step)
from repro.topology import Topology
for kind in ("ring", "torus", "gossip_pairs"):
    topo = Topology.build(kind, WORKERS)
    f0, h0 = PhaseEngine(loss_fn, opt(), sch, topology=topo).run(
        params, batches(), **kw)
    f1, h1 = PhaseEngine(loss_fn, opt(), sch, topology=topo, mesh=mesh,
                         collective="gather").run(params, batches(), **kw)
    np.testing.assert_array_equal(np.asarray(f0["w"]), np.asarray(f1["w"]))
    assert h0 == h1, kind
    f2, h2 = PhaseEngine(loss_fn, opt(), sch, topology=topo, mesh=mesh,
                         collective="psum").run(params, batches(), **kw)
    assert h0["averages"] == h2["averages"], kind
    assert [t for t, _ in h0["dispersion"]] == \
        [t for t, _ in h2["dispersion"]], kind
    np.testing.assert_allclose(np.asarray(f0["w"]), np.asarray(f2["w"]),
                               rtol=1e-5, atol=1e-7)
    print("ok topology", kind)

# compressed communication planes: the gather collective all_gathers the
# error-feedback residual rows too and must stay bit-identical to the
# single-device run; psum encodes shard-locally (per-row scales and
# fold_in uniforms keyed by GLOBAL row ids) and reduces the encoded
# sums — same decision stream, f32-roundoff params
from repro.core import Compression
for wire in ("bf16", "int8", "one_bit"):
    for sname in ("periodic", "stochastic", "adaptive_budget"):
        sch_c, comp = scheds[sname], Compression(wire)
        f0, h0 = PhaseEngine(loss_fn, opt(), sch_c, compression=comp).run(
            params, batches(), **kw)
        f1, h1 = PhaseEngine(loss_fn, opt(), sch_c, compression=comp,
                             mesh=mesh, collective="gather").run(
            params, batches(), **kw)
        np.testing.assert_array_equal(np.asarray(f0["w"]),
                                      np.asarray(f1["w"]))
        assert h0 == h1, (wire, sname)
        f2, h2 = PhaseEngine(loss_fn, opt(), sch_c, compression=comp,
                             mesh=mesh, collective="psum").run(
            params, batches(), **kw)
        assert h0["averages"] == h2["averages"], (wire, sname)
        assert [t for t, _ in h0["dispersion"]] == \
            [t for t, _ in h2["dispersion"]], (wire, sname)
        np.testing.assert_allclose(np.asarray(f0["w"]),
                                   np.asarray(f2["w"]),
                                   rtol=1e-5, atol=1e-7)
    print("ok compressed", wire)

# compressed W-mix events under both collectives
topo = Topology.build("ring", WORKERS)
comp = Compression("int8")
f0, h0 = PhaseEngine(loss_fn, opt(), sch, topology=topo,
                     compression=comp).run(params, batches(), **kw)
f1, h1 = PhaseEngine(loss_fn, opt(), sch, topology=topo, compression=comp,
                     mesh=mesh, collective="gather").run(
    params, batches(), **kw)
np.testing.assert_array_equal(np.asarray(f0["w"]), np.asarray(f1["w"]))
assert h0 == h1
f2, h2 = PhaseEngine(loss_fn, opt(), sch, topology=topo, compression=comp,
                     mesh=mesh, collective="psum").run(
    params, batches(), **kw)
assert h0["averages"] == h2["averages"]
np.testing.assert_allclose(np.asarray(f0["w"]), np.asarray(f2["w"]),
                           rtol=1e-5, atol=1e-7)
print("ok compressed ring mix")

# the run's state is built already split over the mesh (plane form):
# every device computes and holds only its own worker rows
eng = PhaseEngine(loss_fn, opt(), sch, mesh=mesh)
st, layout = eng.start_state(params, WORKERS)
assert layout is not None
for plane in (st.worker_params, *st.opt_state):
    assert plane.shape[0] == WORKERS
    assert len(plane.addressable_shards) == 8
    assert all(s.data.shape[0] == WORKERS // 8
               for s in plane.addressable_shards)
print("ok sharded start_state")
print("ALL-OK")
"""


def test_sharded_engine_matches_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL-OK" in out.stdout


def test_mesh_worker_axes():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    assert mesh_worker_axes(mesh) == ("data",)
    mesh3 = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    assert mesh_worker_axes(mesh3) == ("pod", "data")


def test_plane_sharding_spec():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    s = plane_sharding(mesh)
    assert s.spec == P(("data",))
    s2 = plane_sharding(mesh, axes=("model",))
    assert s2.spec == P(("model",))


def test_engine_state_sharding_tree():
    from repro.core import EngineState
    mesh = jax.make_mesh((1,), ("data",))
    from repro.core import AveragingSchedule
    state = EngineState(
        worker_params={"w": np.zeros((4, 3))},
        opt_state={"v": np.zeros((4, 3))},
        outer_state=(),
        key=np.zeros(2, np.uint32), dec_key=np.zeros(2, np.uint32),
        step=np.int32(0),
        sched=AveragingSchedule("periodic", 8).init_sched_state(),
        resid=np.zeros((4, 3), np.float32))
    sh = engine_state_sharding(mesh, state)
    assert sh.worker_params["w"].spec == P(("data",))
    assert sh.opt_state["v"].spec == P(("data",))
    assert sh.key.spec == P()
    assert sh.step.spec == P()
    assert all(s.spec == P() for s in sh.sched)
    # the error-feedback residual plane shards with the worker rows
    assert sh.resid.spec == P(("data",))
