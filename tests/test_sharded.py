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

A second subprocess runs the leaf carry on the mesh (``flat=False``
under psum, the carry a TPU mesh takes) against the single-device leaf
engine, one parametrised case each: every schedule, the outer
optimizer, groups and gossip mixes that cross shard boundaries, a fault
plan, a compressed wire, the indexed data plane, the telemetry
accumulator and a checkpoint save and resume of the sharded leaf state.

In-process tests cover the sharding spec helpers.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

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
    out = _run_8_devices(_SCRIPT)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL-OK" in out.stdout


_LEAF_SCRIPT = r"""
import json
import os
import tempfile
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from repro.checkpoint import load_engine_state, save_engine_state
from repro.core import (AveragingSchedule, Compression, OuterOptimizer,
                        PhaseEngine)
from repro.data.pipeline import DeviceDataset
from repro.faults import FaultPlan
from repro.optim import Momentum
from repro.telemetry.events import MemorySink
from repro.topology import Topology

assert len(jax.devices()) == 8, jax.devices()
DIM, SAMPLES, WORKERS, STEPS = 12, 256, 16, 41
rng = np.random.default_rng(0)
X = rng.standard_normal((SAMPLES, DIM))
y = X @ rng.standard_normal(DIM)
Xj, yj = jnp.asarray(X), jnp.asarray(y)
IDX = rng.integers(0, SAMPLES, (STEPS, 24, 8))


def loss_fn(params, batch, rng):
    # a vector and a matrix leaf: per-leaf psums, per-leaf events
    r = (batch["x"] @ params["w"] + 0.1 * jnp.sum(batch["x"] @ params["h"],
                                                 -1) - batch["y"])
    return 0.5 * jnp.mean(r * r), {}


PARAMS = {"w": jnp.zeros(DIM), "h": jnp.full((DIM, 3), 0.01)}
MESH = jax.make_mesh((8,), ("data",))


def batches(m=WORKERS):
    return [{"x": Xj[IDX[t, :m]], "y": yj[IDX[t, :m]]} for t in range(STEPS)]


def close(a, b, what, atol=1e-7):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=atol, err_msg=what)


def close_to_scale(a, b, what):
    # momentum (inner and outer) sums steps that cancel: its small
    # entries carry the roundoff of its large ones (the plane's psum path
    # parts from one device by as much), so f32 roundoff is measured at
    # the leaf's scale
    close(a, b, what, atol=1e-5 * float(np.max(np.abs(np.asarray(b)))))


def run(m=WORKERS, mesh=None, data=None, sink=None, **kw):
    eng = PhaseEngine(loss_fn, Momentum(lr=0.05, mu=0.9), flat=False,
                      mesh=mesh, telemetry=sink is not None, **kw)
    return eng.run(PARAMS, batches(m) if data is None else data,
                   num_workers=m, seed=3, record_every=1,
                   return_state=True, sink=sink)


def compare(m=WORKERS, mesh=MESH, data=None, **kw):
    # the leaf carry sharded under psum against the one-device leaf
    # carry: the same decision stream, every other number to f32 roundoff
    f0, h0, s0 = run(m, data=data and data(), **kw)
    f1, h1, s1 = run(m, mesh=mesh, data=data and data(), **kw)
    assert h0["averages"] == h1["averages"], (h0["averages"],
                                              h1["averages"])
    assert h0["averages"] or kw["schedule"].kind == "oneshot"
    for key in ("loss", "disp_trace", "dispersion"):
        assert [t for t, _ in h0[key]] == [t for t, _ in h1[key]], key
        close([v for _, v in h0[key]], [v for _, v in h1[key]], key)
    jax.tree.map(lambda a, b: close(a, b, "consensus"), f0, f1)
    jax.tree.map(lambda a, b: close(a, b, "params"), s0.worker_params,
                 s1.worker_params)
    for part in ("opt_state", "outer_state"):
        jax.tree.map(lambda a, b: close_to_scale(a, b, part),
                     getattr(s0, part), getattr(s1, part))
    for leaf in jax.tree.leaves(s1.worker_params):
        assert len(leaf.addressable_shards) == len(mesh.devices.flat)
    return h0, h1, s1


SCHEDS = {
    "oneshot": AveragingSchedule("oneshot"),
    "minibatch": AveragingSchedule("minibatch"),
    "periodic": AveragingSchedule("periodic", 8),
    "stochastic": AveragingSchedule("stochastic", zeta=0.2),
    "hierarchical": AveragingSchedule("hierarchical", inner_phase_len=5,
                                      outer_phase_len=20, inner_groups=2),
    "adaptive_threshold": AveragingSchedule("adaptive_threshold",
                                            disp_threshold=0.5,
                                            disp_ema_beta=0.5),
    "adaptive_budget": AveragingSchedule("adaptive_budget", comm_budget=6,
                                         budget_horizon=STEPS),
}
PERIODIC = SCHEDS["periodic"]
CASES = {f"schedule-{n}": (lambda s=s: compare(schedule=s))
         for n, s in SCHEDS.items()}
CASES["outer"] = lambda: compare(
    schedule=PERIODIC, outer=OuterOptimizer(lr=0.8, momentum=0.5))
# 12 workers over 4 shards of 3 rows, groups of 2 rows: rows 2-3 and
# 8-9 form groups that straddle two shards
CASES["hierarchical-groups-cross-shards"] = lambda: compare(
    m=12, mesh=jax.make_mesh((4,), ("data",), devices=jax.devices()[:4]),
    schedule=AveragingSchedule("hierarchical", inner_phase_len=3,
                               outer_phase_len=12, inner_groups=6))
CASES["ring-mix"] = lambda: compare(
    schedule=PERIODIC, topology=Topology.build("ring", WORKERS))
CASES["faults-crash-rejoin-straggle"] = lambda: compare(
    schedule=PERIODIC, faults=FaultPlan.parse(
        "crash:m=3@t=5,rejoin:m=3@t=19", WORKERS, straggle_prob=0.1))
CASES["compressed-int8"] = lambda: compare(
    schedule=PERIODIC, compression=Compression("int8"))
CASES["indexed-data"] = lambda: compare(
    schedule=PERIODIC,
    data=lambda: DeviceDataset({"x": Xj, "y": yj}, WORKERS,
                               indices=IDX[:, :WORKERS]))


def telemetry():
    sinks = [MemorySink(), MemorySink()]
    run(schedule=PERIODIC, sink=sinks[0])
    run(schedule=PERIODIC, mesh=MESH, sink=sinks[1])
    recs = [[r for r in s.records if r["type"] == "phase_metrics"]
            for s in sinks]
    assert len(recs[0]) == len(recs[1]) == 6
    for a, b in zip(*recs):
        assert a["carry"] == b["carry"] == "leaf"
        for k, v in a.items():
            if k in ("wall_s", "steps_per_s"):
                continue
            if isinstance(v, float):
                close(b[k], v, k)
            elif k not in ("loss_trace", "disp_trace"):
                assert b[k] == v, (k, b[k], v)


CASES["telemetry"] = telemetry


def checkpoint():
    # 16 steps on the mesh, saved, loaded and resumed on the mesh for the
    # rest: the uninterrupted sharded run's numbers, bit for bit
    eng = PhaseEngine(loss_fn, Momentum(lr=0.05, mu=0.9), PERIODIC,
                      flat=False, mesh=MESH)
    kw = dict(num_workers=WORKERS, seed=3, record_every=1,
              return_state=True)
    f_all, h_all, _ = eng.run(PARAMS, batches(), **kw)
    _, h_a, s_a = eng.run(PARAMS, batches()[:16], **kw)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ckpt")
        save_engine_state(path, s_a)
        loaded, step = load_engine_state(path, eng.init(PARAMS, WORKERS))
    assert step == 16
    f_b, h_b, s_b = eng.run(PARAMS, batches()[16:], state=loaded, **kw)
    for leaf in jax.tree.leaves((s_b.worker_params, s_b.opt_state)):
        assert [s.data.shape[0] for s in leaf.addressable_shards] == \
            [WORKERS // 8] * 8
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), f_all, f_b)
    assert h_a["loss"] + h_b["loss"] == h_all["loss"]
    assert h_a["averages"] + h_b["averages"] == h_all["averages"]


CASES["checkpoint-resume"] = checkpoint


def resume_in_place():
    # the state a run hands back is carried on as it stands: no program
    # copies it (a copy would hold the state twice on every device)
    eng = PhaseEngine(loss_fn, Momentum(lr=0.05, mu=0.9), PERIODIC,
                      flat=False, mesh=MESH)
    _, _, st = eng.run(PARAMS, batches()[:8], num_workers=WORKERS, seed=3,
                       return_state=True)
    again, layout = eng.start_state(None, WORKERS, state=st)
    assert layout is None

    def buffers(s):
        return [sh.data.unsafe_buffer_pointer()
                for x in jax.tree.leaves((s.worker_params, s.opt_state))
                for sh in x.addressable_shards]
    assert buffers(again) == buffers(st)


CASES["resume-in-place"] = resume_in_place

results = {}
for name, case in CASES.items():
    try:
        case()
        results[name] = "ok"
    except Exception:
        results[name] = traceback.format_exc()
print("RESULTS " + json.dumps(results))
"""

LEAF_CASES = [f"schedule-{n}" for n in (
    "oneshot", "minibatch", "periodic", "stochastic", "hierarchical",
    "adaptive_threshold", "adaptive_budget")] + [
    "outer", "hierarchical-groups-cross-shards", "ring-mix",
    "faults-crash-rejoin-straggle", "compressed-int8", "indexed-data",
    "telemetry", "checkpoint-resume", "resume-in-place"]


def _run_8_devices(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=1200)


@pytest.fixture(scope="module")
def leaf_mesh_results():
    out = _run_8_devices(_LEAF_SCRIPT)
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("RESULTS ")]
    assert out.returncode == 0 and lines, out.stdout + "\n" + out.stderr
    return json.loads(lines[-1][len("RESULTS "):])


@pytest.mark.parametrize("case", LEAF_CASES)
def test_leaf_carry_on_a_mesh_matches_one_device(leaf_mesh_results, case):
    """The leaf carry sharded 2 rows per device under psum against the
    single-device leaf carry (one subprocess runs every case)."""
    assert leaf_mesh_results.get(case) == "ok", \
        leaf_mesh_results.get(case, f"case {case} did not run")


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
