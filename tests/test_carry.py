"""The phase's choice of carry: the (M, P) plane where unpacking a row
into the leaves is free, the leaves themselves on a TPU. The rule reads
only what the code can observe — the default backend, as the kernels
do, whether a mesh shards the phase and under which collective; never
the model — and
both carries train the same numbers: a bf16 decoder reaches the same
momentum, losses and dispersion to f32 roundoff, its bf16 params at
most one ulp apart, with and without a fault plan."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core import AveragingSchedule, PhaseEngine
from repro.core.engine import carry_for
from repro.faults import FaultPlan
from repro.models import init_params, lm_loss
from repro.optim import Momentum
from repro.telemetry.events import MemorySink

M = 2


@pytest.mark.parametrize("platform,sharded,collective,want", [
    ("tpu", False, "psum", "leaf"),
    ("tpu", True, "psum", "leaf"),
    ("cpu", False, "psum", "plane"),
    ("cpu", True, "psum", "plane"),
    ("gpu", False, "psum", "plane"),
    ("gpu", True, "psum", "plane"),
    ("tpu", True, "gather", "plane"),
    ("cpu", True, "gather", "plane"),
], ids=["tpu", "tpu-mesh", "cpu", "cpu-mesh", "gpu", "gpu-mesh",
        "tpu-mesh-gather", "cpu-mesh-gather"])
def test_carry_rule(platform, sharded, collective, want):
    """A TPU carries leaves, on one device or a mesh under psum; the
    gather collective exists to reproduce the single-device plane bit
    for bit, so it keeps the plane everywhere."""
    assert carry_for(platform, sharded, collective) == want


def _loss(params, batch, rng):
    return 0.5 * jnp.sum(params["w"] * params["b"][0]) ** 2, {}


MATRIX = {"w": jnp.ones((3, 2)), "b": jnp.ones(2)}
VECTORS = {"w": jnp.ones(6), "b": jnp.ones(2)}


@pytest.mark.parametrize("backend,params,kw,want", [
    ("cpu", MATRIX, {}, "plane"),
    ("cpu", VECTORS, {}, "plane"),
    ("cpu", MATRIX, {"flat": False}, "leaf"),
    ("cpu", {"w": jnp.ones((3, 2)), "b": jnp.ones(2, jnp.int32)}, {},
     "leaf"),
    ("tpu", MATRIX, {}, "leaf"),
    ("tpu", VECTORS, {}, "leaf"),
    ("tpu", MATRIX, {"mesh": True}, "leaf"),
    ("tpu", MATRIX, {"mesh": True, "collective": "gather"}, "plane"),
    ("cpu", MATRIX, {"mesh": True}, "plane"),
    ("cpu", MATRIX, {"mesh": True, "flat": False}, "leaf"),
], ids=["cpu-matrix", "cpu-vectors", "flat-false", "no-f32-image",
        "tpu-matrix", "tpu-vectors", "tpu-mesh", "tpu-mesh-gather",
        "cpu-mesh", "cpu-mesh-flat-false"])
def test_engine_carry_reads_the_state(monkeypatch, backend, params, kw,
                                      want):
    """Off a TPU the plane is free, unless ``flat=False`` or a leaf has
    no float32 image; on a TPU every tree carries its leaves, a tree of
    vectors as well as one of matrices (leaf ranks do not enter), on one
    device and on a mesh under psum — the gather collective keeps the
    plane."""
    if kw.get("mesh"):
        kw = dict(kw, mesh=jax.make_mesh((1,), ("data",)))
    engine = PhaseEngine(_loss, Momentum(lr=0.1), AveragingSchedule(
        "periodic", 4), **kw)
    state = engine.init(params, M)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert engine.carry(state) == want
    assert (engine.plane_layout(state) is None) == (want == "leaf")


def _decoder_run(workers=M, **kw):
    """Two phases (periodic K=4, Momentum) of a 2-layer bf16 decoder on
    ``workers`` workers; the final state in tree form, the history and
    the phase_metrics records."""
    cfg = reduce_config(get_config("smollm-360m"), num_layers=2, d_model=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = PhaseEngine(lambda p, b, r: lm_loss(cfg, p, b),
                         Momentum(lr=0.05, mu=0.9),
                         AveragingSchedule("periodic", 4), telemetry=True,
                         **kw)
    rng = np.random.default_rng(7)
    batches = [{"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (workers, 2, 16)), jnp.int32)}
        for _ in range(8)]
    sink = MemorySink()
    _, hist, state = engine.run(params, batches, num_workers=workers, seed=3,
                                phase_len=4, record_every=1,
                                return_state=True, sink=sink)
    return params, hist, state, [r for r in sink.records
                                 if r["type"] == "phase_metrics"]


@pytest.mark.parametrize("workers,faults", [
    (M, None), (3, FaultPlan.parse("crash:m=2@t=2,rejoin:m=2@t=6", 3))],
    ids=["no-faults", "crash-rejoin"])
def test_leaf_and_plane_carries_agree_on_a_bf16_decoder(workers, faults):
    """Without faults the dispersion is ``worker_dispersion``; under a
    fault plan each carry measures it over the alive rows (the plane's
    ``masked_dispersion``, the leaves' ``masked_dispersion_tree``): two
    of three workers in steps 2-5."""
    params, h_pl, s_pl, rec_pl = _decoder_run(workers, faults=faults)
    _, h_lf, s_lf, rec_lf = _decoder_run(workers, faults=faults,
                                         flat=False)
    assert [r["carry"] for r in rec_pl] == ["plane", "plane"]
    assert [r["carry"] for r in rec_lf] == ["leaf", "leaf"]
    assert h_pl["averages"] == h_lf["averages"] == 2

    # losses and the Eq. 4 dispersion of every step, to f32 roundoff
    np.testing.assert_allclose([v for _, v in h_lf["loss"]],
                               [v for _, v in h_pl["loss"]], rtol=1e-6)
    np.testing.assert_allclose([v for _, v in h_lf["disp_trace"]],
                               [v for _, v in h_pl["disp_trace"]],
                               rtol=1e-5)
    assert all(v > 0 for _, v in h_pl["disp_trace"][:7])

    # momentum stays f32, to f32 roundoff
    for a, b in zip(jax.tree.leaves(s_lf.opt_state),
                    jax.tree.leaves(s_pl.opt_state)):
        assert a.dtype == b.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)

    # bf16 params stay bf16; where the carries part, by one ulp at most
    # (adjacent bit patterns), and only on a rounding boundary's few
    differ = total = 0
    for a, b, p0 in zip(jax.tree.leaves(s_lf.worker_params),
                        jax.tree.leaves(s_pl.worker_params),
                        jax.tree.leaves(params)):
        assert a.dtype == b.dtype == p0.dtype == jnp.bfloat16
        ia = np.asarray(a).view(np.int16).astype(np.int32)
        ib = np.asarray(b).view(np.int16).astype(np.int32)
        assert np.abs(ia - ib).max() <= 1
        differ += int(np.sum(ia != ib))
        total += ia.size
    assert differ <= total * 1e-3, (differ, total)


def test_leaf_carry_runs_on_a_mesh_and_gather_keeps_the_plane():
    """``flat=False`` on a mesh carries the leaves under psum (a
    one-device mesh here; tests/test_sharded.py shards eight) and trains
    what one device trains; the gather collective validates the plane
    carry and refuses the leaves."""
    mesh = jax.make_mesh((1,), ("data",))
    _, h_one, s_one, _ = _decoder_run(flat=False)
    _, h_mesh, s_mesh, rec = _decoder_run(flat=False, mesh=mesh)
    assert [r["carry"] for r in rec] == ["leaf", "leaf"]
    assert h_mesh["averages"] == h_one["averages"] == 2
    np.testing.assert_allclose([v for _, v in h_mesh["loss"]],
                               [v for _, v in h_one["loss"]], rtol=1e-6)
    np.testing.assert_allclose([v for _, v in h_mesh["disp_trace"]],
                               [v for _, v in h_one["disp_trace"]],
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_mesh.opt_state),
                    jax.tree.leaves(s_one.opt_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    with pytest.raises(ValueError, match="gather"):
        _decoder_run(flat=False, mesh=mesh, collective="gather")
