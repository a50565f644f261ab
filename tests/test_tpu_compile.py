"""Mosaic compiles of the engine's Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU
compiler refuses: block shapes off the (8, 128) tiling, vector ops
Mosaic cannot legalize, programs that do not fit HBM. Each test here
compiles one kernel entry point with ``interpret=False`` for one chip of
a described ``v5e:2x2`` topology — no chip is attached — at smollm-360m's
real plane width (P = 361,821,120 columns, M = 2 worker rows), and
checks that the compiled program fits the chip's 15.75 GiB of HBM.

The planes the kernels update in place are donated, as they are inside
the engine's phase scan. The topology is described inside a fixture (a
described topology loads the TPU compiler library, which one process
holds at a time), and the persistent compilation cache is off around
these compiles: a described device's executable cannot be read back.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.avg_disp import (avg_disp, avg_disp_outer, compressed_mix,
                                    mix_disp)
from repro.kernels.opt_step import opt_step

P_FULL = 361_821_120     # smollm-360m: FlatSpec(init_params(cfg)).width
M = 2
HBM_BYTES = int(15.75 * 2 ** 30)   # v5e HBM the compiler may allocate


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _plane():
    return jax.ShapeDtypeStruct((M, P_FULL), jnp.float32)


def _row():
    return jax.ShapeDtypeStruct((P_FULL,), jnp.float32)


def _opt(kind, mode, nstate, **kw):
    def f(x, g, *rest):
        states, rest = rest[:nstate], rest[nstate:]
        extra = dict(zip(kw.get("extra", ()), rest[1:]))
        return opt_step(x, g, states, rest[0], kind=kind, mode=mode,
                        interpret=False,
                        **{k: v for k, v in kw.items() if k != "extra"},
                        **extra)
    shapes = ([_plane(), _plane()] + [_plane()] * nstate
              + [jax.ShapeDtypeStruct((4,), jnp.float32)])
    for name in kw.get("extra", ()):
        shapes.append({"W": jax.ShapeDtypeStruct((M, M), jnp.float32),
                       "codes": _row(),
                       "resid": _plane(), "u": _plane()}[name])
    donate = (0,) + tuple(range(2, 2 + nstate))
    if "resid" in kw.get("extra", ()):
        donate += (3 + nstate + list(kw["extra"]).index("resid"),)
    return f, shapes, donate


CASES = {
    "opt_step-momentum-none": _opt("momentum", "none", 1),
    "opt_step-momentum-mean": _opt("momentum", "mean", 1),
    "opt_step-momentum-mix": _opt("momentum", "mix", 1, extra=("W",)),
    "opt_step-momentum-mean-codes": _opt("momentum", "mean", 1,
                                         extra=("codes",)),
    # a one-dtype model's static code: only its own rounding is emitted
    "opt_step-momentum-mean-bf16": _opt("momentum", "mean", 1, codes=1),
    "opt_step-momentum-mean-f16": _opt("momentum", "mean", 1, codes=2),
    "opt_step-adamw-none": _opt("adamw", "none", 2),
    "opt_step-momentum-mean-int8": _opt(
        "momentum", "mean", 1, wire="int8", extra=("resid", "u")),
    "avg_disp-mean": (lambda x: avg_disp(x, interpret=False), [_plane()],
                      (0,)),
    "avg_disp-groups2": (lambda x: avg_disp(x, groups=2, interpret=False),
                         [_plane()], (0,)),
    "avg_disp_outer": (
        lambda x, a, v: avg_disp_outer(x, a, v, lr=0.7, momentum=0.5,
                                       interpret=False),
        [_plane(), _row(), _row()], (0, 1, 2)),
    "mix_disp": (lambda x, w: mix_disp(x, w, interpret=False),
                 [_plane(), jax.ShapeDtypeStruct((M, M), jnp.float32)],
                 (0,)),
    "compressed_mix-int8": (
        lambda x, e, u: compressed_mix(x, e, wire="int8", u=u,
                                       interpret=False),
        [_plane(), _plane(), _plane()], (0, 1)),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_at_full_width(one_chip, name):
    f, shapes, donate = CASES[name]
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(f, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total <= HBM_BYTES, (name, total / 2 ** 30)
    # the kernels never copy a plane: no temp beyond a few scalars
    assert ma.temp_size_in_bytes < 2 ** 20, (name, ma.temp_size_in_bytes)


def _memory_total(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def test_leaf_carry_phase_compiles_without_the_plane(one_chip, monkeypatch):
    """smollm-360m at full width cut to 4 layers, 2 workers, periodic
    K=4, Momentum: on one v5e the rule takes the leaf carry, whose phase
    compiles with no plane-sized buffer and no Mosaic update on one, and
    needs less HBM than the plane carry of the same state (f32 planes,
    gradient plane, fused kernel). Each layer widens the gap (at one
    layer the leaf carry's temporaries still outweigh it)."""
    from repro.configs import get_config
    from repro.core import AveragingSchedule, FlatOptSpec, FlatSpec, \
        PhaseEngine
    from repro.models import init_params, lm_loss
    from repro.optim import Momentum
    # the kernels pick interpret mode from the default backend, which is
    # the CPU here; the described chip compiles them with Mosaic
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("smollm-360m")
    cfg = dataclasses.replace(cfg, num_layers=4, layers=cfg.layers[:4])
    engine = PhaseEngine(lambda p, b, r: lm_loss(cfg, p, b),
                         Momentum(lr=0.01, mu=0.9),
                         AveragingSchedule("periodic", 4))
    put = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                         sharding=one_chip)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tree = jax.tree.map(put, jax.eval_shape(lambda p: engine.init(p, M),
                                            params))
    batch = {"tokens": put(jax.ShapeDtypeStruct((4, M, 4, 128), jnp.int32))}
    assert engine.carry(tree) == "leaf"
    assert engine.plane_layout(tree) is None
    leaf = type(engine).run_phase.lower(engine, tree, batch).compile()

    spec = FlatSpec.of(tree.worker_params)
    layout = (spec, FlatOptSpec.of(spec, tree.opt_state))
    planes = jax.tree.map(put, jax.eval_shape(
        lambda s: engine.to_planes(layout, s), tree))
    plane = type(engine).run_phase.lower(engine, planes, batch,
                                         layout=layout).compile()
    row = f"[{M},{spec.width}]"
    assert "tpu_custom_call" in plane.as_text() and row in plane.as_text()
    assert "tpu_custom_call" not in leaf.as_text()
    assert row not in leaf.as_text()
    print(f"leaf carry {_memory_total(leaf)} B, plane carry "
          f"{_memory_total(plane)} B")
    assert _memory_total(leaf) < _memory_total(plane)


# the plane carry's compiled phase on the mesh below, per device, as
# the engine built it while a mesh could still carry the plane (f32
# planes, gradient plane, jnp update; 16 layers)
MESH_PLANE_16L_BYTES = 5_158_996_992


def test_leaf_carry_phase_compiles_without_the_plane_on_the_mesh(
        topo, one_chip, monkeypatch):
    """The mesh twin of the test above, on the described ``v5e:2x2``:
    smollm-360m at full width, 8 workers sharded 2 per chip under psum,
    periodic K=4, Momentum. A mesh carries the leaves; its sharded phase
    holds no (2, P) plane buffer and no Mosaic call, and needs less HBM
    per device than the plane carry of the same state did
    (``MESH_PLANE_16L_BYTES``, 4.80 GiB). Cut to 16 layers, not 4: the
    leaf step's own temporaries (the tied embedding's f32 gradient
    above all, as on one chip) outweighed the planes of a few layers,
    and each layer narrowed the gap: 2.54 against 2.06 GiB at 4 layers,
    4.59 against 4.80 at 16, 7.37 against 8.56 at all 32."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.configs import get_config
    from repro.core import AveragingSchedule, FlatSpec, PhaseEngine
    from repro.models import init_params, lm_loss
    from repro.optim import Momentum
    from repro.sharding.specs import engine_state_sharding
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    workers = 8
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("data",))
    cfg = get_config("smollm-360m")
    cfg = dataclasses.replace(cfg, num_layers=16, layers=cfg.layers[:16])
    engine = PhaseEngine(lambda p, b, r: lm_loss(cfg, p, b),
                         Momentum(lr=0.01, mu=0.9),
                         AveragingSchedule("periodic", 4), mesh=mesh)

    def put(tree):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, engine_state_sharding(mesh, tree))
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tree = put(jax.eval_shape(lambda p: engine.init(p, workers), params))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (4, workers, 4, 128), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec(None, "data")))}
    assert engine.carry(tree) == "leaf"
    assert engine.plane_layout(tree) is None
    leaf = type(engine).run_phase.lower(engine, tree, batch).compile()
    row = f"[{workers // 4},{FlatSpec.of(tree.worker_params).width}]"
    assert row not in leaf.as_text()
    assert "tpu_custom_call" not in leaf.as_text()
    print(f"per device: leaf carry {_memory_total(leaf)} B")
    assert _memory_total(leaf) < MESH_PLANE_16L_BYTES
