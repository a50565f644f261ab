"""Each cell's phase program, compiled for a described TPU v5e at the
cell's full size: one chip, or the 2x2 host for a four-chip cell. The
compile must pass and the program must fit one chip's memory; the test
prints ``memory_analysis()``. Nothing runs, so this says nothing about
time or results.

The topology is described inside a module fixture, never at import, and
the persistent compilation cache is off around these compiles (a
described device's program cannot be read back from it here).
"""
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import spec  # noqa: E402

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
HBM_LIMIT = 15.75 * 2 ** 30   # what a v5e chip gives a program


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


def phase_program(name, topo):
    """The cell's ``run_phase`` lowered on abstract state for ``topo``."""
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P
    from repro.sharding.specs import engine_state_sharding, set_axis_sizes
    c = spec.cell(name)
    cfg, tr, wl = c["config"], c["traffic"], c["workload"]
    adapter, ref = spec.model(cfg["model"])
    mesh = None
    if wl["chips"] > 1:
        mesh = Mesh(np.array(topo.devices[:wl["chips"]]), ("data",))
        set_axis_sizes({"data": wl["chips"]})
    engine = adapter.make_engine(cfg, tr, mesh=mesh, kernel_impl="pallas")
    dt = jnp.dtype(ref.shape(cfg)["param_dtype"])
    params = jax.eval_shape(
        lambda k: adapter.to_tree(cfg, ref.init_weights(cfg, k, dt)),
        ref.seed_key(0))
    tree = jax.eval_shape(lambda p: engine.init(p, tr["workers"]), params)
    layout = engine.plane_layout(tree)
    assert layout is not None, "the cell must run flat-native"
    state = jax.eval_shape(lambda s: engine.to_planes(layout, s), tree)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (tr["phase_len"], tr["workers"], tr["batch"], tr["seq"]), jnp.int32)}
    if mesh is None:
        one = SingleDeviceSharding(topo.devices[0])
        put = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)
        state, batch = jax.tree.map(put, (state, batch))
    else:
        sh = engine_state_sharding(mesh, state)
        state = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), state, sh)
        bsh = NamedSharding(mesh, P(None, "data"))
        batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=bsh), batch)
    return type(engine).run_phase.lower(engine, state, batch, layout=layout)


@pytest.mark.parametrize("name", CELLS)
def test_phase_fits_one_chip(name, topo, monkeypatch):
    # the kernels pick interpret mode from the default backend, which is
    # the CPU here; the described chip compiles them with Mosaic
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = phase_program(name, topo).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"{name}: arguments {mem.argument_size_in_bytes} B, outputs "
          f"{mem.output_size_in_bytes} B, aliased {mem.alias_size_in_bytes}"
          f" B, temp {mem.temp_size_in_bytes} B, total {total} B "
          f"({total / 2 ** 30:.2f} GiB per chip)")
    assert "tpu_custom_call" in compiled.as_text() or spec.cell(
        name)["workload"]["chips"] > 1, "the one-chip phase lost its kernel"
    assert total <= HBM_LIMIT, f"{total / 2 ** 30:.2f} GiB does not fit"
