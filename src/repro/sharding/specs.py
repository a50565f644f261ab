"""PartitionSpec rules.

Train (local SGD): every state leaf carries a leading worker axis sharded
over the worker mesh axes; *within* a worker group the largest
model-divisible dim of each tensor is sharded over "model" (FSDP-flavored
— one dim sharded, XLA SPMD inserts the all-gathers). Batches shard their
first model-divisible dim over "model" too so activations stay small.

Serve: params have no worker axis; same within-group rule; the batch
shards over the data axes and KV caches shard sequence (long-context) or
head dims over "model".

These are the *baseline* rules — EXPERIMENTS.md §Perf iterates on them
(e.g. expert-dim sharding for MoE, sequence- vs batch-sharding).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def leaf_spec(shape, msize: int, *, model_axis="model", prefix=(),
              prefer_axis: int | None = None) -> P:
    """Shard the largest dim divisible by ``msize`` over the model axis
    (``prefer_axis`` overrides). ``prefix`` are specs for leading dims."""
    n = len(shape) - len(prefix)
    dims = shape[len(prefix):]
    best = None
    if prefer_axis is not None and dims[prefer_axis] % msize == 0:
        best = prefer_axis
    else:
        for i, s in enumerate(dims):
            if s % msize == 0 and s >= msize:
                if best is None or s > dims[best]:
                    best = i
    spec = [None] * n
    if best is not None:
        spec[best] = model_axis
    return P(*prefix, *spec)


def first_divisible_spec(shape, msize: int, *, model_axis="model",
                         prefix=()) -> P:
    """Shard the leading (batch) dim over the model axis when divisible;
    otherwise replicate within the worker group (FSDP-style). Sharding a
    *sequence* dim here is deliberately avoided: seq-sharded activations
    force SPMD to partition scans/attention along time, which explodes
    both collectives and compile time (measured: 20x+ on the multi-pod
    mesh; see EXPERIMENTS.md §Perf notes)."""
    n = len(shape) - len(prefix)
    dims = shape[len(prefix):]
    spec = [None] * n
    if dims and dims[0] % msize == 0 and dims[0] >= msize:
        spec[0] = model_axis
    return P(*prefix, *spec)


def tree_specs(template, msize: int, *, prefix=(), rule=leaf_spec,
               moe_expert_parallel: bool = False):
    """Map a pytree of ShapeDtypeStruct/arrays to PartitionSpecs."""
    def spec_of(path, leaf):
        prefer = None
        if moe_expert_parallel:
            names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
            if any(n in ("w_in", "w_out", "w_gate") for n in names) and \
                    len(leaf.shape) - len(prefix) == 3:
                prefer = 0  # expert dim
        if rule is leaf_spec:
            return leaf_spec(leaf.shape, msize, prefix=prefix, prefer_axis=prefer)
        return rule(leaf.shape, msize, prefix=prefix)
    return jax.tree_util.tree_map_with_path(spec_of, template)


def param_specs(params_template, msize: int, *, worker_axes=None,
                moe_expert_parallel: bool = False):
    prefix = (worker_axes,) if worker_axes is not None else ()
    return tree_specs(params_template, msize, prefix=prefix,
                      moe_expert_parallel=moe_expert_parallel)


def batch_specs(batch_template, msize: int, *, worker_axes=None):
    """Inputs: leading worker axis (train) then first-divisible rule."""
    prefix = (worker_axes,) if worker_axes is not None else ()
    return tree_specs(batch_template, msize, prefix=prefix,
                      rule=first_divisible_spec)


def cache_specs(cache_template, msize: int, *, data_axes,
                long_layout: str = "seq"):
    """Decode caches: batch over data axes when divisible; otherwise
    (batch=1 long-context) the k/v layout is governed by ``long_layout``:

      "seq"   — shard the sequence dim over data+model jointly (baseline;
                maximum capacity, but the dynamic cache update at a traced
                position forces an SPMD reshard — see EXPERIMENTS.md §Perf)
      "heads" — keep sequence unsharded, shard the largest head/hd dim
                over model (update is shard-local; no reshard collectives)
    """
    def spec_of(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
        dsize = _axes_size(data_axes)
        if shape[0] % dsize == 0 and shape[0] >= dsize:
            # batch shards over data; biggest remaining dim over model
            if long_layout == "heads" and ("k" in names or "v" in names) \
                    and len(shape) == 4:
                sub = leaf_spec(shape[2:], msize, prefix=())
                return P(data_axes, None, *sub)
            sub = leaf_spec(shape[1:], msize, prefix=())
            return P(data_axes, *sub)
        # batch=1 long-context k/v
        if "k" in names or "v" in names:
            if (long_layout == "seq" and len(shape) >= 2
                    and shape[1] % (dsize * msize) == 0):
                return P(None, (_flat(data_axes) + ("model",)),
                         *([None] * (len(shape) - 2)))
            if long_layout == "heads" and len(shape) == 4:
                sub = leaf_spec(shape[2:], msize, prefix=())
                return P(None, None, *sub)
        return leaf_spec(shape, msize, prefix=())
    return jax.tree_util.tree_map_with_path(spec_of, cache_template)


def _flat(axes):
    if isinstance(axes, str):
        return (axes,)
    out = []
    for a in axes:
        out.extend(_flat(a))
    return tuple(out)


# --------------------------------------------------------------------------
# Worker-axis sharding (the phase engine's layout on a mesh)
# --------------------------------------------------------------------------

def mesh_worker_axes(mesh) -> tuple:
    """The mesh axes that form the local-SGD worker axis: ("pod","data")
    when both exist, else ("data",), else the mesh's first axis."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes or tuple(mesh.axis_names[:1])


def plane_sharding(mesh, *, axes=None):
    """NamedSharding for any engine leaf with a leading worker axis (a
    mesh carries the worker state as leaves): M splits over the worker
    mesh axes, all trailing dims stay replicated within a worker
    shard."""
    axes = tuple(axes) if axes else mesh_worker_axes(mesh)
    return jax.sharding.NamedSharding(mesh, P(axes))


def engine_state_sharding(mesh, state, *, axes=None):
    """Shardings for a full ``repro.core.EngineState``: worker-axis
    leaves (params + optimizer state + the error-feedback residual
    plane + the per-worker fault rows) via :func:`plane_sharding`,
    everything else (outer state, PRNG keys, step, schedule state)
    replicated."""
    ws = plane_sharding(mesh, axes=axes)
    repl = jax.sharding.NamedSharding(mesh, P())
    return type(state)(
        jax.tree.map(lambda _: ws, state.worker_params),
        jax.tree.map(lambda _: ws, state.opt_state),
        jax.tree.map(lambda _: repl, state.outer_state),
        repl, repl, repl,
        jax.tree.map(lambda _: repl, state.sched),
        jax.tree.map(lambda _: ws, state.resid),
        jax.tree.map(lambda _: ws, state.fault))


def unshard_engine_state(state):
    """Pull the worker-axis leaves of an ``EngineState`` back to host
    as plain single-device arrays (``repro.elastic`` repacks rows
    between mesh layouts; the PRNG keys and scalar carries are left
    untouched — ``device_get`` on typed key arrays would strip the key
    dtype)."""
    pull = lambda t: jax.tree.map(
        lambda x: jnp.asarray(jax.device_get(x)), t)
    return state._replace(
        worker_params=pull(state.worker_params),
        opt_state=pull(state.opt_state),
        resid=pull(state.resid),
        fault=pull(state.fault))


_SIZES = {}


def set_axis_sizes(sizes: dict):
    """Record mesh axis sizes for divisibility checks (set by launch)."""
    _SIZES.clear()
    _SIZES.update(sizes)


def _axes_size(axes) -> int:
    n = 1
    for a in _flat(axes):
        n *= _SIZES.get(a, 1)
    return n
