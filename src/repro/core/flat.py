"""Flat parameter plane: the whole worker model as ONE (M, P) buffer.

The phase engine's averaging events are pure worker-axis reductions —
mean over M, dispersion around that mean, an optional outer-optimizer
step on the mean. On a params *pytree* each of those is a separate tree
traversal (PR 1 paid 3–4 per event); on a contiguous ``(M, P)`` plane
they are one tiled pass over a single buffer, which is exactly the shape
``repro.kernels.avg_disp`` fuses.

:class:`FlatSpec` records the leaf layout (treedef, shapes, dtypes,
column offsets) so packing is invertible:

    spec  = FlatSpec.of(worker_params)        # leaves (M, *shape)
    plane = spec.pack(worker_params)          # (M, P) float32
    tree  = spec.unpack(plane)                # == worker_params bit-exact

The plane dtype is float32. float32 leaves are stored verbatim;
bfloat16/float16 leaves are stored as their exact float32 image (both
formats embed losslessly in float32) and rounded back on unpack, so the
pack→unpack roundtrip is bit-exact for every finite value and ±inf.
Integer / wider-than-32-bit leaves are not representable this way —
:func:`FlatSpec.supports` reports that, and the engine falls back to the
tree path for such trees.

``pack1``/``unpack1`` are the rank-(P,) variants for trees WITHOUT the
worker axis (consensus params, outer-optimizer state).

:class:`FlatOptSpec` extends the plane to the *optimizer state*: when an
optimizer's state is S structural copies of the params tree in float32
(Momentum velocity: S=1; AdamW moments: S=2; SGD: S=0), the state packs
into S extra ``(M, P)`` planes whose columns align 1:1 with the param
plane — the layout ``repro.kernels.opt_step`` fuses the local update
into. ``rounding_codes`` gives the per-column dtype codes that let a
plane-resident update round exactly like the pytree optimizers'
``.astype(p.dtype)`` after every step.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

_PACKABLE = (jnp.float32, jnp.bfloat16, jnp.float16)

#: per-column dtype codes for plane-resident rounding (0 = float32
#: verbatim, 1 = round through bfloat16, 2 = round through float16)
ROUND_F32, ROUND_BF16, ROUND_F16 = 0, 1, 2


def _packable(dtype) -> bool:
    return any(jnp.dtype(dtype) == jnp.dtype(d) for d in _PACKABLE)


@dataclass(frozen=True)
class FlatSpec:
    """Layout of a params pytree inside a flat float32 plane."""
    treedef: Any
    shapes: tuple          # per-leaf shapes WITHOUT the worker axis
    dtypes: tuple          # per-leaf original dtypes
    offsets: tuple         # per-leaf first column
    width: int             # P: total columns

    # ---- construction ----------------------------------------------------
    @classmethod
    def of(cls, tree, *, worker_axis: bool = True) -> "FlatSpec":
        """Build the spec from a (possibly abstract) pytree. With
        ``worker_axis`` the leading dim of every leaf is the worker axis
        and is excluded from the layout."""
        leaves, treedef = jax.tree.flatten(tree)
        shapes, dtypes, offsets = [], [], []
        off = 0
        for x in leaves:
            if not _packable(x.dtype):
                raise TypeError(
                    f"FlatSpec: dtype {x.dtype} has no exact float32 "
                    "image; use the tree path for this tree")
            shape = tuple(x.shape[1:] if worker_axis else x.shape)
            shapes.append(shape)
            dtypes.append(jnp.dtype(x.dtype))
            offsets.append(off)
            off += math.prod(shape)
        return cls(treedef, tuple(shapes), tuple(dtypes), tuple(offsets),
                   off)

    @staticmethod
    def supports(tree) -> bool:
        """True iff every leaf dtype embeds exactly in float32."""
        return all(_packable(x.dtype) for x in jax.tree.leaves(tree))

    # ---- (M, P) plane <-> worker tree ------------------------------------
    def pack(self, tree):
        """Leaves (M, *shape) -> (M, P) float32, columns in leaf order."""
        leaves = self.treedef.flatten_up_to(tree)
        m = leaves[0].shape[0] if leaves else 0
        cols = [jnp.asarray(x).astype(jnp.float32).reshape(m, -1)
                for x in leaves]
        return jnp.concatenate(cols, axis=1) if cols else \
            jnp.zeros((m, 0), jnp.float32)

    def unpack(self, plane, *, dtypes=None):
        """(M, P) float32 -> leaves (M, *shape) in their original dtype.
        ``dtypes`` overrides the cast (e.g. ``jnp.float32`` for optimizer
        moments, which mirror the param structure but stay float32)."""
        if dtypes is None:
            dtypes = self.dtypes
        elif not isinstance(dtypes, tuple):
            dtypes = (jnp.dtype(dtypes),) * len(self.shapes)
        m = plane.shape[0]
        leaves = [
            plane[:, o:o + math.prod(s)].reshape((m,) + s).astype(dt)
            for o, s, dt in zip(self.offsets, self.shapes, dtypes)]
        return jax.tree.unflatten(self.treedef, leaves)

    # ---- per-column dtype rounding ----------------------------------------
    def rounding_codes(self):
        """(P,) float32 per-column rounding codes (``ROUND_*``), or None
        when every leaf is float32 (no rounding pass needed). The codes
        let a plane-resident optimizer update reproduce the pytree path's
        ``.astype(p.dtype)`` bit-exactly: a bf16/f16 leaf's columns are
        rounded through their dtype after every update, so the plane
        always holds the exact float32 image of the tree.

        A tree whose leaves share one narrow dtype gets its code as a
        Python int instead: a trace-time constant, so the kernels emit
        that one rounding and a full-width model carries no P-wide row."""
        if all(dt == jnp.dtype(jnp.float32) for dt in self.dtypes):
            return None
        if len(set(self.dtypes)) == 1:
            return (ROUND_BF16 if self.dtypes[0] == jnp.dtype(jnp.bfloat16)
                    else ROUND_F16)
        codes = np.zeros(self.width, np.float32)
        for o, s, dt in zip(self.offsets, self.shapes, self.dtypes):
            if dt == jnp.dtype(jnp.bfloat16):
                codes[o:o + math.prod(s)] = ROUND_BF16
            elif dt == jnp.dtype(jnp.float16):
                codes[o:o + math.prod(s)] = ROUND_F16
        return codes

    # ---- (P,) vector <-> consensus tree ----------------------------------
    def pack1(self, tree):
        """Leaves of exactly ``shape`` (no worker axis) -> (P,) float32."""
        leaves = self.treedef.flatten_up_to(tree)
        cols = [jnp.asarray(x).astype(jnp.float32).reshape(-1)
                for x in leaves]
        return jnp.concatenate(cols) if cols else jnp.zeros((0,),
                                                            jnp.float32)

    def unpack1(self, vec, *, dtypes=None):
        """(P,) float32 -> consensus tree. ``dtypes`` overrides the cast
        (e.g. ``jnp.float32`` for outer-optimizer velocity, which mirrors
        the param structure but stays float32)."""
        if dtypes is None:
            dtypes = self.dtypes
        elif not isinstance(dtypes, tuple):
            dtypes = (jnp.dtype(dtypes),) * len(self.shapes)
        leaves = [vec[o:o + math.prod(s)].reshape(s).astype(dt)
                  for o, s, dt in zip(self.offsets, self.shapes, dtypes)]
        return jax.tree.unflatten(self.treedef, leaves)


@dataclass(frozen=True)
class FlatOptSpec:
    """Layout of an optimizer-state pytree as S extra (M, P) planes.

    Applies when the state is S structural copies of the params tree —
    float32 leaves of the param shapes, grouped copy-by-copy in flatten
    order (Momentum velocity S=1; AdamW ``{"m": .., "v": ..}`` S=2; SGD
    ``()`` S=0). Each copy packs through the param :class:`FlatSpec`, so
    state column j describes the same parameter as param column j — the
    alignment ``repro.kernels.opt_step`` relies on. :meth:`of` returns
    None for states that don't align (the engine then falls back to the
    per-step pack/unpack path).
    """
    treedef: Any           # the full opt-state treedef
    num_planes: int        # S
    param: FlatSpec

    @classmethod
    def of(cls, param: FlatSpec, opt_state) -> "FlatOptSpec | None":
        leaves, treedef = jax.tree.flatten(opt_state)
        n = len(param.shapes)
        if n == 0:
            return None
        if not leaves:
            return cls(treedef, 0, param)
        if len(leaves) % n:
            return None
        s = len(leaves) // n
        for k in range(s):
            for j in range(n):
                x = leaves[k * n + j]
                if (jnp.dtype(x.dtype) != jnp.dtype(jnp.float32)
                        or tuple(x.shape[1:]) != param.shapes[j]):
                    return None
        return cls(treedef, s, param)

    def pack(self, opt_state) -> tuple:
        """State tree -> tuple of S (M, P) float32 planes."""
        leaves = self.treedef.flatten_up_to(opt_state)
        n = len(self.param.shapes)
        return tuple(
            self.param.pack(
                jax.tree.unflatten(self.param.treedef,
                                   leaves[k * n:(k + 1) * n]))
            for k in range(self.num_planes))

    def unpack(self, planes: tuple):
        """Tuple of S (M, P) planes -> state tree (float32 leaves)."""
        leaves = []
        for pl in planes:
            leaves.extend(jax.tree.leaves(
                self.param.unpack(pl, dtypes=jnp.float32)))
        return jax.tree.unflatten(self.treedef, leaves)
