"""Pallas fused worker-average + dispersion over the flat (M, P) plane.

One averaging event in the phase engine needs, per the paper: the worker
mean w̄ (or per-group means for the hierarchical schedule), the Eq. 4
dispersion E||w_i - w̄||², the mean broadcast back into every worker row,
and — with the DiLoCo-style outer optimizer — a momentum step on the
mean. The tree path pays 3–4 separate traversals of the params pytree
for that; here it is ONE tiled pass over the contiguous plane.
:func:`mix_disp` generalizes the event to a gossip topology
(``repro.topology``): ``W @ plane`` for a doubly-stochastic (M, M)
mixing matrix, each worker keeping its own mixed row.

Grid (cdiv(P, block_p),): each program reads a full-height
(M, block_p) column block (M is the worker count, 2–64 — far below a
VMEM tile, so the whole worker axis rides along in one block), reduces
over workers on the VPU, writes the broadcast block back in place (the
plane is aliased to the output), and adds its partial dispersion to one
(1, 1) SMEM accumulator that the sequential grid sums. P need not be a
multiple of the block: the last block is ragged, its out-of-range
columns are masked out of every cross-column reduction, and their
writes are dropped — no padded copy of the plane is ever made.

On CPU the kernels run in interpret mode for correctness validation;
the engine's CPU path uses the jnp twins in ``kernels/ref.py``. On a
TPU v5e they compile to Mosaic — ``tests/test_tpu_compile.py`` compiles
every entry point for a described v5e at smollm-360m's plane width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM bytes one (M, block_p) f32 column block aims for (M padded to
#: the 8-row sublane tile); the widest kernel double-buffers 11 such
#: blocks, well inside v5e's default scoped VMEM
BLOCK_BYTES = 512 * 1024


def block_cols(m: int, p: int, block_p: int | None):
    """(block width, grid length) for an (M, P) plane: ``block_p``
    columns, or by default the lane-aligned width of a BLOCK_BYTES
    block, never wider than the plane."""
    if block_p is None:
        rows = -(-m // 8) * 8
        block_p = max(128, BLOCK_BYTES // (4 * rows) // 128 * 128)
    block_p = min(block_p, max(p, 1))
    return block_p, pl.cdiv(max(p, 1), block_p)


def col_mask(p: int, bp: int, j):
    """(1, bp) mask of block ``j``'s in-range columns, or None when
    every block is full. A ragged last block reads unspecified values
    past column P; every reduction ACROSS columns must drop them."""
    if p % bp == 0:
        return None
    col = j * bp + jax.lax.broadcasted_iota(jnp.int32, (1, bp), 1)
    return col < p


def disp_part(x, glob, valid):
    """This block's share of the Eq. 4 dispersion, sum((x - w̄)²) / M."""
    sq = jnp.square(x - glob[None])
    if valid is not None:
        sq = jnp.where(valid, sq, 0.0)
    return jnp.sum(sq) / x.shape[0]


def accumulate(d_ref, part, first):
    """Add ``part`` to the (1, 1) SMEM accumulator, zeroed on the first
    grid step (the grid runs sequentially on one core)."""
    @pl.when(first)
    def _init():
        d_ref[0, 0] = jnp.float32(0.0)

    d_ref[0, 0] += part


def group_bcast(x, groups: int):
    """Per-group means of the (M, bp) block's contiguous row groups,
    broadcast back over each group's rows."""
    m, bp = x.shape
    s = m // groups
    return jnp.concatenate(
        [jnp.broadcast_to(jnp.mean(x[k * s:(k + 1) * s], axis=0,
                                   keepdims=True), (s, bp))
         for k in range(groups)], axis=0)


def _round_f16(x):
    """``x.astype(float16).astype(float32)`` in f32 arithmetic (Mosaic
    has no f16 vectors on v5e): round-half-even to a multiple of the
    f16 quantum of x's binade (2^-24 floor for f16 subnormals), with
    every scaling an exact power of two; overflow goes to ±inf."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    q_bits = jnp.maximum((bits & 0x7F800000) - (10 << 23), 103 << 23)
    q = jax.lax.bitcast_convert_type(q_bits, jnp.float32)
    inv = jax.lax.bitcast_convert_type((254 << 23) - q_bits, jnp.float32)
    r = jnp.round(x * inv) * q
    r = jnp.where(jnp.abs(r) > 65504.0, jnp.where(x < 0, -jnp.inf, jnp.inf),
                  r)
    return jnp.where(jnp.isfinite(x), r, x)


def round_codes(x, codes):
    """``x`` rounded through its columns' dtypes (``FlatSpec``
    ``rounding_codes``: 1 bf16, 2 f16, else f32). A one-dtype plane's
    code is a static int and emits only its own rounding; a mixed
    plane's (1, block_p) code row selects per column."""
    bf16 = lambda: x.astype(jnp.bfloat16).astype(jnp.float32)
    if isinstance(codes, int):
        return bf16() if codes == 1 else _round_f16(x)
    return jnp.where(codes == 1.0, bf16(),
                     jnp.where(codes == 2.0, _round_f16(x), x))


def codes_row(codes):
    """True when ``codes`` is a mixed plane's per-column row, which the
    kernels read as (1, block_p) blocks; a static int code or None
    needs no operand."""
    return codes is not None and not isinstance(codes, int)


def dspec(index_map):
    """The (1, 1) SMEM dispersion accumulator block."""
    return pl.BlockSpec((1, 1), index_map, memory_space=pltpu.SMEM)


def _avg_disp_kernel(x_ref, o_ref, d_ref, *, groups, p):
    j = pl.program_id(0)
    x = x_ref[...]                                   # (M, block_p) f32
    glob = jnp.mean(x, axis=0)                       # (block_p,)
    accumulate(d_ref, disp_part(x, glob, col_mask(p, x.shape[1], j)),
               j == 0)
    if groups > 1:
        o_ref[...] = group_bcast(x, groups)
    else:
        o_ref[...] = jnp.broadcast_to(glob[None], x.shape)


def _mix_disp_kernel(x_ref, w_ref, o_ref, d_ref, *, p):
    j = pl.program_id(0)
    x = x_ref[...]                                   # (M, block_p) f32
    glob = jnp.mean(x, axis=0)
    accumulate(d_ref, disp_part(x, glob, col_mask(p, x.shape[1], j)),
               j == 0)
    # the (M, M) @ (M, block_p) gossip mix rides the same column sweep:
    # M is tiny, so W lives whole in VMEM and the contraction hits the
    # MXU without extra plane traffic
    o_ref[...] = jnp.dot(w_ref[...], x, preferred_element_type=jnp.float32)


def _avg_disp_outer_kernel(x_ref, p_ref, v_ref, o_ref, a_ref, w_ref, d_ref,
                           *, lr, momentum, nesterov, p):
    j = pl.program_id(0)
    x = x_ref[...]                                   # (M, block_p) f32
    avg = jnp.mean(x, axis=0)
    accumulate(d_ref, disp_part(x, avg, col_mask(p, x.shape[1], j)),
               j == 0)
    g = p_ref[0] - avg                               # outer gradient
    vel = momentum * v_ref[0] + g
    step = momentum * vel + g if nesterov else vel
    upd = p_ref[0] - lr * step
    a_ref[0, :] = upd
    w_ref[0, :] = vel
    o_ref[...] = jnp.broadcast_to(upd[None], x.shape)


def _compressed_mix_kernel(*refs, wire, mode, groups, has_u, has_codes,
                           round_to, error_feedback, p):
    i = 0
    x_ref, e_ref = refs[0], refs[1]
    i = 2
    u_ref = refs[i] if has_u else None
    i += int(has_u)
    codes = refs[i][...] if has_codes else round_to
    i += int(has_codes)
    w_ref = refs[i] if mode == "mix" else None
    i += int(mode == "mix")
    o_ref, r_ref, d_ref, sc_ref = refs[i], refs[i + 1], refs[i + 2], refs[i + 3]

    ph, j = pl.program_id(0), pl.program_id(1)
    x = x_ref[...]                                   # (M, block_p) f32
    m, bp = x.shape
    valid = col_mask(p, bp, j)
    v = x + e_ref[...] if error_feedback else x
    glob = jnp.mean(x, axis=0)

    @pl.when(ph == 0)
    def _disp():
        # pre-encode, pre-average Eq. 4 dispersion, summed in phase 0
        accumulate(d_ref, disp_part(x, glob, valid), j == 0)

    if wire in ("int8", "one_bit"):
        # phase 0: accumulate the per-row scale statistic across the
        # column blocks into VMEM scratch, which persists over the
        # sequentially-executed grid (amax for int8, abs-sum for one_bit)
        av = jnp.abs(v)
        if valid is not None:
            av = jnp.where(valid, av, 0.0)
        part = (jnp.max(av, axis=1, keepdims=True) if wire == "int8"
                else jnp.sum(av, axis=1, keepdims=True))

        @pl.when((ph == 0) & (j == 0))
        def _init():
            sc_ref[...] = part

        @pl.when((ph == 0) & (j > 0))
        def _acc():
            sc_ref[...] = (jnp.maximum(sc_ref[...], part)
                           if wire == "int8" else sc_ref[...] + part)

    @pl.when(ph == 1)
    def _emit():
        if wire == "bf16":
            q = v.astype(jnp.bfloat16).astype(jnp.float32)
        elif wire == "int8":
            amax = sc_ref[...]
            s = jnp.where(amax > 0.0, amax / 127.0, 1.0)
            q = jnp.clip(jnp.floor(v / s + u_ref[...]), -127.0, 127.0) * s
        else:  # one_bit
            s = sc_ref[...] / p
            q = jnp.where(v >= 0.0, s, -s)
        if mode == "mix":
            out = jnp.dot(w_ref[...], q,
                          preferred_element_type=jnp.float32)
        elif mode == "group" and groups > 1:
            out = group_bcast(q, groups)
        else:
            out = jnp.broadcast_to(jnp.mean(q, axis=0)[None], (m, bp))
        if codes is not None:
            out = round_codes(out, codes)
        o_ref[...] = out
        r_ref[...] = v - q if error_feedback else e_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("groups", "block_p", "interpret"))
def avg_disp(plane, *, groups: int = 1, alive=None,
             block_p: int | None = None,
             interpret: bool | None = None):
    """plane: (M, P) float32 -> (averaged plane, Eq. 4 dispersion scalar).

    ``groups`` > 1 broadcasts per-group means (hierarchical inner
    average); the dispersion is always against the global mean.

    ``alive`` ((M,) f32, ``repro.faults``) degrades the event over the
    alive rows: the masked (group-)mean lowers to the SAME fused mix
    pass (``faults.masked_event_matrix`` is doubly stochastic with
    identity rows for dead workers), the dispersion is over the alive
    set, and dead rows keep their stale values. Matches the masked
    ``repro.kernels.ref.avg_disp_ref`` up to matmul rounding."""
    if alive is not None:
        from repro import faults as _faults
        A = _faults.masked_event_matrix(alive, groups)
        out, _ = mix_disp(plane, A, block_p=block_p, interpret=interpret)
        out = _faults.select_rows(out, plane, alive)
        return out, _faults.masked_dispersion(plane, alive)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, p = plane.shape
    assert groups >= 1 and m % groups == 0, (m, groups)
    bp, nb = block_cols(m, p, block_p)
    blk = pl.BlockSpec((m, bp), lambda i: (0, i))
    out, d = pl.pallas_call(
        functools.partial(_avg_disp_kernel, groups=groups, p=p),
        grid=(nb,),
        in_specs=[blk],
        out_specs=[blk, dspec(lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((m, p), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        input_output_aliases={0: 0},
        interpret=interpret,
        name="avg_disp",
    )(plane.astype(jnp.float32))
    return out, d[0, 0]


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def mix_disp(plane, W, *, alive=None, block_p: int | None = None,
             interpret: bool | None = None):
    """Fused gossip mix + dispersion: plane (M, P) f32, W (M, M)
    doubly-stochastic f32 -> (W @ plane, Eq. 4 dispersion of the input
    plane). Each worker keeps its own mixed row — no broadcast. The
    generalization of :func:`avg_disp` to a mixing-matrix topology
    (``repro.topology``); matches ``repro.kernels.ref.mix_disp_ref``.

    ``alive`` ((M,) f32, ``repro.faults``) renormalizes ``W`` over the
    alive rows (``faults.degraded_matrix``) before the same fused pass;
    dead rows keep their stale values and the dispersion is over the
    alive set."""
    if alive is not None:
        from repro import faults as _faults
        Wm = _faults.degraded_matrix(W.astype(jnp.float32), alive)
        out, _ = mix_disp(plane, Wm, block_p=block_p, interpret=interpret)
        out = _faults.select_rows(out, plane, alive)
        return out, _faults.masked_dispersion(plane, alive)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, p = plane.shape
    assert W.shape == (m, m), (W.shape, m)
    bp, nb = block_cols(m, p, block_p)
    blk = pl.BlockSpec((m, bp), lambda i: (0, i))
    out, d = pl.pallas_call(
        functools.partial(_mix_disp_kernel, p=p),
        grid=(nb,),
        in_specs=[blk, pl.BlockSpec((m, m), lambda i: (0, 0))],
        out_specs=[blk, dspec(lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((m, p), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        input_output_aliases={0: 0},
        interpret=interpret,
        name="mix_disp",
    )(plane.astype(jnp.float32), W.astype(jnp.float32))
    return out, d[0, 0]


@functools.partial(jax.jit,
                   static_argnames=("lr", "momentum", "nesterov", "block_p",
                                    "interpret"))
def avg_disp_outer(plane, prev_avg, vel, *, lr: float, momentum: float,
                   nesterov: bool = True, block_p: int | None = None,
                   interpret: bool | None = None):
    """Fused all-average + dispersion + outer momentum step.

    plane: (M, P) f32; prev_avg/vel: (P,) f32. Returns
    (averaged plane, new_avg, new_vel, dispersion) — the flat twin of
    ``worker_dispersion`` + ``consensus`` + ``OuterOptimizer.apply`` +
    ``replicate`` in one pass."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, p = plane.shape
    bp, nb = block_cols(m, p, block_p)
    blk = pl.BlockSpec((m, bp), lambda i: (0, i))
    row = pl.BlockSpec((1, bp), lambda i: (0, i))
    out, avg, new_vel, d = pl.pallas_call(
        functools.partial(_avg_disp_outer_kernel, lr=lr, momentum=momentum,
                          nesterov=nesterov, p=p),
        grid=(nb,),
        in_specs=[blk, row, row],
        out_specs=[blk, row, row, dspec(lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((m, p), jnp.float32),
            jax.ShapeDtypeStruct((1, p), jnp.float32),
            jax.ShapeDtypeStruct((1, p), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        input_output_aliases={0: 0, 1: 1, 2: 2},
        interpret=interpret,
        name="avg_disp_outer",
    )(plane.astype(jnp.float32), prev_avg.astype(jnp.float32)[None],
      vel.astype(jnp.float32)[None])
    return out, avg[0], new_vel[0], d[0, 0]


def compressed_mix(plane, resid, *, wire, mode="mean", groups: int = 1,
                   W=None, u=None, codes=None, error_feedback: bool = True,
                   alive=None, block_p: int | None = None,
                   interpret: bool | None = None):
    """Fused compressed averaging/mixing event on the (M, P) plane:
    error-feedback encode (``v = plane + resid``, ``q = Q(v)``,
    ``resid' = v - q`` — ``repro.core.compress`` formats ``bf16`` /
    ``int8`` / ``one_bit``), the event operator on the decoded ``q``
    (mode "mean" | "group" | "mix" with the doubly-stochastic (M, M)
    ``W``), dtype-rounding ``codes`` (``FlatSpec.rounding_codes``: a
    static int or a (P,) row), and the pre-encode Eq. 4 dispersion, in
    one pass. Not jitted itself, so an int code stays static; callers
    trace it inside their own jit.

    The scaled formats need a per-ROW statistic (amax / abs-mean) that
    spans every column block, so the kernel runs a (2, nb) grid: phase 0
    accumulates the row statistic into VMEM scratch (the grid executes
    sequentially, so scratch persists), phase 1 quantizes, applies the
    event and writes the plane + residual. ``u`` is the int8
    ``row_uniforms`` plane. Returns (plane, new residual, dispersion);
    matches ``repro.kernels.ref.compressed_avg_ref`` /
    ``compressed_mix_ref``.

    ``alive`` ((M,) f32, ``repro.faults``) degrades the event over the
    alive rows: masked means lower to the kernel's own fused ``mix``
    path on ``faults.masked_event_matrix``, gossip ``W`` is
    renormalized by ``faults.degraded_matrix``, and dead rows keep
    their stale params AND residual (they ship no bytes). Matches the
    masked refs up to matmul rounding."""
    assert wire in ("bf16", "int8", "one_bit"), wire
    assert mode in ("mean", "group", "mix"), mode
    assert (W is not None) == (mode == "mix"), (mode, W is None)
    if alive is not None:
        from repro import faults as _faults
        Wm = (_faults.degraded_matrix(W.astype(jnp.float32), alive)
              if mode == "mix"
              else _faults.masked_event_matrix(
                  alive, groups if mode == "group" else 1))
        out, r_new, _ = compressed_mix(
            plane, resid, wire=wire, mode="mix", W=Wm, u=u, codes=codes,
            error_feedback=error_feedback, block_p=block_p,
            interpret=interpret)
        out = _faults.select_rows(out, plane, alive)
        r_new = _faults.select_rows(r_new, resid, alive)
        return out, r_new, _faults.masked_dispersion(plane, alive)
    has_u = wire == "int8"
    assert (u is not None) == has_u, (wire, u is None)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, p = plane.shape
    assert groups >= 1 and m % groups == 0, (m, groups)
    bp, nb = block_cols(m, p, block_p)
    has_codes = codes_row(codes)

    blk = pl.BlockSpec((m, bp), lambda ph, i: (0, i))
    # outputs alias the plane and residual, so phase 0 (which writes
    # nothing) must not flush a block: it parks on block 0, which phase
    # 1's first step then fills before its first write-back
    oblk = pl.BlockSpec((m, bp), lambda ph, i: (0, i * ph))
    ins = [plane.astype(jnp.float32), resid.astype(jnp.float32)]
    in_specs = [blk, blk]
    if has_u:
        ins.append(u.astype(jnp.float32))
        in_specs.append(blk)
    if has_codes:
        ins.append(jnp.asarray(codes, jnp.float32)[None])
        in_specs.append(pl.BlockSpec((1, bp), lambda ph, i: (0, i)))
    if mode == "mix":
        assert W.shape == (m, m), (W.shape, m)
        ins.append(W.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((m, m), lambda ph, i: (0, 0)))

    out, r, d = pl.pallas_call(
        functools.partial(_compressed_mix_kernel, wire=wire, mode=mode,
                          groups=groups, has_u=has_u, has_codes=has_codes,
                          round_to=None if has_codes else codes,
                          error_feedback=error_feedback, p=p),
        grid=(2, nb),
        in_specs=in_specs,
        out_specs=[oblk, oblk, dspec(lambda ph, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, p), jnp.float32),
                   jax.ShapeDtypeStruct((m, p), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((m, 1), jnp.float32)],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
        name="compressed_mix",
    )(*ins)
    return out, r, d[0, 0]
