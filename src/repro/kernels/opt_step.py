"""Pallas fused optimizer step (+ optional averaging) on the (M, P) plane.

The phase engine's flat-native inner loop (paper Eq. 3: K cheap local
steps, then average) needs, per step: the optimizer update applied to
every worker row, and — on averaging steps — the worker mean (global or
per-group), the Eq. 4 dispersion, and the broadcast. Doing those as
separate passes costs 2–3 extra sweeps of the plane per averaging event
and a tree-mapped optimizer apply per local step; this kernel does
update + mean + dispersion + broadcast in ONE tiled pass.

Grid (cdiv(P, block_p),): each program reads full-height (M, block_p)
column blocks of the param plane, the grad plane and the S
optimizer-state planes (S=0 SGD, 1 Momentum, 2 AdamW — layouts from
``repro.core.flat.FlatOptSpec``), applies the update on the VPU, reduces
over the worker axis (M rides in-block, as in ``avg_disp``), writes the
updated/broadcast block plus state blocks back in place (the param and
state planes are aliased to the outputs), and adds its partial
dispersion to one SMEM accumulator. The last block may be ragged: its
out-of-range columns are masked out of the cross-column reductions
(``avg_disp.col_mask``), so no padded copy of any plane is made.
Dynamic per-step scalars (lr and the AdamW bias corrections) arrive as
one (1, 4) SMEM vector. bf16/f16 params round exactly like the pytree
optimizers (``FlatSpec.rounding_codes``): a one-dtype plane's code is
static, so only its rounding is emitted; a mixed plane's per-column
codes ride as an f32 row.

On CPU the kernel runs in interpret mode for validation; the engine's
CPU path uses the jnp twin ``repro.kernels.ref.opt_step_ref`` (identical
math). On a TPU v5e it compiles to Mosaic and is what the engine runs
(``tests/test_tpu_compile.py`` compiles it at smollm-360m's width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.avg_disp import (accumulate, block_cols, codes_row,
                                    col_mask, disp_part, dspec,
                                    group_bcast, round_codes)

_KINDS = ("sgd", "momentum", "adamw")
_MODES = ("none", "mean", "group", "mix")


def _opt_step_kernel(*refs, kind, mode, groups, nstate, has_codes,
                     round_to, mu, nesterov, b1, b2, eps, weight_decay,
                     wire, error_feedback, p):
    compressed = wire is not None
    scaled = wire in ("int8", "one_bit")
    has_u = wire == "int8"
    i = 0
    x_ref, g_ref = refs[0], refs[1]
    i = 2
    s_refs = refs[i:i + nstate]
    i += nstate
    codes = refs[i][...] if has_codes else round_to
    i += int(has_codes)
    w_ref = refs[i] if mode == "mix" else None
    i += int(mode == "mix")
    u_ref = refs[i] if has_u else None
    i += int(has_u)
    e_ref = refs[i] if compressed else None
    i += int(compressed)
    scal_ref = refs[i]
    i += 1
    o_ref = refs[i]
    s_out = refs[i + 1:i + 1 + nstate]
    i += 1 + nstate
    r_ref = refs[i] if compressed else None
    i += int(compressed)
    d_ref = refs[i]
    sc_ref = refs[i + 1] if scaled else None

    j = pl.program_id(1 if compressed else 0)       # column block
    x = x_ref[...]                                   # (M, block_p) f32
    g = g_ref[...]
    lr = scal_ref[0, 0]
    if kind == "sgd":
        upd = x - lr * g
    elif kind == "momentum":
        v = mu * s_refs[0][...] + g
        upd = x - lr * (g + mu * v if nesterov else v)
        s_out[0][...] = v
    else:  # adamw
        c1, c2 = scal_ref[0, 1], scal_ref[0, 2]
        m2 = b1 * s_refs[0][...] + (1 - b1) * g
        v2 = b2 * s_refs[1][...] + (1 - b2) * g * g
        d = (m2 / c1) / (jnp.sqrt(v2 / c2) + eps)
        upd = x - lr * (d + weight_decay * x)
        s_out[0][...] = m2
        s_out[1][...] = v2
    if codes is not None:
        upd = round_codes(upd, codes)

    m, bp = upd.shape
    valid = col_mask(p, bp, j)
    glob = jnp.mean(upd, axis=0)                     # (block_p,)
    # the Eq. 4 dispersion is emitted in EVERY mode: adaptive schedules
    # and the per-step diagnostic trace consume it on non-averaging
    # steps too
    if compressed:
        # (2, nb) grid: the update is recomputed in both phases (same
        # inputs, same values); phase 0 sums the dispersion and
        # accumulates the per-row scale statistic across column blocks
        # into VMEM scratch, phase 1 encodes, applies the event on the
        # decoded q and writes the plane + error-feedback residual
        ph = pl.program_id(0)

        @pl.when(ph == 0)
        def _disp():
            accumulate(d_ref, disp_part(upd, glob, valid), j == 0)

        ve = upd + e_ref[...] if error_feedback else upd
        if scaled:
            av = jnp.abs(ve)
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
                q = ve.astype(jnp.bfloat16).astype(jnp.float32)
            elif wire == "int8":
                amax = sc_ref[...]
                s = jnp.where(amax > 0.0, amax / 127.0, 1.0)
                q = jnp.clip(jnp.floor(ve / s + u_ref[...]),
                             -127.0, 127.0) * s
            else:  # one_bit
                s = sc_ref[...] / p
                q = jnp.where(ve >= 0.0, s, -s)
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
            r_ref[...] = ve - q if error_feedback else e_ref[...]
        return
    accumulate(d_ref, disp_part(upd, glob, valid), j == 0)
    if mode == "none":
        o_ref[...] = upd
        return
    if mode == "mix":
        # gossip topology: (M, M) @ (M, block_p) on the MXU — each
        # worker keeps its own mixed row, no broadcast (the dispersion
        # above stays the pre-mix diagnostic)
        out = jnp.dot(w_ref[...], upd, preferred_element_type=jnp.float32)
        if codes is not None:
            out = round_codes(out, codes)
        o_ref[...] = out
        return
    if mode == "group" and groups > 1:
        out = group_bcast(upd, groups)
    else:
        out = jnp.broadcast_to(glob[None], (m, bp))
    if codes is not None:
        out = round_codes(out, codes)
    o_ref[...] = out


def opt_step(plane, grads, planes, scalars, *, kind, mode="none",
             groups: int = 1, W=None, mu=0.9, nesterov=False, b1=0.9,
             b2=0.95, eps=1e-8, weight_decay=0.0, codes=None,
             wire=None, resid=None, u=None, error_feedback: bool = True,
             alive=None, umask=None,
             block_p: int | None = None, interpret: bool | None = None):
    """Fused optimizer step + optional averaging on the (M, P) plane.

    plane/grads: (M, P) f32; planes: tuple of S f32 state planes
    (``FlatOptSpec`` layout); scalars: (4,) f32 [lr, c1, c2, _];
    codes: ``FlatSpec.rounding_codes`` (None, a static int, or a (P,)
    f32 row; not jitted itself, so an int stays static — callers trace
    it inside their own jit). mode: "none" | "mean" |
    "group" | "mix" — "mix" applies the doubly-stochastic (M, M)
    mixing matrix ``W`` (``repro.topology``) after the update: each
    worker keeps its own mixed row, no broadcast. Returns
    (plane, state planes, Eq. 4 dispersion scalar).
    The dispersion of the post-update plane is emitted in every mode —
    "none" measures without averaging and "mix" pre-mix, so adaptive
    schedules and the per-step diagnostic trace see the true value on
    every step. Matches ``repro.kernels.ref.opt_step_ref``.

    ``wire`` (``repro.core.compress`` format ``bf16`` / ``int8`` /
    ``one_bit``; ``f32`` lowers to ``wire=None`` in the engine) fuses
    the compressed event into the pass: the error-feedback encode acts
    on the post-update plane (``resid`` the (M, P) residual, ``u`` the
    int8 ``row_uniforms`` plane), the event operator on the decoded
    ``q``. The scaled formats need a per-row statistic spanning all
    column blocks, so the grid becomes (2, nb) — phase 0 accumulates
    the row scales into VMEM scratch, phase 1 quantizes and applies the
    event. Returns (plane, state planes, new residual, dispersion).

    ``alive`` / ``umask`` ((M,) f32, ``repro.faults``) run the
    fault-degraded pass: the fused update kernel runs in "none" mode
    and only rows with ``umask > 0`` keep the result (dead and
    straggling rows must not advance optimizer momentum), then the
    masked event rides the SAME fused mix kernels — masked means lower
    to ``faults.masked_event_matrix``, gossip ``W`` to
    ``faults.degraded_matrix`` — with the dispersion over the alive
    set. Matches the masked ``opt_step_ref`` up to matmul rounding.
    """
    assert kind in _KINDS, kind
    assert mode in _MODES, mode
    assert (W is not None) == (mode == "mix"), (mode, W is None)
    if alive is not None:
        from repro import faults as _faults
        from repro.kernels import avg_disp as _avg
        if umask is None:
            umask = alive
        upd, new_planes, _ = opt_step(
            plane, grads, planes, scalars, kind=kind, mode="none",
            mu=mu, nesterov=nesterov, b1=b1, b2=b2, eps=eps,
            weight_decay=weight_decay, codes=codes, block_p=block_p,
            interpret=interpret)
        upd = _faults.select_rows(upd, plane, umask)
        new_planes = tuple(_faults.select_rows(n, o, umask)
                           for n, o in zip(new_planes, planes))
        if wire is not None and mode != "none":
            out, r_new, disp = _avg.compressed_mix(
                upd, resid, wire=wire, mode=mode, groups=groups, W=W,
                u=u, codes=codes, error_feedback=error_feedback,
                alive=alive, block_p=block_p, interpret=interpret)
            return out, new_planes, r_new, disp
        if mode == "none":
            return upd, new_planes, _faults.masked_dispersion(upd, alive)
        if mode == "mix":
            out, disp = _avg.mix_disp(upd, W, alive=alive,
                                      block_p=block_p, interpret=interpret)
        else:
            out, disp = _avg.avg_disp(
                upd, groups=groups if mode == "group" else 1,
                alive=alive, block_p=block_p, interpret=interpret)
        if codes is not None:
            out = round_codes(out, codes)
            out = _faults.select_rows(out, upd, alive)
        return out, new_planes, disp
    compressed = wire is not None
    assert not compressed or (wire in ("bf16", "int8", "one_bit")
                              and mode != "none"), (wire, mode)
    has_u = wire == "int8"
    assert (u is not None) == has_u, (wire, u is None)
    assert (resid is not None) == compressed, (wire, resid is None)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, p = plane.shape
    assert groups >= 1 and m % groups == 0, (m, groups)
    nstate = len(planes)
    bp, nb = block_cols(m, p, block_p)
    has_codes = codes_row(codes)

    # the compressed path runs a (2, nb) grid — index maps drop the
    # phase coordinate; its outputs alias the input planes, so phase 0
    # (which writes nothing) parks them on block 0, which phase 1's
    # first step fills before its first write-back
    if compressed:
        blk = pl.BlockSpec((m, bp), lambda ph, i: (0, i))
        oblk = pl.BlockSpec((m, bp), lambda ph, i: (0, i * ph))
        row = pl.BlockSpec((1, bp), lambda ph, i: (0, i))
        const = lambda ph, i: (0, 0)
        grid = (2, nb)
    else:
        blk = oblk = pl.BlockSpec((m, bp), lambda i: (0, i))
        row = pl.BlockSpec((1, bp), lambda i: (0, i))
        const = lambda i: (0, 0)
        grid = (nb,)

    ins = ([plane.astype(jnp.float32), grads.astype(jnp.float32)]
           + [s.astype(jnp.float32) for s in planes])
    in_specs = [blk, blk] + [blk] * nstate
    if has_codes:
        ins.append(jnp.asarray(codes, jnp.float32)[None])
        in_specs.append(row)
    if mode == "mix":
        assert W.shape == (m, m), (W.shape, m)
        ins.append(W.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((m, m), const))
    if has_u:
        ins.append(u.astype(jnp.float32))
        in_specs.append(blk)
    if compressed:
        ins.append(resid.astype(jnp.float32))
        in_specs.append(blk)
    ins.append(jnp.asarray(scalars, jnp.float32).reshape(1, 4))
    in_specs.append(pl.BlockSpec((1, 4), const, memory_space=pltpu.SMEM))

    nplanes_out = 1 + nstate + int(compressed)
    # update in place: plane -> out, state planes -> new state planes,
    # residual -> new residual
    aliases = {0: 0, **{2 + k: 1 + k for k in range(nstate)}}
    if compressed:
        aliases[len(ins) - 2] = 1 + nstate
    outs = pl.pallas_call(
        functools.partial(_opt_step_kernel, kind=kind, mode=mode,
                          groups=groups, nstate=nstate, has_codes=has_codes,
                          round_to=None if has_codes else codes, mu=mu,
                          nesterov=nesterov, b1=b1, b2=b2, eps=eps,
                          weight_decay=weight_decay, wire=wire,
                          error_feedback=error_feedback, p=p),
        grid=grid,
        in_specs=in_specs,
        out_specs=[oblk] * nplanes_out + [dspec(const)],
        out_shape=([jax.ShapeDtypeStruct((m, p), jnp.float32)]
                   * nplanes_out
                   + [jax.ShapeDtypeStruct((1, 1), jnp.float32)]),
        scratch_shapes=([pltpu.VMEM((m, 1), jnp.float32)]
                        if wire in ("int8", "one_bit") else []),
        input_output_aliases=aliases,
        interpret=interpret,
        name="opt_step",
    )(*ins)
    out, disp = outs[0], outs[-1][0, 0]
    new_planes = tuple(outs[1:1 + nstate])
    if compressed:
        return out, new_planes, outs[1 + nstate], disp
    return out, new_planes, disp
