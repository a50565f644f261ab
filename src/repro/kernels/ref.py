"""Pure-jnp oracles for every kernel — written as straightforward,
obviously-correct (sequential where natural) references. Kernel tests
assert_allclose against these across shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def flash_attention_ref(q, k, v, *, causal: bool, window: int = 0,
                        scale: float | None = None):
    """q: (B,S,H,hd), k/v: (B,S,Hkv,hd) -> (B,S,H,hd). GQA by repeat."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    g = h // hkv
    kr = jnp.repeat(k, g, axis=2)
    vr = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def avg_disp_ref(plane, *, groups: int = 1, alive=None):
    """Fused worker-average + dispersion on the flat (M, P) float32 plane.

    Returns (averaged plane, dispersion). ``groups`` > 1 averages within
    ``groups`` contiguous worker groups (hierarchical inner average);
    the dispersion is ALWAYS measured against the global mean — the
    paper's Eq. 4 diagnostic E||w_i - w̄||², matching
    ``repro.core.averaging.worker_dispersion``.

    ``alive`` ((M,) f32, ``repro.faults``) restricts the event to the
    alive rows: the mean and dispersion are over the alive set, dead
    rows keep their stale values.
    """
    if alive is not None:
        return plane_average_ref(plane, groups=groups, alive=alive)
    m, p = plane.shape
    glob = jnp.mean(plane, axis=0)
    disp = jnp.sum(jnp.square(plane - glob[None])) / m
    if groups > 1:
        gm = jnp.mean(plane.reshape(groups, m // groups, p), axis=1)
        out = jnp.broadcast_to(gm[:, None], (groups, m // groups, p))
        out = out.reshape(m, p)
    else:
        out = jnp.broadcast_to(glob[None], (m, p))
    return out, disp


def mix_disp_ref(plane, W, *, codes=None, alive=None):
    """Gossip mixing event on the flat (M, P) plane: ``W @ plane`` for a
    doubly-stochastic (M, M) mixing matrix — each worker keeps its own
    mixed row, no broadcast — plus the Eq. 4 dispersion of the INPUT
    plane (pre-mix, matching ``avg_disp_ref``'s pre-average diagnostic).
    ``Topology.full``'s W reproduces the mean only up to matmul rounding,
    which is why the engine lowers that kind to the mean path instead.

    ``codes`` (``FlatSpec.rounding_codes``) rounds the mixed rows
    through the leaf dtypes, matching the tree operator
    ``repro.topology.mix_tree``'s ``.astype``. ``alive`` ((M,) f32,
    ``repro.faults``) degrades ``W`` over the alive rows
    (``faults.degraded_matrix`` Metropolis renormalization): dead rows
    keep their stale values, the dispersion is over the alive set.
    Returns (mixed plane, dispersion)."""
    from repro import faults as _faults
    if alive is not None:
        disp = _faults.masked_dispersion(plane, alive)
        Wm = _faults.degraded_matrix(W.astype(jnp.float32), alive)
        out = jnp.dot(Wm, plane, preferred_element_type=jnp.float32)
        if codes is not None:
            out = round_to_codes(out, codes)
        return _faults.select_rows(out, plane, alive), disp
    m = plane.shape[0]
    glob = jnp.mean(plane, axis=0)
    disp = jnp.sum(jnp.square(plane - glob[None])) / m
    out = jnp.dot(W.astype(jnp.float32), plane,
                  preferred_element_type=jnp.float32)
    if codes is not None:
        out = round_to_codes(out, codes)
    return out, disp


def avg_disp_outer_ref(plane, prev_avg, vel, *, lr: float, momentum: float,
                       nesterov: bool = True, codes=None):
    """avg_disp with the outer-optimizer momentum step folded in: the
    consensus mean becomes the outer gradient target, the updated average
    is broadcast back into the plane. Mirrors
    ``repro.core.averaging.OuterOptimizer.apply`` on flat f32 buffers.

    ``codes`` (``FlatSpec.rounding_codes``) reproduces the tree path's
    dtype rounding for mixed-dtype params: the consensus mean is rounded
    before it becomes the outer gradient target (``consensus`` yields a
    leaf-dtype mean) and the updated average is rounded before carry and
    broadcast (``OuterOptimizer.apply`` ends with ``.astype(p.dtype)``).
    Dispersion stays measured against the unrounded f32 mean, like
    ``worker_dispersion``.

    plane: (M, P); prev_avg/vel: (P,). Returns
    (averaged plane, new_avg, new_vel, dispersion)."""
    m = plane.shape[0]
    avg = jnp.mean(plane, axis=0)
    disp = jnp.sum(jnp.square(plane - avg[None])) / m
    if codes is not None:
        avg = round_to_codes(avg, codes)
    g = prev_avg - avg
    vel = momentum * vel + g
    step = momentum * vel + g if nesterov else vel
    upd = prev_avg - lr * step
    if codes is not None:
        upd = round_to_codes(upd, codes)
    return jnp.broadcast_to(upd[None], plane.shape), upd, vel, disp


def round_to_codes(x, codes):
    """Round each column of ``x`` through its original dtype (codes from
    ``FlatSpec.rounding_codes``: 0 f32, 1 bf16, 2 f16) and back to f32 —
    the plane-resident twin of the pytree optimizers' ``.astype(p.dtype)``
    after every update. A one-dtype plane's code is one int (only its
    rounding is traced); a per-column row broadcasts over leading axes.

    The rounding is explicit (``reduce_precision``), not an
    ``astype(bf16).astype(f32)`` pair: the TPU compiler may treat such a
    pair as excess precision and skip it, leaving the plane unrounded.
    ``reduce_precision`` flushes f16 subnormals, so below f16's smallest
    normal the value is rounded to the 2^-24 subnormal quantum instead."""
    def bf16():
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def f16():
        sub = jnp.round(x * 2.0 ** 24) * 2.0 ** -24
        return jnp.where(jnp.abs(x) < 2.0 ** -14, sub,
                         jax.lax.reduce_precision(x, exponent_bits=5,
                                                  mantissa_bits=10))
    if isinstance(codes, int):
        return bf16() if codes == 1 else f16()
    return jnp.where(codes == 1.0, bf16(),
                     jnp.where(codes == 2.0, f16(), x))


def widen(x):
    """``x`` as float32 at its own precision: a bf16 or f16 array's
    image, rounded through its dtype explicitly (:func:`round_to_codes`).
    The TPU compiler may take a narrowing ``astype`` followed by this
    widening as excess precision and hand on the unrounded value; the
    explicit rounding is an identity wherever the values are stored
    rounded."""
    code = {jnp.dtype(jnp.bfloat16): 1,
            jnp.dtype(jnp.float16): 2}.get(jnp.dtype(x.dtype))
    xf = x.astype(jnp.float32)
    return xf if code is None else round_to_codes(xf, code)


def plane_update_ref(plane, grads, planes, scalars, *, kind, mu=0.9,
                     nesterov=False, b1=0.9, b2=0.95, eps=1e-8,
                     weight_decay=0.0, codes=None):
    """The local optimizer step on the flat (M, P) plane — bit-exact twin
    of ``repro.optim`` SGD/Momentum/AdamW ``apply`` on the packed tree.

    plane/grads: (M, P) f32 (grads = f32 image of the param-dtype grads,
    i.e. what one vjp through ``FlatSpec.unpack`` yields); planes: tuple
    of S state planes; scalars: (4,) f32 [lr, c1, c2, _]. Returns
    (updated plane, new state planes)."""
    lr, c1, c2 = scalars[0], scalars[1], scalars[2]
    g = grads
    if kind == "sgd":
        upd, planes = plane - lr * g, ()
    elif kind == "momentum":
        v = mu * planes[0] + g
        upd = plane - lr * (g + mu * v if nesterov else v)
        planes = (v,)
    elif kind == "adamw":
        m2 = b1 * planes[0] + (1 - b1) * g
        v2 = b2 * planes[1] + (1 - b2) * g * g
        d = (m2 / c1) / (jnp.sqrt(v2 / c2) + eps)
        upd = plane - lr * (d + weight_decay * plane)
        planes = (m2, v2)
    else:
        raise ValueError(f"unknown plane optimizer kind {kind!r}")
    if codes is not None:
        upd = round_to_codes(upd, codes)
    return upd, planes


def plane_average_ref(plane, *, groups: int = 1, codes=None, alive=None):
    """Worker mean (global, or per contiguous group) + Eq. 4 dispersion
    + broadcast on the (M, P) plane. Like ``avg_disp_ref`` but with the
    per-column dtype rounding the tree operators apply (``average_all``
    casts the mean back to the leaf dtype). ``alive`` ((M,) f32,
    ``repro.faults``) makes the event a masked one: the exact mean over
    alive rows broadcast to alive rows only, dead rows keeping their
    stale values, the dispersion over the alive set."""
    from repro import faults as _faults
    m, p = plane.shape
    if alive is not None:
        disp = _faults.masked_dispersion(plane, alive)
        if groups > 1:
            out = _faults.masked_group_mean(plane, alive, groups)
        else:
            glob = _faults.masked_mean(plane, alive)
            out = jnp.broadcast_to(glob[None], (m, p))
        if codes is not None:
            out = round_to_codes(out, codes)
        return _faults.select_rows(out, plane, alive), disp
    glob = jnp.mean(plane, axis=0)
    disp = jnp.sum(jnp.square(plane - glob[None])) / m
    if groups > 1:
        gm = jnp.mean(plane.reshape(groups, m // groups, p), axis=1)
        out = jnp.broadcast_to(gm[:, None], (groups, m // groups, p))
        out = out.reshape(m, p)
    else:
        out = jnp.broadcast_to(glob[None], (m, p))
    if codes is not None:
        out = round_to_codes(out, codes)
    return out, disp


def opt_step_ref(plane, grads, planes, scalars, *, kind, mode="none",
                 groups: int = 1, W=None, mu=0.9, nesterov=False, b1=0.9,
                 b2=0.95, eps=1e-8, weight_decay=0.0, codes=None,
                 wire=None, resid=None, u=None,
                 error_feedback: bool = True, alive=None, umask=None):
    """Fused local optimizer step + optional averaging event in one pass
    over the flat (M, P) plane — the jnp twin of
    ``repro.kernels.opt_step``.

    mode: "none" (pure local step), "mean" (step + worker mean + Eq. 4
    dispersion + broadcast), "group" (per-group means; dispersion still
    against the global mean), or "mix" (step + ``W @ plane`` gossip mix
    for the doubly-stochastic (M, M) ``W`` — no broadcast, each worker
    keeps its own mixed row). Returns
    (plane, new state planes, dispersion). The Eq. 4 dispersion of the
    post-update plane is emitted in EVERY mode — "none" measures
    without averaging and "mix" measures pre-mix, so adaptive schedules
    and the per-step diagnostic trace see the true value on every
    step.

    ``wire`` (``repro.core.compress`` format, not "f32") switches the
    averaging event to the compressed twin: the error-feedback encode
    acts on the POST-update plane (``resid`` the (M, P) residual, ``u``
    the int8 ``row_uniforms``), the event operator on the decoded
    ``q``, and the return gains the residual:
    (plane, new state planes, new residual, dispersion).

    ``alive`` / ``umask`` ((M,) f32, ``repro.faults``) make the pass a
    fault-degraded one: only rows with ``umask > 0`` apply the local
    update (dead AND straggling rows keep their params and optimizer
    planes — zeroing the gradient would still advance momentum), the
    event is masked over the alive rows (degraded ``W`` for "mix",
    exact alive means otherwise), and the dispersion is over the alive
    set."""
    from repro import faults as _faults
    upd, new_planes = plane_update_ref(
        plane, grads, planes, scalars, kind=kind, mu=mu, nesterov=nesterov,
        b1=b1, b2=b2, eps=eps, weight_decay=weight_decay, codes=codes)
    if alive is not None:
        if umask is None:
            umask = alive
        upd = _faults.select_rows(upd, plane, umask)
        new_planes = tuple(_faults.select_rows(n, o, umask)
                           for n, o in zip(new_planes, planes))
    planes = new_planes
    if wire is not None and mode != "none":
        kw = dict(wire=wire, u=u, codes=codes,
                  error_feedback=error_feedback, alive=alive)
        if mode == "mix":
            out, resid, disp = compressed_mix_ref(upd, resid, W, **kw)
        else:
            out, resid, disp = compressed_avg_ref(
                upd, resid, groups=groups if mode == "group" else 1, **kw)
        return out, planes, resid, disp
    if mode == "none":
        if alive is not None:
            return upd, planes, _faults.masked_dispersion(upd, alive)
        m = upd.shape[0]
        glob = jnp.mean(upd, axis=0)
        disp = jnp.sum(jnp.square(upd - glob[None])) / m
        return upd, planes, disp
    if mode == "mix":
        out, disp = mix_disp_ref(upd, W, codes=codes, alive=alive)
        return out, planes, disp
    out, disp = plane_average_ref(
        upd, groups=groups if mode == "group" else 1, codes=codes,
        alive=alive)
    return out, planes, disp


def compressed_avg_ref(plane, resid, *, wire, groups: int = 1, u=None,
                       codes=None, error_feedback: bool = True,
                       alive=None):
    """Compressed averaging event on the (M, P) plane: error-feedback
    encode (``v = plane + resid``, ``q = Q(v)``, ``resid' = v - q``,
    ``repro.core.compress``), then the worker mean (global, or per
    contiguous group) of the DECODED ``q`` broadcast back — what every
    worker reconstructs from the bytes actually shipped. The Eq. 4
    dispersion stays measured on the input plane (pre-encode,
    pre-average), like every other event twin. ``u`` is the
    ``row_uniforms`` plane (int8 stochastic rounding); ``codes``
    (``FlatSpec.rounding_codes``) rounds the broadcast mean through the
    leaf dtypes like ``plane_average_ref``. ``alive`` ((M,) f32,
    ``repro.faults``) masks the event: dead rows neither ship bytes nor
    accumulate residual, the mean is over the alive rows' decoded
    ``q``, and dead rows keep their stale params. Returns
    (plane, new residual, dispersion)."""
    from repro.core.compress import encode_decode
    from repro import faults as _faults
    m, p = plane.shape
    if alive is not None:
        disp = _faults.masked_dispersion(plane, alive)
        q, r_new = encode_decode(plane, resid, wire=wire, u=u,
                                 error_feedback=error_feedback)
        resid = _faults.select_rows(r_new, resid, alive)
        if groups > 1:
            out = _faults.masked_group_mean(q, alive, groups)
        else:
            out = jnp.broadcast_to(
                _faults.masked_mean(q, alive)[None], (m, p))
        if codes is not None:
            out = round_to_codes(out, codes)
        return _faults.select_rows(out, plane, alive), resid, disp
    glob = jnp.mean(plane, axis=0)
    disp = jnp.sum(jnp.square(plane - glob[None])) / m
    q, resid = encode_decode(plane, resid, wire=wire, u=u,
                             error_feedback=error_feedback)
    if groups > 1:
        gm = jnp.mean(q.reshape(groups, m // groups, p), axis=1)
        out = jnp.broadcast_to(gm[:, None], (groups, m // groups, p))
        out = out.reshape(m, p)
    else:
        out = jnp.broadcast_to(jnp.mean(q, axis=0)[None], (m, p))
    if codes is not None:
        out = round_to_codes(out, codes)
    return out, resid, disp


def compressed_mix_ref(plane, resid, W, *, wire, u=None, codes=None,
                       error_feedback: bool = True, alive=None):
    """Compressed gossip mixing event: error-feedback encode, then
    ``W @ q`` on the decoded plane — each worker keeps its own mixed
    row, no broadcast. The Eq. 4 dispersion is of the input plane
    (pre-encode, pre-mix), matching ``mix_disp_ref``. ``alive``
    degrades ``W`` over the alive rows (``repro.faults``): dead rows
    keep their stale params and residual. Returns
    (mixed plane, new residual, dispersion)."""
    from repro.core.compress import encode_decode
    from repro import faults as _faults
    m = plane.shape[0]
    if alive is not None:
        disp = _faults.masked_dispersion(plane, alive)
        q, r_new = encode_decode(plane, resid, wire=wire, u=u,
                                 error_feedback=error_feedback)
        resid = _faults.select_rows(r_new, resid, alive)
        Wm = _faults.degraded_matrix(W.astype(jnp.float32), alive)
        out = jnp.dot(Wm, q, preferred_element_type=jnp.float32)
        if codes is not None:
            out = round_to_codes(out, codes)
        return _faults.select_rows(out, plane, alive), resid, disp
    glob = jnp.mean(plane, axis=0)
    disp = jnp.sum(jnp.square(plane - glob[None])) / m
    q, resid = encode_decode(plane, resid, wire=wire, u=u,
                             error_feedback=error_feedback)
    out = jnp.dot(W.astype(jnp.float32), q,
                  preferred_element_type=jnp.float32)
    if codes is not None:
        out = round_to_codes(out, codes)
    return out, resid, disp


def rglru_scan_ref(a, b):
    """h_t = a_t h_{t-1} + b_t, h_0 = 0. a,b: (B,S,W) fp32. Sequential."""
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h
    a_t = jnp.swapaxes(a, 0, 1)
    b_t = jnp.swapaxes(b, 0, 1)
    _, hs = jax.lax.scan(step, jnp.zeros_like(a[:, 0]), (a_t, b_t))
    return jnp.swapaxes(hs, 0, 1)


def rwkv6_scan_ref(r, k, v, log_w, u):
    """Exact sequential WKV6.
    r,k,v,log_w: (B,S,H,n); u: (H*n,) or (H,n). Returns (B,S,H,n) fp32:
      y_t = r_t · (S_{t-1} + (u∘k_t) v_tᵀ);  S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
    """
    bsz, s, h, n = r.shape
    u = jnp.asarray(u, jnp.float32).reshape(h, n)
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    w = jnp.exp(log_w.astype(jnp.float32))

    def step(S, inp):
        rt, kt, vt, wt = inp  # (B,H,n)
        kv = kt[..., None] * vt[..., None, :]            # (B,H,n,n)
        y = jnp.einsum("bhi,bhij->bhj", rt, S + u[None, :, :, None] * kv)
        S = wt[..., None] * S + kv
        return S, y

    xs = tuple(jnp.swapaxes(t, 0, 1) for t in (rf, kf, vf, w))
    S0 = jnp.zeros((bsz, h, n, n), jnp.float32)
    _, ys = jax.lax.scan(step, S0, xs)
    return jnp.swapaxes(ys, 0, 1)


# Kernel-twin registry: maps every public Pallas kernel under
# ``repro.kernels`` to the jnp oracle(s) that define its semantics.
# Checked by the ``kernel-twin`` rule of ``repro.analysis`` — adding a
# kernel without registering (and testing) its twin fails CI.
TWINS = {
    "avg_disp": "avg_disp_ref",
    "mix_disp": "mix_disp_ref",
    "avg_disp_outer": "avg_disp_outer_ref",
    "compressed_mix": ("compressed_avg_ref", "compressed_mix_ref"),
    "opt_step": "opt_step_ref",
    "flash_attention": "flash_attention_ref",
    "rglru_scan": "rglru_scan_ref",
    "rwkv6_scan": "rwkv6_scan_ref",
}
