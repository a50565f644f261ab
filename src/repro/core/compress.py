"""Compressed communication planes: the wire precision of averaging events.

The paper trades statistical efficiency against communication by picking
WHEN to average; PR 4/5 added adaptive timing and sparse topologies.
This module adds the third axis — what PRECISION the averaged/mixed rows
travel at. Every averaging event conceptually ships each worker's (P,)
row to its neighbors; production gossip quantizes that row. Four wire
formats:

  - ``f32``     — identity. The engine lowers this to the existing
                  uncompressed paths, bit-exactly.
  - ``bf16``    — round-to-nearest-even cast through bfloat16 (half the
                  bytes; deterministic, no shared randomness needed).
  - ``int8``    — per-row scale ``s = max|v| / 127`` plus stochastic
                  rounding of ``v / s`` to the int8 grid (4x fewer
                  bytes + one f32 scale per row).
  - ``one_bit`` — per-row scale ``s = mean|v|`` times the sign of each
                  entry (signSGD/EF-style; 32x fewer bytes + one f32
                  scale per row).

The quantizer is *biased* per event for ``int8``/``one_bit`` — what
makes low-precision mixing still converge like Parallel Restarted SGD
(Yu, Yang & Zhu, arXiv 1807.06629) predicts for infrequent exact
averaging is **error feedback**: the residual ``e`` of what quantization
dropped is added back before the next encode,

    v = plane + e;   q = Q(v);   e' = v - q;   event acts on q,

so the quantization error is re-sent (at full resolution, eventually)
instead of lost. The residual rides the phase scan as one more (M, P)
float32 plane, carried in ``EngineState.resid`` and checkpointed
(engine-state layout v3).

Reproducibility: ``int8``'s stochastic rounding draws one uniform per
entry from a salted per-row fold_in chain on ``(dec_key, step,
global_row_index)`` (:func:`row_uniforms`) — the same pure-function
recipe as the stochastic schedule and the gossip matchings — so every
engine path, phase blocking, shard (each shard generates exactly its own
rows) and checkpoint/resume replays identical quantizations.

``repro.kernels.ref`` holds the jnp event twins
(``compressed_avg_ref`` / ``compressed_mix_ref``), ``repro.kernels``
the fused Pallas passes; :class:`repro.core.engine.PhaseEngine`
accepts ``compression=Compression(...)``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

#: wire formats, cheapest-precision last
WIRE_FORMATS = ("f32", "bf16", "int8", "one_bit")

#: payload bits per plane entry on the wire
WIRE_BITS = {"f32": 32, "bf16": 16, "int8": 8, "one_bit": 1}

#: formats whose per-event quantization is biased and therefore
#: requires the error-feedback residual to converge
_NEEDS_ERROR_FEEDBACK = ("int8", "one_bit")

#: formats that ship one f32 scale per row next to the payload
_SCALED = ("int8", "one_bit")

_ENC_SALT = 0x656E63  # "enc": decorrelates the stochastic-rounding
#                     # stream from the schedule's Bernoulli draws and
#                     # the gossip matchings, which fold the same
#                     # (dec_key, step)


def wire_row_bytes(p: int, wire: str) -> int:
    """Bytes ONE worker row (P entries) occupies on the wire: the packed
    payload (rounded up to whole bytes) plus the f32 per-row scale for
    the scaled formats."""
    if wire not in WIRE_FORMATS:
        raise ValueError(f"unknown wire format {wire!r}; "
                         f"pick one of {WIRE_FORMATS}")
    payload = -(-p * WIRE_BITS[wire] // 8)
    return payload + (4 if wire in _SCALED else 0)


@dataclass(frozen=True)
class Compression:
    """The communication-precision axis of every averaging/mixing event.

    ``wire`` picks the format; ``error_feedback`` keeps the (M, P)
    residual plane of what quantization dropped and re-adds it before
    the next encode. The biased formats (``int8``, ``one_bit``) refuse
    to run without it — without the residual their per-event bias
    accumulates and the run drifts from the consensus trajectory.
    ``f32`` is the identity: the engine lowers it to the uncompressed
    paths bit-exactly and carries no residual."""
    wire: str = "f32"
    error_feedback: bool = True

    def __post_init__(self):
        if self.wire not in WIRE_FORMATS:
            raise ValueError(f"unknown wire format {self.wire!r}; "
                             f"pick one of {WIRE_FORMATS}")
        if self.wire in _NEEDS_ERROR_FEEDBACK and not self.error_feedback:
            raise ValueError(
                f"wire format {self.wire!r} quantizes with per-event "
                "bias and needs the error-feedback residual to "
                "converge — keep error_feedback=True (or use bf16/f32)")

    @property
    def is_identity(self) -> bool:
        return self.wire == "f32"

    @property
    def stochastic(self) -> bool:
        """True when encoding consumes the per-row uniform stream
        (:func:`row_uniforms`)."""
        return self.wire == "int8"

    def row_bytes(self, p: int) -> int:
        return wire_row_bytes(p, self.wire)


def row_uniforms(dec_key, step, row_ids, p: int):
    """The stochastic-rounding uniforms for the given GLOBAL worker rows
    at this step: ``u[i] = uniform(fold_in(fold_in(fold_in(dec_key,
    salt), step), row_ids[i]), (p,))``.

    Keyed per row so a sharded engine generates exactly its own rows —
    bit-identical to the rows a single-device run generates — and pure
    in ``(dec_key, step)`` so every path, phase blocking and resume
    replays the same draws. ``step`` and ``row_ids`` may be traced."""
    base = jax.random.fold_in(jax.random.fold_in(dec_key, _ENC_SALT), step)
    return jax.vmap(
        lambda rid: jax.random.uniform(jax.random.fold_in(base, rid),
                                       (p,), jnp.float32))(row_ids)


def quantize(v, wire: str, *, u=None):
    """Encode+decode one (M, P) float32 plane through ``wire``: returns
    the decoded float32 image ``q`` — what the receiving workers
    reconstruct from the bytes actually shipped. ``u`` is the
    :func:`row_uniforms` plane (required for ``int8``, ignored
    otherwise). All-zero rows quantize to zero in every format."""
    if wire == "f32":
        return v
    if wire == "bf16":
        # explicit: the TPU compiler may skip an astype round trip
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    if wire == "int8":
        assert u is not None, "int8 stochastic rounding needs row_uniforms"
        amax = jnp.max(jnp.abs(v), axis=1, keepdims=True)
        s = jnp.where(amax > 0.0, amax / 127.0, 1.0)
        qi = jnp.clip(jnp.floor(v / s + u), -127.0, 127.0)
        return qi * s
    if wire == "one_bit":
        s = jnp.mean(jnp.abs(v), axis=1, keepdims=True)
        return jnp.where(v >= 0.0, s, -s)
    raise ValueError(f"unknown wire format {wire!r}; "
                     f"pick one of {WIRE_FORMATS}")


def encode_decode(plane, resid, *, wire: str, u=None,
                  error_feedback: bool = True):
    """The error-feedback encode of one event: ``v = plane + resid``,
    ``q = quantize(v)``, ``resid' = v - q``. Returns ``(q, resid')`` —
    the event operator (mean / group mean / ``W @``) acts on ``q``.
    Without ``error_feedback`` the residual passes through unchanged
    and ``v = plane``."""
    v = plane + resid if error_feedback else plane
    q = quantize(v, wire, u=u)
    return q, (v - q if error_feedback else resid)
