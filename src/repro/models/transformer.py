"""Model assembly: heterogeneous block stacks (dense / local / recurrent /
rwkv / moe / cross-attn), encoder-decoder support (whisper), VLM
cross-attention, full-sequence forward (train & prefill) and single-token
decode with per-layer caches.

Layers are applied with an unrolled python loop (no lax.scan) so XLA's
cost analysis sees the full FLOP count (DESIGN.md §5); per-block remat is
available for the training path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs import LayerSpec, ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models import rwkv as rwkv_mod


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def _init_block(cfg: ModelConfig, spec: LayerSpec, key):
    ks = jax.random.split(key, 4)
    p = {"norm1": L.init_norm(cfg)}
    if spec.mixer in ("attn", "attn_local"):
        p["mixer"] = attn_mod.init_attn(cfg, ks[0])
    elif spec.mixer == "rglru":
        p["mixer"] = rec_mod.init_rglru(cfg, ks[0])
    elif spec.mixer == "rwkv":
        p["mixer"] = rwkv_mod.init_rwkv(cfg, ks[0])
    if spec.cross_attn:
        p["norm_cross"] = L.init_norm(cfg)
        p["cross"] = attn_mod.init_attn(cfg, ks[1], cross=True)
    p["norm2"] = L.init_norm(cfg)
    if spec.ffn == "dense":
        p["ffn"] = L.init_mlp(cfg, ks[2])
    elif spec.ffn == "moe":
        p["ffn"] = moe_mod.init_moe(cfg, ks[2])
    elif spec.ffn == "rwkv_cmix":
        p["ffn"] = rwkv_mod.init_rwkv_cmix(cfg, ks[2])
    return p


def init_params(cfg: ModelConfig, key):
    ks = jax.random.split(key, cfg.num_layers + cfg.encoder_layers + 2)
    params = {
        "embed": L.init_embed(cfg, ks[0]),
        "final_norm": L.init_norm(cfg),
        "layers": [
            _init_block(cfg, spec, ks[1 + i])
            for i, spec in enumerate(cfg.layers)
        ],
    }
    if cfg.encoder_layers:
        enc_spec = LayerSpec(mixer="attn", causal=False)
        params["encoder"] = {
            "layers": [
                _init_block(cfg, enc_spec, ks[1 + cfg.num_layers + i])
                for i in range(cfg.encoder_layers)
            ],
            "final_norm": L.init_norm(cfg),
        }
    return params


# --------------------------------------------------------------------------
# Full-sequence block / forward (train & prefill)
# --------------------------------------------------------------------------

def _apply_block(cfg: ModelConfig, spec: LayerSpec, p, x, memory, impl,
                 capture: int = 0):
    """capture > 0: also return the decode cache for this block, with
    attention K/V padded to ``capture`` positions (prefill)."""
    aux = {}
    cache = {}
    if spec.mixer != "none":
        h = L.apply_norm(cfg, p["norm1"], x)
        if spec.mixer in ("attn", "attn_local"):
            if capture:
                h, (k, v) = attn_mod.attention(cfg, p["mixer"], h,
                                               layer=spec, impl=impl,
                                               return_kv=True)
                pad = ((0, 0), (0, capture - k.shape[1]), (0, 0), (0, 0))
                cache["attn"] = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
            else:
                h = attn_mod.attention(cfg, p["mixer"], h, layer=spec,
                                       impl=impl)
        elif spec.mixer == "rglru":
            if capture:
                h, cache["rglru"] = rec_mod.apply_rglru(
                    cfg, p["mixer"], h, impl=impl, return_state=True)
            else:
                h = rec_mod.apply_rglru(cfg, p["mixer"], h, impl=impl)
        elif spec.mixer == "rwkv":
            if capture:
                h, cache["rwkv"] = rwkv_mod.apply_rwkv(
                    cfg, p["mixer"], h, impl=impl, return_state=True)
            else:
                h = rwkv_mod.apply_rwkv(cfg, p["mixer"], h, impl=impl)
        x = x + h
    if spec.cross_attn:
        h = L.apply_norm(cfg, p["norm_cross"], x)
        h = attn_mod.attention(cfg, p["cross"], h, layer=spec,
                               kv_x=memory, impl=impl)
        if capture:
            cache["cross"] = attn_mod.cross_cache_from_memory(
                cfg, p["cross"], memory)
        x = x + h
    h = L.apply_norm(cfg, p["norm2"], x)
    if spec.ffn == "dense":
        h = L.apply_mlp(cfg, p["ffn"], h)
    elif spec.ffn == "moe":
        h, aux = moe_mod.apply_moe(cfg, p["ffn"], h)
    elif spec.ffn == "rwkv_cmix":
        h2 = rwkv_mod.apply_rwkv_cmix(cfg, p["ffn"], h)
        if capture:
            cache.setdefault("rwkv", {})["shift_c"] = h[:, -1:]
        h = h2
    else:
        h = jnp.zeros_like(x)
    if capture:
        return x + h, aux, cache
    return x + h, aux


def encode(cfg: ModelConfig, params, memory_embed, impl="xla"):
    """Run the (whisper) encoder over stubbed frame embeddings."""
    x = memory_embed.astype(L.cdtype(cfg))
    enc_spec = LayerSpec(mixer="attn", causal=False)
    for p in params["encoder"]["layers"]:
        x, _ = _apply_block(cfg, enc_spec, p, x, None, impl)
    return L.apply_norm(cfg, params["encoder"]["final_norm"], x)


def _get_memory(cfg: ModelConfig, params, batch, impl):
    if cfg.family == "audio":
        return encode(cfg, params, batch["audio"], impl)
    if cfg.family == "vlm":
        return batch["media"].astype(L.cdtype(cfg))
    return None


def forward(cfg: ModelConfig, params, batch, *, impl="xla", remat=False,
            return_cache=False, cache_len=0):
    """batch: {"tokens": (B,S) int32, ["audio"|"media"]: (B,T,d)}.
    Returns (logits fp32 (B,S,V), aux dict of scalar metrics); with
    ``return_cache`` (true prefill) additionally a decode cache sized
    ``cache_len`` (>= S), ready for repro.models.decode_step."""
    x, aux_sum, caches = hidden(cfg, params, batch, impl=impl, remat=remat,
                                return_cache=return_cache,
                                cache_len=cache_len)
    logits = L.unembed(cfg, params["embed"], x)
    if return_cache:
        cache = {"pos": jnp.asarray(batch["tokens"].shape[1], jnp.int32),
                 "layers": caches}
        return logits, aux_sum, cache
    return logits, aux_sum


def hidden(cfg: ModelConfig, params, batch, *, impl="xla", remat=False,
           return_cache=False, cache_len=0):
    """The blocks and the final norm: (B, S, d) states, the aux sums and
    the per-layer decode caches (empty unless ``return_cache``)."""
    memory = _get_memory(cfg, params, batch, impl)
    tokens = batch["tokens"]
    x = L.embed(cfg, params["embed"], tokens)
    aux_sum = {"load_balance": 0.0, "router_z": 0.0}
    capture = 0
    if return_cache:
        assert not remat, "prefill cache capture is a no-remat path"
        capture = max(cache_len, tokens.shape[1])

    caches = []
    for spec, p in zip(cfg.layers, params["layers"]):
        fn = functools.partial(_apply_block, cfg, spec)
        if remat:
            fn = jax.checkpoint(
                lambda p_, x_, m_, fn=fn: fn(p_, x_, m_, impl))
            x, aux = fn(p, x, memory)
        elif capture:
            x, aux, c = fn(p, x, memory, impl, capture)
            caches.append(c)
        else:
            x, aux = fn(p, x, memory, impl)
        for k_ in aux:
            aux_sum[k_] = aux_sum[k_] + aux[k_]
    x = L.apply_norm(cfg, params["final_norm"], x)
    return x, aux_sum, caches


def _nll_sum(embed_p, x, labels, *, cfg: ModelConfig):
    """Sum over the positions with a label (>= 0) of the next-token
    negative log-likelihood of states ``x``."""
    logits = L.unembed(cfg, embed_p, x)
    mask = (labels >= 0).astype(jnp.float32)
    labels_c = jnp.clip(labels, 0, cfg.padded_vocab - 1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels_c[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask)


def _chunked_nll_sum(cfg: ModelConfig, embed_p, x, labels, chunk: int):
    """:func:`_nll_sum` over sequence chunks of ``chunk`` positions, each
    under ``jax.checkpoint``: the backward pass recomputes one chunk's
    logits at a time, so the (B, S, V) logits never exist whole."""
    b, s, d = x.shape
    if s % chunk:
        raise ValueError(f"sequence {s} is not a whole number of "
                         f"{chunk}-position loss chunks")
    n = s // chunk
    xs = x.reshape(b, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(b, n, chunk).swapaxes(0, 1)
    one = jax.checkpoint(functools.partial(_nll_sum, cfg=cfg))

    def body(acc, xl):
        return acc + one(embed_p, *xl), None
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total


def lm_loss(cfg: ModelConfig, params, batch, *, impl="xla", remat=False,
            loss_chunk=0):
    """Next-token cross-entropy (+ MoE aux). labels default to shifted
    tokens; positions where label < 0 are masked. ``loss_chunk`` > 0
    computes the cross-entropy over sequence chunks of that many
    positions (:func:`_chunked_nll_sum`), with the same value and
    gradient."""
    x, aux, _ = hidden(cfg, params, batch, impl=impl, remat=remat)
    if "labels" in batch:
        labels = batch["labels"]
    else:
        labels = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)),
                         constant_values=-1)
    mask = (labels >= 0).astype(jnp.float32)
    if loss_chunk:
        with jax.named_scope("model.loss"):
            total = _chunked_nll_sum(cfg, params["embed"], x, labels,
                                    loss_chunk)
    else:
        total = _nll_sum(params["embed"], x, labels, cfg=cfg)
    loss = total / jnp.maximum(jnp.sum(mask), 1.0)
    moe_layers = max(1, sum(1 for s in cfg.layers if s.ffn == "moe"))
    aux_loss = cfg.router_aux_coef * aux["load_balance"] / moe_layers \
        + 1e-3 * aux["router_z"] / moe_layers
    if cfg.num_experts:
        loss = loss + aux_loss
    metrics = {"ce": loss, **{k: v for k, v in aux.items()}}
    return loss, metrics


# --------------------------------------------------------------------------
# Decode (single token, per-layer caches)
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq_len: int, *,
               memory=None, params=None):
    """Build the per-layer decode cache pytree.

    memory: encoder/vision embeddings (B, T, d) — cross K/V are
    precomputed here (as a real serving runtime does at prefill)."""
    dt = L.cdtype(cfg)
    layers = []
    for spec, p in zip(cfg.layers, params["layers"] if params else [None] * cfg.num_layers):
        c = {}
        if spec.mixer in ("attn", "attn_local"):
            c["attn"] = attn_mod.init_attn_cache(cfg, batch, seq_len, dt)
        elif spec.mixer == "rglru":
            c["rglru"] = rec_mod.init_rglru_cache(cfg, batch, dt)
        elif spec.mixer == "rwkv":
            c["rwkv"] = rwkv_mod.init_rwkv_cache(cfg, batch, dt)
        if spec.cross_attn:
            assert memory is not None and p is not None
            c["cross"] = attn_mod.cross_cache_from_memory(cfg, p["cross"], memory)
        if spec.ffn == "rwkv_cmix":
            c.setdefault("rwkv", rwkv_mod.init_rwkv_cache(cfg, batch, dt))
        layers.append(c)
    return {"pos": jnp.zeros((), jnp.int32), "layers": layers}


def _decode_block(cfg, spec, p, x, cache, pos):
    if spec.mixer != "none":
        h = L.apply_norm(cfg, p["norm1"], x)
        if spec.mixer in ("attn", "attn_local"):
            h, cache["attn"] = attn_mod.decode_attention(
                cfg, p["mixer"], h, cache["attn"], pos, layer=spec)
        elif spec.mixer == "rglru":
            h, cache["rglru"] = rec_mod.decode_rglru(cfg, p["mixer"], h, cache["rglru"])
        elif spec.mixer == "rwkv":
            h, cache["rwkv"] = rwkv_mod.decode_rwkv(cfg, p["mixer"], h, cache["rwkv"])
        x = x + h
    if spec.cross_attn:
        h = L.apply_norm(cfg, p["norm_cross"], x)
        h = attn_mod.decode_cross_attention(cfg, p["cross"], h, cache["cross"])
        x = x + h
    h = L.apply_norm(cfg, p["norm2"], x)
    if spec.ffn == "dense":
        h = L.apply_mlp(cfg, p["ffn"], h)
    elif spec.ffn == "moe":
        h, _ = moe_mod.apply_moe(cfg, p["ffn"], h)
    elif spec.ffn == "rwkv_cmix":
        h, cache["rwkv"] = rwkv_mod.decode_rwkv_cmix(cfg, p["ffn"], h, cache["rwkv"])
    else:
        h = jnp.zeros_like(x)
    return x + h, cache


def decode_step(cfg: ModelConfig, params, tokens, cache):
    """tokens: (B,1) int32. Returns (logits (B,1,V) fp32, new cache)."""
    pos = cache["pos"]
    x = L.embed(cfg, params["embed"], tokens, pos_offset=pos)
    new_layers = []
    for spec, p, c in zip(cfg.layers, params["layers"], cache["layers"]):
        x, c = _decode_block(cfg, spec, p, x, dict(c), pos)
        new_layers.append(c)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], x)
    return logits, {"pos": pos + 1, "layers": new_layers}
