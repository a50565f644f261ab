"""The program's side of a StarCoder2 cell at long sequences: its
``ModelConfig`` at the published values (biases, LayerNorm epsilon, no
embedding scale), its params tree filled from the seeded weights, and
the ``PhaseEngine`` the window drives: ``lm_loss`` with the windowed
attention kernel, per-block remat and the chunked cross-entropy,
momentum, periodic averaging, telemetry on."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models.decoder import _norms
from bench.ref import starcoder2 as ref

LOSS_CHUNK = 1024   # positions per chunk of the cross-entropy


def program_config(c):
    from repro.configs import LayerSpec, ModelConfig
    s = ref.shape(c)
    mixer = "attn_local" if s["window"] else "attn"
    cfg = ModelConfig(
        name=c["name"], family="dense", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["ff"], vocab_size=s["vocab"],
        layers=tuple(LayerSpec(mixer=mixer) for _ in range(s["layers"])),
        sliding_window=s["window"], rope_theta=s["rope_theta"],
        norm="layernorm", norm_eps=s["eps"], embed_scale=False,
        linear_bias=s["bias"], act="gelu", gated_mlp=False,
        tie_embeddings=s["tied"], dtype=s["param_dtype"])
    if cfg.padded_vocab != s["vocab"]:
        raise ValueError("the program pads the vocabulary to a multiple of "
                         "256; the reference has no padded rows")
    return cfg


def _norm(w, name):
    return {k.rsplit(".", 1)[1]: v for k, v in w.items()
            if k.rsplit(".", 1)[0] == name}


def to_tree(c, w):
    """The program's params tree from the named weights."""
    s = ref.shape(c)
    tree = {"embed": {"tok": w["embed"]},
            "final_norm": _norm(w, "final_norm"), "layers": []}
    if not s["tied"]:
        tree["embed"]["unembed"] = w["head"]
    for i in range(s["layers"]):
        p = f"layers.{i}."
        tree["layers"].append({
            "norm1": _norm(w, p + "norm1"), "mixer": _norm(w, p + "attn"),
            "norm2": _norm(w, p + "norm2"), "ffn": _norm(w, p + "mlp")})
    return tree


def named(c, tree):
    """Inverse of :func:`to_tree`: ``name -> leaf``."""
    s = ref.shape(c)
    w = {"embed": tree["embed"]["tok"]}
    if not s["tied"]:
        w["head"] = tree["embed"]["unembed"]
    for i, lay in enumerate(tree["layers"]):
        p = f"layers.{i}."
        for part, name in (("norm1", "norm1"), ("mixer", "attn"),
                           ("norm2", "norm2"), ("ffn", "mlp")):
            w.update({p + name + "." + k: v for k, v in lay[part].items()})
    w.update({"final_norm." + k: v for k, v in tree["final_norm"].items()})
    return w


def make_params(c, seed):
    """The seeded weights as the program's tree, made on the device in
    one jitted call, in the parameter dtype."""
    dt = jnp.dtype(ref.shape(c)["param_dtype"])
    return jax.jit(lambda key: to_tree(c, ref.init_weights(c, key, dt)))(
        ref.seed_key(seed))


def make_engine(c, traffic, *, mesh=None, kernel_impl="auto"):
    from repro.core import AveragingSchedule, PhaseEngine
    from repro.models import lm_loss
    from repro.optim import Momentum
    if traffic["optimizer"] != "momentum" or traffic["schedule"] != "periodic":
        raise ValueError("this adapter drives periodic momentum SGD only")
    cfg = program_config(c)
    chunk = min(LOSS_CHUNK, traffic["seq"])
    return PhaseEngine(
        lambda p, batch, rng: lm_loss(cfg, p, batch, impl="splash",
                                      remat=True, loss_chunk=chunk),
        Momentum(lr=traffic["lr"], mu=traffic["momentum"]),
        AveragingSchedule("periodic", traffic["phase_len"]),
        kernel_impl=kernel_impl, mesh=mesh,
        collective=traffic.get("collective", "psum"), telemetry=True)


def state_norms(c, state, seed):
    """Per-worker change and velocity norms of an engine state in tree
    form (``run(..., return_state=True)``), against the seeded weights:
    lists over workers of ``name -> float``."""
    theta0 = named(c, make_params(c, seed))
    ch, vn = _norms(named(c, state.worker_params),
                    named(c, state.opt_state), theta0)
    del theta0
    ch, vn = jax.device_get((ch, vn))
    m = len(next(iter(ch.values())))
    return ([{k: float(v[w]) for k, v in ch.items()} for w in range(m)],
            [{k: float(v[w]) for k, v in vn.items()} for w in range(m)])
