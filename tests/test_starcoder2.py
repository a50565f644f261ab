"""StarCoder2 at a small size: the program (biases, LayerNorm epsilon,
no embedding scale, the windowed attention kernel in Pallas interpret
mode, remat, the chunked loss) against the benchmark's plain reference
``bench/ref/starcoder2.py`` on the same seeded weights, and the cell's
configuration against the published widths.

The small model keeps the kernel's real head (head_dim 128) and shrinks
the rest: d 256, 4/2 heads, MLP 512, window 64, vocabulary 512,
sequences of 256, 2 layers, float32 parameters (so that the program and
the float32 HIGHEST-precision reference differ by summation order
alone)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from bench.feed import Feed, TokenBlocks
from bench.models import starcoder2 as adapter
from bench.ref import starcoder2 as ref
from repro.configs import get_config
from repro.core import engine as engine_mod
from repro.kernels.window_attention import window_attention
from repro.models import lm_loss
from repro.models.attention import _sdpa_xla, make_mask

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "bench", "configs", "starcoder2-3b-l5.json")
SEQ = 256
PUBLISHED = {"hidden_size": 3072, "intermediate_size": 12288,
             "num_attention_heads": 24, "num_key_value_heads": 2,
             "sliding_window": 4096, "vocab_size": 49152,
             "rope_theta": 999999.4420358813, "norm_epsilon": 1e-05,
             "use_bias": True, "hidden_act": "gelu_pytorch_tanh",
             "norm_type": "layer_norm", "max_position_embeddings": 16384}


def _published():
    with open(CONFIG) as f:
        return json.load(f)


def small(window=64):
    c = _published()
    c.update(name="starcoder2-small", hidden_size=256,
             intermediate_size=512, num_attention_heads=4,
             num_key_value_heads=2, head_dim=128, num_hidden_layers=2,
             sliding_window=window, vocab_size=512)
    c["assumed"] = dict(c["assumed"], param_dtype="float32")
    return c


def _weights(c, seed=3):
    """Seeded weights with nonzero biases and norm offsets, so that every
    bias and gain term changes the loss."""
    w = ref.init_weights(c, ref.seed_key(seed), jnp.float32)
    key = jax.random.PRNGKey(seed + 1)
    return {k: (v + 0.05 * jax.random.normal(jax.random.fold_in(key, i),
                                             v.shape)
                if v.ndim == 1 else v)
            for i, (k, v) in enumerate(w.items())}


def _tokens(seed=1, b=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, SEQ), 0, 512)


def _program(c, w, tokens, **kw):
    cfg = adapter.program_config(c)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(cfg, p, {"tokens": tokens}, **kw)[0]))(
            adapter.to_tree(c, w))
    return float(loss), adapter.named(c, grads)


def _reference(c, w, tokens):
    """(loss, gradients by name) of the reference."""
    loss, grads = jax.jit(jax.value_and_grad(
        lambda s: ref.loss(c, s, tokens)))(ref.stack(c, w))
    out = dict(grads["top"])
    for q, v in grads["layers"].items():
        out.update({f"layers.{i}.{q}": x for i, x in enumerate(v)})
    return float(loss), out


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


TRAIN = dict(impl="splash", remat=True, loss_chunk=64)


def test_loss_and_gradients_match_the_reference():
    """Every leaf, biases and norm biases included. Tolerance: f32 on
    both sides, the reference at HIGHEST precision and the program at
    the CPU's f32 default; the kernel's online softmax and the chunked
    sums differ from the reference's in summation order only (readings:
    loss 0, worst leaf gradient 3.0e-6 relative), so 1e-5 and 1e-4."""
    c = small()
    w, tok = _weights(c), _tokens()
    lp, gp = _program(c, w, tok, **TRAIN)
    lr, gr = _reference(c, w, tok)
    assert lp == pytest.approx(lr, rel=1e-5)
    assert set(gp) == set(gr)
    assert any(".attn.b" in k for k in gp) and any(".mlp.b" in k for k in gp)
    worst = max(_rel(gp[k], gr[k]) for k in gr)
    assert worst < 1e-4, worst


def test_the_window_binds():
    """At 256 tokens a window of 64 leaves out most keys. With random
    weights the loss hardly notices (1.3e-3 relative), the gradients do:
    every attention leaf's gradient of the reference without a window
    lies 0.32-1.17 (relative) from the windowed one's, the program's
    within 3e-6 of the windowed one. So the program must follow the
    windowed reference (1e-4, as above) and be at least 0.1 from the
    other, on every attention leaf."""
    w, tok = _weights(small()), _tokens()
    _, gp = _program(small(), w, tok, **TRAIN)
    _, g_win = _reference(small(), w, tok)
    _, g_all = _reference(small(window=None), w, tok)
    attn = [k for k in g_win if ".attn." in k]
    assert max(_rel(gp[k], g_win[k]) for k in attn) < 1e-4
    assert min(_rel(gp[k], g_all[k]) for k in attn) > 0.1


def test_remat_changes_nothing():
    """Remat recomputes the same ops in the backward pass: loss and
    gradients equal (readings 0 and 0; 1e-6 lets a recomputed fusion
    round once differently)."""
    c = small()
    w, tok = _weights(c), _tokens()
    l_on, g_on = _program(c, w, tok, impl="splash", remat=True)
    l_off, g_off = _program(c, w, tok, impl="splash", remat=False)
    assert l_on == pytest.approx(l_off, rel=1e-6)
    assert max(_rel(g_on[k], g_off[k]) for k in g_off) < 1e-6


@pytest.mark.parametrize("chunk", [32, 128], ids=["chunk32", "chunk128"])
def test_chunked_loss_equals_the_whole_loss(chunk):
    """Value and gradient; also with a label masked out. Tolerance: the
    chunked sum adds chunk sums instead of one sum over the positions
    (f32 order; readings: loss 0, gradients at most 4.1e-7 relative):
    1e-6 on the loss, 1e-5 on the gradients."""
    c = small()
    cfg = adapter.program_config(c)
    tree = adapter.to_tree(c, _weights(c))
    tok = _tokens()
    labels = jnp.pad(tok[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
    labels = labels.at[0, 7].set(-1)
    batch = {"tokens": tok, "labels": labels}

    def f(p, n):
        return lm_loss(cfg, p, batch, loss_chunk=n)[0]
    lw, gw = jax.jit(jax.value_and_grad(lambda p: f(p, 0)))(tree)
    lc, gc = jax.jit(jax.value_and_grad(lambda p: f(p, chunk)))(tree)
    assert float(lc) == pytest.approx(float(lw), rel=1e-6)
    worst = max(jax.tree.leaves(jax.tree.map(_rel, gc, gw)))
    assert worst < 1e-5, worst


def test_the_training_path_runs_the_kernel():
    """impl="splash" puts a pallas_call in the loss; the default XLA path
    (the twin, and smollm's path) has none."""
    c = small()
    cfg = adapter.program_config(c)
    tree = adapter.to_tree(c, _weights(c))
    batch = {"tokens": _tokens()}

    def jaxpr(**kw):
        return str(jax.make_jaxpr(jax.grad(
            lambda p: lm_loss(cfg, p, batch, **kw)[0]))(tree))
    assert "pallas_call" in jaxpr(**TRAIN)
    assert "pallas_call" not in jaxpr()


@pytest.mark.parametrize("seq,window,block", [
    (512, 64, 128), (256, 0, 128), (256, 100, 256)],
    ids=["window64-skips-blocks", "causal", "window100-one-block"])
def test_window_attention_matches_its_xla_twin(seq, window, block):
    """Forward and backward (a vjp with a random cotangent) of the
    Pallas kernel, in interpret mode, against ``_sdpa_xla`` with the
    same causal window. At 512 tokens, a window of 64 and blocks of 128
    each query block needs 2 of the 4 key blocks, so the kernel's
    skipping is exercised. Tolerance: f32 inputs, online softmax against
    a whole one (readings at most 7.0e-7 relative): 1e-5."""
    b, h, hkv, hd = 2, 4, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(seq + window), 4)
    q = jax.random.normal(ks[0], (b, seq, h, hd))
    k = jax.random.normal(ks[1], (b, seq, hkv, hd))
    v = jax.random.normal(ks[2], (b, seq, hkv, hd))
    ct = jax.random.normal(ks[3], (b, seq, h, hd))
    scale = 1.0 / np.sqrt(hd)
    mask = make_mask(seq, seq, causal=True, window=window)[None, None]

    def kern(q, k, v):
        return window_attention(q, k, v, window=window, scale=scale,
                                block=block, interpret=True)

    def twin(q, k, v):
        return _sdpa_xla(q, k, v, mask, scale)
    out_k, vjp_k = jax.vjp(kern, q, k, v)
    out_t, vjp_t = jax.vjp(twin, q, k, v)
    assert _rel(out_k, out_t) < 1e-5
    for gk, gt in zip(vjp_k(ct), vjp_t(ct)):
        assert _rel(gk, gt) < 1e-5


def test_first_phase_of_the_engine_matches_the_reference(monkeypatch):
    """``PhaseEngine.run`` on the leaf carry (as on one TPU), two
    workers, K=2 steps and the mean at the second, against
    ``train_phase`` on the numbers ``bench/check.py`` compares.
    Tolerance: float32 parameters and momentum on both sides, so the
    gaps are f32 roundoff (readings 1.3e-7 to 4.4e-7); 1e-4 is still
    6x tighter than the bf16 cells' tightest limit. The reference
    without its window reads a velocity gap of 0.32 here."""
    c = small()
    traffic = {"workers": 2, "batch": 1, "seq": SEQ, "phase_len": 2,
               "schedule": "periodic", "optimizer": "momentum",
               "lr": 0.01, "momentum": 0.9, "noise": 0.1}
    seed = 2 ** 33 + 5
    # the carry one TPU takes, here on the CPU
    monkeypatch.setattr(engine_mod, "carry_for", lambda *a: "leaf")
    engine = adapter.make_engine(c, traffic)
    feed = Feed(TokenBlocks(traffic, 512, seed))
    params = adapter.make_params(c, seed)
    _, hist, state = engine.run(
        params, feed, num_workers=2, seed=seed % (2 ** 31), phase_len=2,
        steps=2, record_every=1, return_state=True)
    assert engine.carry(state) == "leaf"
    prog = {"loss": [v for _, v in hist["loss"]],
            "dispersion": [v for _, v in hist["disp_trace"]]}
    prog["change"], prog["velocity"] = adapter.state_norms(c, state, seed)
    base = ref.train_phase(c, traffic, seed, feed.first_block)
    read = check.readings(prog, base)
    for n in check.NAMES:
        assert read[n] < 1e-4, (n, read)
    # the check sees a wrong model: the reference without its window
    other = ref.train_phase(small(window=None), traffic, seed,
                            feed.first_block)
    assert check.readings(other, base)["velocity_gap"] > 0.1


def test_the_configuration_keeps_every_published_width():
    """Only depth is cut (5 of the published 30 layers); the program's
    full-size StarCoder2 config has the published values too."""
    c = _published()
    for k, v in PUBLISHED.items():
        assert c[k] == v, k
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 30}
    assert c["num_hidden_layers"] == 5
    assert "6 pipeline stages of 5 layers" in c["deployment"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {e["name"]: e for e in json.load(f)["configs"]}[
            "starcoder2-3b-l5"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "bench/configs/starcoder2-3b-l5.json"

    full = get_config("starcoder2-3b")
    prog = adapter.program_config(dict(c, name="starcoder2-3b"))
    for f in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
              "vocab_size", "sliding_window", "rope_theta", "norm",
              "norm_eps", "embed_scale", "linear_bias", "act", "gated_mlp",
              "tie_embeddings"):
        assert getattr(prog, f) == getattr(full, f), f
    assert full.num_layers == 30 and prog.num_layers == 5
    # 3.03B parameters at 30 layers, as published
    assert full.num_params() == pytest.approx(3.03e9, rel=2e-3)
    # the reference's shape carries the dense keys bench/flops.py reads
    s = ref.shape(c)
    assert (s["d"], s["heads"], s["kv_heads"], s["head_dim"], s["ff"],
            s["vocab"], s["window"], s["gated"]) == (
        3072, 24, 2, 128, 12288, 49152, 4096, False)
    assert ref.width(c) == pytest.approx(631e6, rel=2e-3)


def _trace(events, steps=2):
    from types import SimpleNamespace
    return SimpleNamespace(events={0: events}, t0=0, t1=1_000_000_000,
                           steps=steps)


KERNEL_EVENTS = [
    ['%splash_mqa_fwd_residuals.1 = (f32[2,2,512,128]) custom-call(%a), '
     'custom_call_target="tpu_custom_call"', 10_000_000, 100_000_000],
    ['%splash_mqa_dkv_no_residuals.3 = (bf16[2,2,8192,128]) '
     'custom-call(%b), custom_call_target="tpu_custom_call"',
     200_000_000, 150_000_000],
    ['%fusion.12 = bf16[2,8192,3072] fusion(%c), kind=kLoop',
     120_000_000, 50_000_000],
    ['%custom-call.4 = f32[2,8] custom-call(%d), '
     'custom_call_target="tpu_custom_call"', 400_000_000, 5_000_000],
]


def test_attention_readers_find_the_kernel_by_name():
    """``attn_ms`` sums the ``splash_mqa_*`` calls inside the window
    (100 + 150 ms over 2 steps), not other fusions or Mosaic calls;
    ``attn_roofline`` divides the counted attention work of those steps
    by that time and the peak. A trace without the kernel (the parent's)
    reads None."""
    from types import SimpleNamespace

    from bench import flops, spec
    c = _published()
    traffic = {"workers": 2, "batch": 1, "seq": 8192}
    ctx = SimpleNamespace(trace=_trace(KERNEL_EVENTS), shape=ref.shape(c),
                          traffic=traffic, chips=1,
                          peak={"bf16_flops_per_s": 197e12})
    assert spec.reader("attn_ms")(ctx) == pytest.approx(125.0)
    work = 5 * 16384 * 12 * 3072 * flops.mean_context(8192, 4096)
    assert work == pytest.approx(9.28e12, rel=1e-3)
    assert spec.reader("attn_roofline")(ctx) == pytest.approx(
        100 * 2 * work / 0.25 / 197e12)
    parent = SimpleNamespace(**{**vars(ctx),
                                "trace": _trace(KERNEL_EVENTS[2:])})
    assert spec.reader("attn_ms")(parent) is None
    assert spec.reader("attn_roofline")(parent) is None
