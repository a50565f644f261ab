"""The FLOP count by layer groups (``bench.flops``): today's cells count
what they counted as one dense group, an explicit ``layer_work`` built
with the helpers counts the same, and the helpers give the hand counts
of a latent-attention sparse-expert model and of a window/full mix. The
catalog sizes are written here; the test loads no catalog."""
import importlib.util
import os

import pytest

from bench import flops, spec
from bench.flops import attention, dense_ffn, experts, latent_attention, layer


def _shape(workload):
    c = spec.cell(workload)
    _, ref = spec.model(c["config"]["model"])
    return ref.shape(c["config"]), c["traffic"]


@pytest.mark.parametrize("workload,params,per_token", [
    ("smollm360m-m2-s128-k4", 361_758_720, 2_194_329_600),
    ("smollm360m-m8x4c-s128-k4", 361_758_720, 2_194_329_600),
    ("starcoder2l5-m2-s8192-k8", 630_718_464, 4_350_587_904),
])
def test_the_cells_count_as_before(workload, params, per_token):
    s, t = _shape(workload)
    assert "layer_work" not in s
    assert flops.matmul_params(s) == params
    assert flops.train_flops_per_token(s, t["seq"]) == per_token


def test_attn_roofline_work_of_the_starcoder2_cell():
    path = os.path.join(spec.ROOT, "bench", "metrics", "attn_roofline.py")
    sp = importlib.util.spec_from_file_location("attn_roofline_under_test",
                                                path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    s, t = _shape("starcoder2l5-m2-s8192-k8")
    # 5 layers x 2 x 8,192 tokens x 12 x 3,072 x 3,072.25 mean keys
    assert mod.attn_flops_per_step(s, t) == pytest.approx(
        9_277_884_334_080.0, rel=1e-12)


@pytest.mark.parametrize("workload,d,heads,kv_heads,head_dim,ff,gated,window,"
                         "layers", [
    ("smollm360m-m2-s128-k4", 960, 15, 5, 64, 2560, True, 0, 32),
    ("starcoder2l5-m2-s8192-k8", 3072, 24, 2, 128, 12288, False, 4096, 5),
])
def test_explicit_layer_work_counts_as_the_dense_fallback(
        workload, d, heads, kv_heads, head_dim, ff, gated, window, layers):
    s, t = _shape(workload)
    block = layer(attention(d, heads, kv_heads, head_dim, window),
                  dense_ffn(d, ff, gated))
    explicit = dict(s, layer_work=[(layers, block)])
    assert flops.layer_work(explicit) == [(layers, block)]
    assert flops.layer_work(s) == [(layers, block)]
    assert flops.matmul_params(explicit) == flops.matmul_params(s)
    assert (flops.train_flops_per_token(explicit, t["seq"])
            == flops.train_flops_per_token(s, t["seq"]))


# Moonlight-16B-A3B (huggingface.co/moonshotai/Moonlight-16B-A3B): MLA
# with no q latent, kv rank 512, nope 128, rope 64, v 128, 16 heads;
# one dense layer (11,264), then 64 experts of 1,408, top 6, 2 shared.
# Cut to 1 dense + 5 expert layers, 8 of 64 experts held, 20,480 of the
# 163,840 vocabulary.
_MLA = latent_attention(2048, 16, None, 512, 128, 64, 128, 0)
MOONLIGHT_CUT = {"d": 2048, "vocab": 20480, "layer_work": [
    (1, layer(_MLA, dense_ffn(2048, 11264, True))),
    (5, layer(_MLA, experts(2048, 1408, 64, 6, 8, 2 * 1408, True))),
]}

# Mellum2-12B-A2.5B-Instruct (huggingface.co/JetBrains/Mellum2-12B-A2.5B-
# Instruct): GQA 32/4, head_dim 128, 64 experts of 896, top 8, none
# shared. One period: three layers with window 1,024, one full; 8 of
# 64 experts held, 12,288 of the 98,304 vocabulary.
_MOE = experts(2304, 896, 64, 8, 8, 0, True)
MELLUM2_PERIOD = {"d": 2304, "vocab": 12288, "layer_work": [
    (3, layer(attention(2304, 32, 4, 128, 1024), _MOE)),
    (1, layer(attention(2304, 32, 4, 128, 0), _MOE)),
]}


@pytest.mark.parametrize("s,params,per_token", [
    (MOONLIGHT_CUT, 313_327_616, 2_635_032_576),
    (MELLUM2_PERIOD, 138_608_640, 1_174_569_984),
])
def test_hand_counts_at_8192(s, params, per_token):
    assert flops.matmul_params(s) == params
    assert flops.train_flops_per_token(s, 8192) == per_token


def test_latent_attention_widths():
    # query-key 16 x 192, value 16 x 128: not one width as in GQA
    assert (_MLA["qk"], _MLA["v"]) == (3072, 2048)
    # q 2048 x 3072; kv 2048 x 576 + 512 x 16 x 256; o 2048 x 2048
    assert _MLA["weights"] == 6_291_456 + 3_276_800 + 4_194_304
    with_q_rank = latent_attention(2048, 16, 1536, 512, 128, 64, 128, 0)
    assert (with_q_rank["weights"] - _MLA["weights"]
            == 2048 * 1536 + 1536 * 3072 - 2048 * 3072)


def test_mean_context_of_a_window_and_full_mix():
    assert flops.mean_context(8192, 1024) == 960.0625
    assert flops.mean_context(8192, 0) == 4096.5


@pytest.mark.parametrize("held", [0, 1, 8, 32, 64])
def test_routed_experts_linear_in_held(held):
    d, ff, n, k = 2048, 1408, 64, 6
    none_held = experts(d, ff, n, k, 0, 2 * ff, True)
    one = experts(d, ff, n, k, 1, 2 * ff, True) - none_held
    assert none_held == d * n + 3 * d * 2 * ff
    assert experts(d, ff, n, k, held, 2 * ff, True) - none_held == held * one
    # every expert held: top_k whole experts a token
    assert experts(d, ff, n, k, n, 0, False) == d * n + k * 2 * d * ff
