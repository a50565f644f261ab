"""Config system: model/shape dataclasses + arch registry.

Every assigned architecture has one file in this package exporting
``make_config() -> ModelConfig`` (full size, citation in the docstring)
and ``make_reduced() -> ModelConfig`` (2 layers, d_model<=512, <=4
experts) for CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, replace


# --------------------------------------------------------------------------
# Layer / model configs
# --------------------------------------------------------------------------

MIXERS = ("attn", "attn_local", "rglru", "rwkv", "none")
FFNS = ("dense", "moe", "rwkv_cmix", "none")


@dataclass(frozen=True)
class LayerSpec:
    """One transformer block: a sequence mixer + an FFN.

    mixer:      attn | attn_local | rglru | rwkv | none
    ffn:        dense | moe | rwkv_cmix | none
    cross_attn: insert a cross-attention sublayer (VLM / whisper decoder)
    causal:     causal mask for attention mixers (False for encoders)
    """

    mixer: str = "attn"
    ffn: str = "dense"
    cross_attn: bool = False
    causal: bool = True

    def __post_init__(self):
        assert self.mixer in MIXERS, self.mixer
        assert self.ffn in FFNS, self.ffn


def _pattern(pattern: list[LayerSpec], n: int) -> tuple[LayerSpec, ...]:
    """Repeat ``pattern`` cyclically, truncated to exactly ``n`` layers."""
    out = []
    while len(out) < n:
        out.extend(pattern)
    return tuple(out[:n])


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    layers: tuple[LayerSpec, ...] = ()
    # attention
    sliding_window: int = 0          # window for attn_local mixers
    rope_theta: float = 10_000.0
    pos_emb: str = "rope"            # rope | learned | none
    max_seq_len: int = 1 << 20       # cap for learned positions
    logit_softcap: float = 0.0
    # moe
    num_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    shared_expert: bool = False
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_group_size: int = 0          # 0 = global capacity (naive GShard);
                                     # >0 = per-group dispatch (§Perf)
    # recurrent (RG-LRU)
    rnn_width: int = 0
    conv_width: int = 4
    # rwkv
    rwkv_head_dim: int = 64
    # enc-dec / modality frontends (stubbed per the audio/vlm carve-out)
    encoder_layers: int = 0
    encoder_seq: int = 0             # whisper: 1500 frames
    num_media_tokens: int = 0        # vlm: image-patch token count
    # perf variants (EXPERIMENTS.md §Perf; defaults = paper-faithful baseline)
    attn_banded: bool = False        # banded sliding-window attention
    score_dtype: str = "float32"     # attention score traffic dtype
    # misc
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    norm_eps: float = 1e-6           # added to the variance in every norm
    embed_scale: bool = True         # scale token embeddings by sqrt(d_model)
    linear_bias: bool = False        # biases on q/k/v/o and MLP projections
    act: str = "silu"                # silu | gelu | relu2
    gated_mlp: bool = True
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    citation: str = ""

    # ---- derived -----------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded to a multiple of 256 so it shards over 16-way
        model axes and aligns with the MXU lane width (128)."""
        return -(-self.vocab_size // 256) * 256

    def supports_long_decode(self) -> bool:
        """True if every mixer is sub-quadratic at decode time (recurrent
        state, sliding window, or a local:global mix where global layers
        are O(S) per decoded token)."""
        for spec in self.layers:
            if spec.mixer == "attn_local" and self.sliding_window <= 0:
                return False
        return self.encoder_layers == 0 or self.family != "audio"

    def num_params(self) -> int:
        """Analytic parameter count (used for 6ND model FLOPs)."""
        d, hd = self.d_model, self.head_dim
        n = self.padded_vocab * d  # embed
        if not self.tie_embeddings:
            n += self.padded_vocab * d
        for spec in self.layers:
            if spec.mixer in ("attn", "attn_local"):
                n += d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
                if self.linear_bias:
                    n += self.q_dim + 2 * self.kv_dim + d
            elif spec.mixer == "rglru":
                w = self.rnn_width or d
                n += 2 * d * w + w * d + self.conv_width * w + 3 * w
            elif spec.mixer == "rwkv":
                n += 4 * d * d + d * d // 2  # r,k,v,o + decay lora approx
            if spec.cross_attn:
                n += d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
            if spec.ffn == "dense":
                mult = 3 if self.gated_mlp else 2
                n += mult * d * self.d_ff
                if self.linear_bias:
                    n += (mult - 1) * self.d_ff + d
            elif spec.ffn == "moe":
                mult = 3 if self.gated_mlp else 2
                n += self.num_experts * mult * d * self.moe_d_ff
                n += d * self.num_experts  # router
                if self.shared_expert:
                    n += mult * d * self.moe_d_ff
            elif spec.ffn == "rwkv_cmix":
                n += 2 * d * self.d_ff
            n += 2 * d  # norms
        for _ in range(self.encoder_layers):
            n += d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
            mult = 3 if self.gated_mlp else 2
            n += mult * d * self.d_ff + 2 * d
        return n

    def num_active_params(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.num_experts == 0:
            return self.num_params()
        mult = 3 if self.gated_mlp else 2
        moe_layers = sum(1 for s in self.layers if s.ffn == "moe")
        dead = (self.num_experts - self.top_k) * mult * self.d_model * self.moe_d_ff
        return self.num_params() - moe_layers * dead


# --------------------------------------------------------------------------
# Input shapes (assigned)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str        # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

ARCHS = [
    "recurrentgemma-2b",
    "gemma3-27b",
    "starcoder2-3b",
    "smollm-360m",
    "rwkv6-7b",
    "whisper-small",
    "minitron-8b",
    "llama-3.2-vision-90b",
    "phi3.5-moe-42b-a6.6b",
    "llama4-maverick-400b-a17b",
]

_MODULES = {
    "recurrentgemma-2b": "recurrentgemma_2b",
    "gemma3-27b": "gemma3_27b",
    "starcoder2-3b": "starcoder2_3b",
    "smollm-360m": "smollm_360m",
    "rwkv6-7b": "rwkv6_7b",
    "whisper-small": "whisper_small",
    "minitron-8b": "minitron_8b",
    "llama-3.2-vision-90b": "llama32_vision_90b",
    "phi3.5-moe-42b-a6.6b": "phi35_moe_42b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b",
}


def get_config(arch: str, reduced: bool = False) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCHS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.make_reduced() if reduced else mod.make_config()


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def long_context_variant(cfg: ModelConfig) -> ModelConfig:
    """Sliding-window variant used for ``long_500k`` on dense archs
    (see DESIGN.md §4 shape skips). Archs with native sub-quadratic
    mixers are returned unchanged."""
    if all(s.mixer in ("rglru", "rwkv", "attn_local", "none") for s in cfg.layers):
        return cfg
    window = cfg.sliding_window or 8_192
    new_layers = tuple(
        replace(s, mixer="attn_local") if s.mixer == "attn" else s
        for s in cfg.layers
    )
    return replace(cfg, layers=new_layers, sliding_window=window,
                   name=cfg.name + "+swa")


def reduce_config(cfg: ModelConfig, num_layers: int = 2,
                  d_model: int = 256) -> ModelConfig:
    """Generic reduced variant for smoke tests: preserves the layer-type
    flavor of the family while shrinking every dimension."""
    head_dim = 32
    num_heads = max(2, min(4, cfg.num_heads))
    num_kv = 1 if cfg.num_kv_heads < cfg.num_heads else num_heads
    # keep the first layers of the pattern so every mixer kind appears
    kinds = list(dict.fromkeys(s.mixer for s in cfg.layers))
    layers = []
    for i in range(num_layers):
        base = cfg.layers[i % len(cfg.layers)]
        layers.append(base)
    # guarantee every distinct mixer kind shows up at least once
    for j, k in enumerate(kinds[:num_layers]):
        if all(l.mixer != k for l in layers):
            layers[j] = replace(layers[j], mixer=k)
    return replace(
        cfg,
        name=cfg.name + "-reduced",
        num_layers=num_layers,
        d_model=d_model,
        num_heads=num_heads,
        num_kv_heads=num_kv,
        head_dim=head_dim,
        d_ff=2 * d_model,
        vocab_size=512,
        layers=tuple(layers),
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        num_experts=min(cfg.num_experts, 4) if cfg.num_experts else 0,
        top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        moe_d_ff=2 * d_model if cfg.moe_d_ff else 0,
        rnn_width=d_model if cfg.rnn_width else 0,
        rwkv_head_dim=32,
        encoder_layers=min(cfg.encoder_layers, 2),
        encoder_seq=min(cfg.encoder_seq, 32) if cfg.encoder_seq else 0,
        num_media_tokens=min(cfg.num_media_tokens, 16) if cfg.num_media_tokens else 0,
        max_seq_len=4096,
    )
