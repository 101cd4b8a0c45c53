"""Model configuration shared by every assigned architecture.

One :class:`ModelConfig` describes dense GQA transformers, MoE, SSM (Mamba-2),
hybrid (RG-LRU + local attention), and the modality-stub families, so the
launcher / dry-run can treat all ten assigned archs uniformly.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.factorized import FactorizationConfig

__all__ = ["MoEConfig", "SSMConfig", "RGLRUConfig", "ModelConfig"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    dense_residual: bool = False  # arctic: parallel dense FFN alongside MoE
    d_ff_dense: int = 0
    capacity_factor: float = 1.25
    router_jitter: float = 0.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 / SSD block (arXiv:2405.21060)."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    """Griffin recurrent block (arXiv:2402.19427)."""

    lru_width: int = 0  # 0 = d_model
    conv_width: int = 4
    c_exponent: float = 8.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio | encoder | encdec
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab_size: int
    n_kv_heads: Optional[int] = None  # None -> MHA
    d_head: Optional[int] = None  # None -> d_model // n_heads
    qkv_bias: bool = False
    # Biases on the attention output and the feed-forward linears; with
    # qkv_bias, on every linear of the block (starcoder2's ``use_bias``).
    linear_bias: bool = False
    act: str = "swiglu"  # swiglu | gelu | geglu
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-6
    rope: bool = True
    rope_theta: float = 1e6
    tie_embeddings: bool = False
    learned_pos: bool = False  # BERT/ViT-style absolute positions
    causal: bool = True
    sliding_window: Optional[int] = None  # starcoder2: 4096
    # Heterogeneous layer pattern (recurrentgemma): tuple of block kinds,
    # cycled over layers. None -> uniform ("attn" or "ssd" etc. by family).
    layer_pattern: Optional[Tuple[str, ...]] = None
    local_window: int = 2048  # window of "local" blocks in the pattern
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    n_codebooks: int = 1  # musicgen: 4 parallel EnCodec codebooks
    external_embeddings: bool = False  # vlm/audio: frontend stub supplies (B,S,d)
    factorization: FactorizationConfig = FactorizationConfig()
    # Weight representation the forward pass consumes: "dense" (dense w /
    # factorized wd leaves) or "compressed" (the T-REX streaming format from
    # core/factorized.py compress_model_params — nibble-packed W_S codes +
    # delta/quantized W_D). apply_linear dispatches per leaf either way; the
    # config field makes the serving mode explicit and validated.
    weight_format: str = "dense"
    dtype: str = "bfloat16"  # compute dtype
    param_dtype: str = "float32"
    remat: str = "nothing_saveable"  # jax.checkpoint policy name, or "none"
    attn_chunk: int = 512  # flash-in-JAX chunk size
    # ---- beyond-paper performance knobs (EXPERIMENTS §Perf) ----
    # Unroll the layer loop for decode: the graphs are tiny and static layer
    # indices let XLA update caches in place (the scanned carry otherwise
    # copies the full stacked cache every layer).
    unroll_decode: bool = False
    # Pin activation shardings (batch on dp, wide feature on model) so GSPMD
    # gathers weights instead of all-reducing big activations.
    constrain_acts: bool = False
    # Dtype of flash-attention probability blocks (stats stay f32).
    flash_block_dtype: str = "float32"
    # int8 KV cache with per-(token, head) scales (KIVI-lite): halves the
    # decode memory wall and the cache footprint on MHA archs.
    kv_quant: bool = False
    # Decode-attention impl: "dense" (jnp masked softmax over the whole
    # cache), "tda" (fused Pallas kernel — per-slot length predication,
    # in-VMEM int8 dequant, online softmax), or "auto" (tda on TPU, dense
    # elsewhere; resolved by repro.kernels.common.resolve_decode_attn).
    decode_attn: str = "dense"
    # KV-block size of the fused decode kernel's predication grid (also the
    # granularity of the blocks-visited accounting in serve/benchmarks).
    decode_block_k: int = 128
    # Causal wedge: static triangle decomposition of the flash loops — visit
    # only ~half the (q, kv) chunk grid instead of masking it (§Perf).
    causal_wedge: bool = False
    # Encoder-decoder extras (paper workloads)
    n_encoder_layers: int = 0
    max_len: int = 131072

    # ---- derived ----
    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def params_dtype(self):
        return jnp.dtype(self.param_dtype)

    def block_kind(self, layer_idx: int) -> str:
        if self.layer_pattern is not None:
            return self.layer_pattern[layer_idx % len(self.layer_pattern)]
        if self.family == "ssm":
            return "ssd"
        return "attn"

    @property
    def uniform_layers(self) -> bool:
        """True when every layer is identical -> scan-over-layers applies."""
        return self.layer_pattern is None or len(set(self.layer_pattern)) == 1

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for the long_500k shape (SSM / hybrid / windowed-only)."""
        return self.family in ("ssm", "hybrid")

    def n_params(self) -> int:
        """Approximate dense parameter count (embeddings + blocks), for 6ND."""
        d, L = self.d_model, self.n_layers
        hd = self.head_dim
        p = self.vocab_size * d * (1 if self.tie_embeddings else 2) * self.n_codebooks
        for i in range(L):
            kind = self.block_kind(i)
            if kind in ("attn", "local"):
                p += d * hd * (self.n_heads + 2 * self.kv_heads) + self.n_heads * hd * d
            elif kind == "ssd":
                s = self.ssm
                d_in = s.expand * d
                conv_ch = d_in + 2 * s.n_groups * s.d_state
                p += d * (2 * d_in + 2 * s.n_groups * s.d_state + d_in // s.head_dim)
                p += conv_ch * s.d_conv + d_in * d
            elif kind == "rglru":
                w = self.rglru.lru_width or d
                p += 2 * d * w + w * self.rglru.conv_width + 2 * w + w * d
            if kind in ("attn", "local"):
                if self.moe is not None:
                    m = self.moe
                    p += d * m.n_experts  # router
                    p += m.n_experts * 3 * d * m.d_ff_expert
                    if m.dense_residual:
                        p += 3 * d * m.d_ff_dense
                else:
                    mults = 3 if self.act in ("swiglu", "geglu") else 2
                    p += mults * d * self.d_ff
            elif kind == "rglru":
                mults = 3 if self.act in ("swiglu", "geglu") else 2
                p += mults * d * self.d_ff
        return p

    def n_active_params(self) -> int:
        """Active params per token (MoE: only top_k experts) for 6*N_active*D."""
        if self.moe is None:
            return self.n_params()
        m = self.moe
        total = self.n_params()
        expert_p = self.n_layers * m.n_experts * 3 * self.d_model * m.d_ff_expert
        active_p = self.n_layers * m.top_k * 3 * self.d_model * m.d_ff_expert
        return total - expert_p + active_p
