"""The paper's four evaluation workloads [25-28] as runnable models.

- ``bert``  [28]: bidirectional encoder (BERT family) — MLM/classification.
- ``vit``   [25]: encoder over patch embeddings (frontend stub projects
  flattened patches), class token readout.
- ``mt``    [26]: encoder-decoder transformer (R-Drop's base MT setup).
- ``s2t``   [27]: fairseq-S2T-style encoder-decoder over fbank frames
  (conv-subsample frontend stubbed as a linear projection).

Encoders reuse the main ``Model`` with ``causal=False``; the encoder-decoder
adds cross-attention through the same ``attention_block`` (kv= path). All
linears are factorization-eligible with per-side dictionaries (enc/dec x
attn/ffn), matching the paper's "separate W_S per encoder/decoder and
attention/FFN" rule. Sizes follow core/ema.py's calibrated workload specs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.factorized import DictionaryBank, FactorizationConfig
from repro.models import layers as L
from repro.models.common import ModelConfig
from repro.models.transformer import Model

__all__ = ["paper_model_config", "EncDecModel", "build_paper_model"]


def paper_model_config(name: str, factorized: bool = True) -> ModelConfig:
    f = FactorizationConfig(enabled=factorized, min_dim=128)
    if name == "bert":
        return ModelConfig(
            name="trex-bert", family="encoder", n_layers=12, d_model=768,
            n_heads=12, d_ff=3072, vocab_size=30522, act="gelu",
            norm="layernorm", rope=False, learned_pos=True, causal=False,
            max_len=512, factorization=f, remat="none", attn_chunk=128)
    if name == "vit":
        return ModelConfig(
            name="trex-vit", family="encoder", n_layers=12, d_model=384,
            n_heads=6, d_ff=1536, vocab_size=1000, act="gelu",
            norm="layernorm", rope=False, learned_pos=True, causal=False,
            external_embeddings=True, max_len=512, factorization=f,
            remat="none", attn_chunk=128)
    if name == "mt":
        return ModelConfig(
            name="trex-mt", family="encdec", n_layers=6, n_encoder_layers=6,
            d_model=512, n_heads=8, d_ff=2048, vocab_size=32000, act="gelu",
            norm="layernorm", rope=False, learned_pos=True, causal=True,
            max_len=512, factorization=f, remat="none", attn_chunk=128)
    if name == "s2t":
        return ModelConfig(
            name="trex-s2t", family="encdec", n_layers=6, n_encoder_layers=12,
            d_model=256, n_heads=4, d_ff=2048, vocab_size=10000, act="gelu",
            norm="layernorm", rope=False, learned_pos=True, causal=True,
            external_embeddings=True,  # fbank frontend stub
            max_len=1024, factorization=f, remat="none", attn_chunk=128)
    raise ValueError(name)


class EncDecModel:
    """Compact encoder-decoder (MT / S2T). Python-loop layers (<= 12+6)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def init(self, key: jax.Array) -> Dict:
        cfg = self.cfg
        bank = DictionaryBank(cfg.factorization, cfg.params_dtype) \
            if cfg.factorization.enabled else None
        keys = jax.random.split(key, 8)
        p: Dict = {"embed": L.init_embedding(keys[0], cfg),
                   "dec_embed": {"tok": jax.random.normal(
                       keys[1], (cfg.vocab_size, cfg.d_model),
                       cfg.params_dtype) * 0.02},
                   "lm_head": L.init_lm_head(keys[2], cfg)}
        if cfg.external_embeddings:  # S2T: fbank(80) -> d stub projection
            p["frontend"] = {"w": jax.random.normal(
                keys[3], (80, cfg.d_model), cfg.params_dtype) / 9.0}
        ek = jax.random.split(keys[4], cfg.n_encoder_layers)
        dk = jax.random.split(keys[5], cfg.n_layers)
        p["encoder"] = {}
        for i in range(cfg.n_encoder_layers):
            ks = jax.random.split(ek[i], 2)
            p["encoder"][f"layer_{i:02d}"] = {
                "norm1": L.init_norm(cfg), "norm2": L.init_norm(cfg),
                "attn": L.init_attention(ks[0], cfg, bank, prefix="enc_attn"),
                "ffn": L.init_ffn(ks[1], cfg, bank, prefix="enc_ffn"),
            }
        p["decoder"] = {}
        for i in range(cfg.n_layers):
            ks = jax.random.split(dk[i], 3)
            p["decoder"][f"layer_{i:02d}"] = {
                "norm1": L.init_norm(cfg), "norm2": L.init_norm(cfg),
                "norm3": L.init_norm(cfg),
                "attn": L.init_attention(ks[0], cfg, bank, prefix="dec_attn"),
                "xattn": L.init_attention(ks[1], cfg, bank, prefix="dec_xattn"),
                "ffn": L.init_ffn(ks[2], cfg, bank, prefix="dec_ffn"),
            }
        p["enc_norm"] = L.init_norm(cfg)
        p["dec_norm"] = L.init_norm(cfg)
        if bank is not None:
            p["dicts"] = bank.dicts
        return p

    def encode(self, p: Dict, batch: Dict, sparse_train=False) -> jnp.ndarray:
        cfg = self.cfg
        dicts = p.get("dicts")
        if cfg.external_embeddings:
            x = (batch["src_feats"].astype(cfg.compute_dtype)
                 @ p["frontend"]["w"].astype(cfg.compute_dtype))
        else:
            x = L.embed_tokens(p["embed"], batch["src"], cfg,
                               positions=batch.get("src_positions"))
        if cfg.learned_pos and "pos" in p["embed"]:
            Spos = x.shape[1]
            x = x + p["embed"]["pos"][None, :Spos].astype(x.dtype)
        seg = batch.get("src_seg")
        B, Ssrc = x.shape[0], x.shape[1]
        pos = jnp.broadcast_to(jnp.arange(Ssrc, dtype=jnp.int32), (B, Ssrc))
        old_causal = cfg.causal
        enc_cfg = dataclasses.replace(cfg, causal=False)
        for i in range(cfg.n_encoder_layers):
            lp = p["encoder"][f"layer_{i:02d}"]
            h = L.apply_norm(lp["norm1"], x, cfg.norm_eps)
            a, _ = L.attention_block(lp["attn"], h, cfg=enc_cfg, dicts=dicts,
                                     positions=pos, seg_ids=seg,
                                     sparse_train=sparse_train,
                                     prefix="enc_attn")
            x = x + a
            x = x + L.ffn_block(lp["ffn"],
                                L.apply_norm(lp["norm2"], x, cfg.norm_eps),
                                cfg=cfg, dicts=dicts,
                                sparse_train=sparse_train, prefix="enc_ffn")
        return L.apply_norm(p["enc_norm"], x, cfg.norm_eps)

    def decode(self, p: Dict, memory: jnp.ndarray, batch: Dict,
               sparse_train=False) -> jnp.ndarray:
        cfg = self.cfg
        dicts = p.get("dicts")
        tgt = batch["tgt"]
        B, St = tgt.shape
        x = jnp.take(p["dec_embed"]["tok"], tgt, axis=0).astype(
            cfg.compute_dtype)
        pos = jnp.broadcast_to(jnp.arange(St, dtype=jnp.int32), (B, St))
        seg_kv = batch.get("src_seg")
        for i in range(cfg.n_layers):
            lp = p["decoder"][f"layer_{i:02d}"]
            h = L.apply_norm(lp["norm1"], x, cfg.norm_eps)
            a, _ = L.attention_block(lp["attn"], h, cfg=cfg, dicts=dicts,
                                     positions=pos, seg_ids=None,
                                     sparse_train=sparse_train,
                                     prefix="dec_attn")
            x = x + a
            h = L.apply_norm(lp["norm2"], x, cfg.norm_eps)
            a, _ = L.attention_block(lp["xattn"], h, cfg=cfg, dicts=dicts,
                                     positions=pos, seg_ids=None,
                                     kv=memory, seg_kv=seg_kv,
                                     sparse_train=sparse_train,
                                     prefix="dec_xattn")
            x = x + a
            x = x + L.ffn_block(lp["ffn"],
                                L.apply_norm(lp["norm3"], x, cfg.norm_eps),
                                cfg=cfg, dicts=dicts,
                                sparse_train=sparse_train, prefix="dec_ffn")
        x = L.apply_norm(p["dec_norm"], x, cfg.norm_eps)
        return x.astype(jnp.float32) @ p["lm_head"]["w"].astype(jnp.float32)

    def loss(self, p: Dict, batch: Dict, sparse_train=False
             ) -> Tuple[jnp.ndarray, Dict]:
        memory = self.encode(p, batch, sparse_train)
        logits = self.decode(p, memory, batch, sparse_train)
        xe = L.cross_entropy(logits, batch["labels"], batch.get("weights"))
        return xe, {"xent": xe, "loss": xe}


def build_paper_model(name: str, factorized: bool = True):
    """Returns (model, cfg) — Model for encoders, EncDecModel for enc-dec."""
    cfg = paper_model_config(name, factorized)
    if cfg.family == "encdec":
        return EncDecModel(cfg), cfg
    return Model(cfg), cfg
