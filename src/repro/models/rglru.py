"""Griffin RG-LRU recurrent block — recurrentgemma-2b (arXiv:2402.19427).

Block: x -> [GeLU(x W_y)] (gate branch) (*) [x W_x -> causal conv1d -> RG-LRU]
-> W_out. The RG-LRU recurrence

    r_t = sigmoid(x_t W_a + b_a)            (recurrence gate)
    i_t = sigmoid(x_t W_i + b_i)            (input gate)
    a_t = exp(-c * softplus(Lambda) * r_t)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

is a diagonal linear recurrence -> `associative_scan` over the sequence for
training/prefill and an O(1) step for decode. Projections are
factorization-eligible; the tiny gates stay dense (DESIGN §4).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.factorized import DictionaryBank, apply_linear, init_linear
from repro.models.common import ModelConfig

__all__ = ["init_rglru", "rglru_block", "init_rglru_cache"]


def _width(cfg: ModelConfig) -> int:
    return cfg.rglru.lru_width or cfg.d_model


def init_rglru(key: jax.Array, cfg: ModelConfig, bank: Optional[DictionaryBank]) -> Dict:
    d = cfg.d_model
    w = _width(cfg)
    g = cfg.rglru
    fcfg = cfg.factorization
    ks = jax.random.split(key, 6)
    # Lambda init so a ~ uniform(0.9, 0.999) at r=1 (Griffin appendix).
    u = jax.random.uniform(ks[4], (w,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(jnp.exp(-jnp.log(u) / g.c_exponent) - 1.0 + 1e-8)
    return {
        "w_y": init_linear(ks[0], d, w, fcfg, bank, "rglru_y",
                           dtype=cfg.params_dtype),
        "w_x": init_linear(ks[1], d, w, fcfg, bank, "rglru_x",
                           dtype=cfg.params_dtype),
        "w_out": init_linear(ks[2], w, d, fcfg, bank, "rglru_out",
                             dtype=cfg.params_dtype),
        "conv_w": (jax.random.normal(ks[3], (w, g.conv_width),
                                     cfg.params_dtype)
                   / np.sqrt(g.conv_width)).astype(cfg.params_dtype),
        "conv_b": jnp.zeros((w,), cfg.params_dtype),
        "w_a": (jax.random.normal(ks[5], (w, w), cfg.params_dtype)
                / np.sqrt(w)).astype(cfg.params_dtype),
        "b_a": jnp.zeros((w,), cfg.params_dtype),
        "w_i": (jax.random.normal(ks[5], (w, w), cfg.params_dtype)
                / np.sqrt(w)).astype(cfg.params_dtype),
        "b_i": jnp.zeros((w,), cfg.params_dtype),
        "lambda": lam.astype(cfg.params_dtype),
    }


def _rglru_gates(p, u):
    """u: (..., w) conv output. Returns (a, b) of h = a*h_prev + b, float32."""
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(uf @ p["w_a"].astype(jnp.float32) + p["b_a"])
    i = jax.nn.sigmoid(uf @ p["w_i"].astype(jnp.float32) + p["b_i"])
    log_a = -8.0 * jax.nn.softplus(p["lambda"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12, 1.0)) * (i * uf)
    return a, b


def _causal_conv(x, w, b, state=None):
    K = w.shape[1]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = jnp.zeros(x.shape, jnp.float32)
    for i in range(K):
        y = y + xp[:, i : i + x.shape[1]].astype(jnp.float32) * w[:, i]
    return (y + b).astype(x.dtype), xp[:, -(K - 1):]


def rglru_block(
    p: Dict,
    x: jnp.ndarray,  # (B, S, d)
    *,
    cfg: ModelConfig,
    dicts: Optional[Dict],
    cache: Optional[Dict] = None,
    seg_ids: Optional[jnp.ndarray] = None,  # (B, S) int, 0 = padding
    slot_mask: Optional[jnp.ndarray] = None,  # (B,) bool: rows allowed to
    # update their recurrent state (inactive serving slots stay frozen)
    sparse_train: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    fcfg = cfg.factorization
    dt = cfg.compute_dtype
    B, S, _ = x.shape

    y_gate = jax.nn.gelu(
        apply_linear(p["w_y"], x, dicts, "rglru_y", fcfg, sparse_train)
        .astype(jnp.float32))
    u = apply_linear(p["w_x"], x, dicts, "rglru_x", fcfg, sparse_train).astype(dt)

    # Padding positions (seg id 0) are identity steps: their conv input is
    # zeroed (matching the zero initial conv state of an unpadded run) and
    # their recurrence update is (a, b) = (1, 0), so a right-aligned padded
    # row ends in exactly the state a solo unpadded forward would produce —
    # this is what lets the serving engine gather end-of-row states into
    # slot lanes (serve/kv_slots.py).
    seg_mask = None
    if seg_ids is not None and S > 1:
        seg_mask = (seg_ids > 0)[..., None]  # (B, S, 1)
        u = jnp.where(seg_mask, u, 0)

    if cache is not None and S == 1:
        conv_out, new_conv = _causal_conv(u, p["conv_w"], p["conv_b"],
                                          cache["conv"])
        a, b = _rglru_gates(p, conv_out[:, 0])
        h = a * cache["h"] + b  # (B, w)
        if slot_mask is not None:
            live = jnp.reshape(slot_mask, (-1, 1))
            h = jnp.where(live, h, cache["h"])
            new_conv = jnp.where(live[:, None], new_conv, cache["conv"])
        new_cache = {"h": h, "conv": new_conv}
        ht = h[:, None]
    else:
        conv_out, conv_state = _causal_conv(u, p["conv_w"], p["conv_b"])
        a, b = _rglru_gates(p, conv_out)  # (B,S,w)
        if seg_mask is not None:
            a = jnp.where(seg_mask, a, 1.0)
            b = jnp.where(seg_mask, b, 0.0)

        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, a2 * b1 + b2

        aa, bb = jax.lax.associative_scan(combine, (a, b), axis=1)
        ht = bb  # h_t with h_0 = 0
        new_cache = None
        if cache is not None:
            new_cache = {"h": ht[:, -1], "conv": conv_state}

    out = apply_linear(p["w_out"], (ht * y_gate).astype(dt), dicts,
                       "rglru_out", fcfg, sparse_train)
    return out.astype(dt), new_cache


def init_rglru_cache(cfg: ModelConfig, batch: int) -> Dict:
    w = _width(cfg)
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, cfg.rglru.conv_width - 1, w),
                          cfg.compute_dtype),
    }
