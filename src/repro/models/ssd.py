"""Mamba-2 SSD (state-space duality) mixer — mamba2-370m (arXiv:2405.21060).

Chunked-parallel SSD: the sequence is split into chunks of length Q; within a
chunk the quadratic "attention-like" form runs on the MXU, across chunks a
small sequential scan carries the (H, P, N) state. Decode is the O(1)
recurrent step. The in/out projections are factorization-eligible (the bulk of
Mamba's parameters); the SSD state path itself has no weight matrix to
factorize (DESIGN §4, partial applicability).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.factorized import DictionaryBank, apply_linear, init_linear
from repro.models.common import ModelConfig

__all__ = ["init_ssd", "ssd_block", "init_ssd_cache"]


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    return s, d_in, H, conv_ch


def init_ssd(key: jax.Array, cfg: ModelConfig, bank: Optional[DictionaryBank]) -> Dict:
    s, d_in, H, conv_ch = _dims(cfg)
    d = cfg.d_model
    fcfg = cfg.factorization
    ks = jax.random.split(key, 6)
    proj_out = 2 * d_in + 2 * s.n_groups * s.d_state + H  # z, x, B, C, dt
    p = {
        "in_proj": init_linear(ks[0], d, proj_out, fcfg, bank, "ssd_in",
                               dtype=cfg.params_dtype),
        "out_proj": init_linear(ks[1], d_in, d, fcfg, bank, "ssd_out",
                                dtype=cfg.params_dtype),
        "conv_w": (jax.random.normal(ks[2], (conv_ch, s.d_conv),
                                     cfg.params_dtype)
                   / np.sqrt(s.d_conv)).astype(cfg.params_dtype),
        "conv_b": jnp.zeros((conv_ch,), cfg.params_dtype),
        # A_log: decay rates; dt_bias: per-head step bias; D: skip.
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), cfg.params_dtype,
                                            1.0, 16.0)),
        "dt_bias": jnp.log(jnp.exp(jax.random.uniform(
            ks[4], (H,), cfg.params_dtype, s.dt_min, s.dt_max)) - 1.0 + 1e-6),
        "D": jnp.ones((H,), cfg.params_dtype),
        "norm_scale": jnp.ones((d_in,), cfg.params_dtype),
    }
    return p


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv. x (B,S,C), w (C,K). Returns (y, new_state)."""
    K = w.shape[1]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, S+K-1, C)
    # Explicit taps (K is 4): fusion-friendly, no conv primitive needed.
    y = jnp.zeros(x.shape, jnp.float32)
    for i in range(K):
        y = y + xp[:, i : i + x.shape[1]].astype(jnp.float32) * w[:, i]
    y = y + b
    new_state = xp[:, -(K - 1):] if K > 1 else state
    return jax.nn.silu(y).astype(x.dtype), new_state


def _ssd_scan(xh, a_log, Bm, Cm, chunk: int):
    """Chunked SSD. xh (B,S,H,P); a_log (B,S,H) per-step log decay;
    Bm/Cm (B,S,G,N). Returns y (B,S,H,P) and final state (B,H,N,P)."""
    Bsz, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nC = S // Q
    hpg = H // G

    def ch(x):  # (B,S,...) -> (B,nC,Q,...)
        return x.reshape(Bsz, nC, Q, *x.shape[2:])

    x_, a_, B_, C_ = ch(xh), ch(a_log), ch(Bm), ch(Cm)
    a_ = a_.astype(jnp.float32)
    s_cum = jnp.cumsum(a_, axis=2)  # (B,nC,Q,H) inclusive log-decay
    # Intra-chunk "attention": scores[i,j] = (C_i . B_j) * exp(s_i - s_j), i>=j.
    CB = jnp.einsum("bcqgn,bckgn->bcgqk", C_.astype(jnp.float32),
                    B_.astype(jnp.float32))
    CB = jnp.repeat(CB, hpg, axis=2)  # (B,nC,H,Q,Q)
    si = s_cum.transpose(0, 1, 3, 2)  # (B,nC,H,Q): decay[i,j] = exp(s_i - s_j)
    decay = jnp.exp(jnp.clip(si[..., :, None] - si[..., None, :], -60.0, 0.0))
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    scores = jnp.where(mask, CB * decay, 0.0)
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", scores, x_.astype(jnp.float32))

    # Chunk-local end states: sum_j exp(s_Q - s_j) B_j (x) x_j.
    end_decay = jnp.exp(jnp.clip(si[..., -1:] - si, -60.0, 0.0))  # (B,nC,H,Q)
    xw = x_.astype(jnp.float32) * end_decay.transpose(0, 1, 3, 2)[..., None]
    B_heads = jnp.repeat(B_.astype(jnp.float32), hpg, axis=2) \
        if G > 1 else jnp.broadcast_to(
            B_.astype(jnp.float32), (Bsz, nC, Q, H, N))
    states = jnp.einsum("bcqhn,bcqhp->bchnp", B_heads, xw)

    # Inter-chunk recurrence over nC chunks (small sequential scan).
    chunk_decay = jnp.exp(jnp.clip(si[..., -1], -60.0, 0.0))  # (B,nC,H)

    def step(h, inp):
        st, dec = inp  # (B,H,N,P), (B,H)
        h_new = h * dec[..., None, None] + st
        return h_new, h

    h0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    h_last, h_prevs = jax.lax.scan(
        step, h0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(1, 0, 2)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)  # (B,nC,H,N,P) state before chunk

    # Inter-chunk contribution: y_inter[i] = C_i . (exp(s_i) * h_prev).
    C_heads = jnp.repeat(C_.astype(jnp.float32), hpg, axis=2) \
        if G > 1 else jnp.broadcast_to(
            C_.astype(jnp.float32), (Bsz, nC, Q, H, N))
    in_decay = jnp.exp(jnp.clip(si, -60.0, 0.0)).transpose(0, 1, 3, 2)  # (B,nC,Q,H)
    y_inter = jnp.einsum("bcqhn,bchnp->bcqhp", C_heads, h_prevs) \
        * in_decay[..., None]
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y, h_last


def ssd_block(
    p: Dict,
    x: jnp.ndarray,  # (B, S, d)
    *,
    cfg: ModelConfig,
    dicts: Optional[Dict],
    cache: Optional[Dict] = None,
    cache_index: Optional[jnp.ndarray] = None,
    layer_idx: Optional[jnp.ndarray] = None,
    seg_ids: Optional[jnp.ndarray] = None,  # (B, S) int, 0 = padding
    slot_mask: Optional[jnp.ndarray] = None,  # (B,) bool: rows allowed to
    # update their recurrent state (inactive serving slots stay frozen)
    sparse_train: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    s, d_in, H, conv_ch = _dims(cfg)

    def write(buf, upd):
        upd = upd.astype(buf.dtype)
        if layer_idx is not None:
            upd = upd[None]
            starts = (layer_idx,) + (0,) * (buf.ndim - 1)
        else:
            starts = (0,) * buf.ndim
        return jax.lax.dynamic_update_slice(buf, upd, starts)

    def view(buf):
        if layer_idx is None:
            return buf
        return jax.lax.dynamic_index_in_dim(buf, layer_idx, 0, keepdims=False)
    fcfg = cfg.factorization
    dt_c = cfg.compute_dtype
    B_, S, _ = x.shape
    G, N, P = s.n_groups, s.d_state, s.head_dim

    zxbcdt = apply_linear(p["in_proj"], x, dicts, "ssd_in", fcfg,
                          sparse_train).astype(dt_c)
    z, xs, Bm, Cm, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + G * N, 2 * d_in + 2 * G * N], axis=-1)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)

    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,) negative
    dt_f = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)

    # Padding positions (seg id 0) are identity steps: zeroed conv input
    # (matching the zero initial conv taps of an unpadded run) and dt = 0,
    # which makes the SSD recurrence decay exp(dt*A) = 1 with zero input
    # contribution. A right-aligned padded row therefore ends in exactly the
    # state a solo unpadded forward would produce, so the serving engine can
    # gather end-of-row states into slot lanes (serve/kv_slots.py).
    if seg_ids is not None and S > 1:
        seg_mask = (seg_ids > 0)
        conv_in = jnp.where(seg_mask[..., None], conv_in, 0)
        dt_f = jnp.where(seg_mask[..., None], dt_f, 0.0)

    if cache is not None and S == 1:
        # ---- decode: O(1) recurrent update
        conv_state = view(cache["conv"])
        xp = jnp.concatenate([conv_state, conv_in], axis=1)  # (B,K,C)
        y = jnp.einsum("bkc,ck->bc", xp.astype(jnp.float32),
                       p["conv_w"]) + p["conv_b"]
        conv_out = jax.nn.silu(y)[:, None]  # (B,1,C)
        new_conv = xp[:, 1:]
        xs_c, Bm_c, Cm_c = jnp.split(conv_out, [d_in, d_in + G * N], axis=-1)
        xh = xs_c.reshape(B_, H, P).astype(jnp.float32)
        Bv = Bm_c.reshape(B_, G, N).astype(jnp.float32)
        Cv = Cm_c.reshape(B_, G, N).astype(jnp.float32)
        hpg = H // G
        Bh = jnp.repeat(Bv, hpg, axis=1) if G > 1 else jnp.broadcast_to(
            Bv, (B_, H, N))
        Ch = jnp.repeat(Cv, hpg, axis=1) if G > 1 else jnp.broadcast_to(
            Cv, (B_, H, N))
        dts = dt_f[:, 0]  # (B,H)
        decay = jnp.exp(dts * A)  # (B,H)
        h = view(cache["state"])  # (B,H,N,P) f32
        h = h * decay[..., None, None] + jnp.einsum(
            "bhn,bhp->bhnp", Bh, xh * dts[..., None])
        yh = jnp.einsum("bhn,bhnp->bhp", Ch, h)
        yh = yh + p["D"].astype(jnp.float32)[:, None] * xh
        y_out = yh.reshape(B_, 1, d_in)
        if slot_mask is not None:
            live = jnp.reshape(slot_mask, (-1, 1))
            h = jnp.where(live[..., None, None], h, view(cache["state"]))
            new_conv = jnp.where(live[:, None], new_conv, conv_state)
        new_cache = {"state": write(cache["state"], h),
                     "conv": write(cache["conv"], new_conv)}
    else:
        conv_out, conv_state = _causal_conv(conv_in, p["conv_w"], p["conv_b"])
        xs_c, Bm_c, Cm_c = jnp.split(conv_out, [d_in, d_in + G * N], axis=-1)
        xh = xs_c.reshape(B_, S, H, P)
        Bv = Bm_c.reshape(B_, S, G, N)
        Cv = Cm_c.reshape(B_, S, G, N)
        a_log = dt_f * A  # (B,S,H)
        y, h_last = _ssd_scan(xh.astype(jnp.float32) * dt_f[..., None],
                              a_log, Bv, Cv, s.chunk)
        y = y + p["D"].astype(jnp.float32)[:, None] * xh.astype(jnp.float32)
        y_out = y.reshape(B_, S, d_in)
        new_cache = None
        if cache is not None:  # prefill fills the recurrent state
            new_cache = {"state": write(cache["state"], h_last),
                         "conv": write(cache["conv"], conv_state)}

    # Gated RMSNorm (Mamba-2): norm(y * silu(z)).
    g = y_out * jax.nn.silu(z.astype(jnp.float32))
    var = (g * g).mean(-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + 1e-6) * p["norm_scale"]
    out = apply_linear(p["out_proj"], g.astype(dt_c), dicts, "ssd_out", fcfg,
                       sparse_train)
    return out.astype(dt_c), new_cache


def init_ssd_cache(cfg: ModelConfig, batch: int) -> Dict:
    s, d_in, H, conv_ch = _dims(cfg)
    return {
        "state": jnp.zeros((batch, H, s.d_state, s.head_dim), jnp.float32),
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_ch), cfg.compute_dtype),
    }
