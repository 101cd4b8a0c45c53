"""Shared neural layers: norms, RoPE, chunked flash attention (pure JAX),
GQA attention blocks with KV caches, FFNs — all factorization-aware.

Design notes
------------
* Pure functions over parameter pytrees (no module framework is installed).
* Attention is a chunked, numerically-stable online-softmax ("flash in JAX"):
  an outer `lax.scan` over query chunks and an inner `lax.scan` /
  `fori`-free windowed gather over key-value chunks, so the S x S score
  matrix is never materialized — required for the 32k prefill shapes to fit.
* Sliding-window ("local") attention only visits the ceil(W/chunk)+1
  kv-chunks a query chunk can see — O(S*W) flops, static trip counts (the
  roofline analyzer multiplies loop bodies by trip count, so static structure
  keeps the accounting exact).
* Packed sequences (dynamic batching, the paper's technique) thread
  ``seg_ids`` through every mask.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.factorized import (
    DictionaryBank,
    FactorizationConfig,
    apply_linear,
    init_linear,
)
from repro.kernels.common import resolve_decode_attn
from repro.kernels.tda.ops import (
    fused_decode_attention,
    fused_mixed_attention,
    gather_paged_lanes,
    write_paged_kv,
)
from repro.kernels.tda.sharded import per_rank, sharded_decode_attention
from repro.launch.mesh import (data_parallel_axes, data_parallel_size,
                               tensor_parallel_size)
from repro.models.common import ModelConfig

NEG_INF = -1e30

# Name scopes of a paged serving step's device work, carried into the
# compiled program's op metadata (``op_name``): the write of the step's
# K/V into the page pool, and the attention call over it.
KV_WRITE_SCOPE = "kv_write"
ATTN_SCOPE = "attn_kernel"
# On a tensor-parallel mesh only: the row-parallel projections (attention
# output, feed-forward down), whose partial sums the partitioner joins by
# an all-reduce across ranks.
TP_REDUCE_SCOPE = "tp_reduce"


def _row_parallel(mesh):
    """The name scope of a row-parallel projection: ``tp_reduce`` on a
    tensor-parallel mesh, none on one device."""
    if tensor_parallel_size(mesh) > 1:
        return jax.named_scope(TP_REDUCE_SCOPE)
    return contextlib.nullcontext()


def _pool_head_axis(layer_idx) -> int:
    """The KV-head axis of a head-major page pool, ``(L?, P, Hkv, ...)``."""
    return 1 if layer_idx is None else 2


def constrain_batch(x: jnp.ndarray, mesh,
                    model_dim: Optional[int] = None) -> jnp.ndarray:
    """Pin the batch dim to the data-parallel axes (and optionally one wide
    feature dim to ``model``). GSPMD's propagation can drop the batch
    sharding inside nested scans (observed on the flash loops: full-batch f32
    score blocks on every chip — EXPERIMENTS §Dry-run); an explicit
    constraint at the attention inputs keeps it. The model-dim pin makes
    GSPMD prefer gathering weights over all-reducing big activations
    (§Perf, starcoder2 prefill)."""
    dp = data_parallel_axes(mesh)
    if not dp or x.shape[0] % data_parallel_size(mesh) != 0:
        return x
    spec = [None] * x.ndim
    spec[0] = dp
    if model_dim is not None:
        md = model_dim % x.ndim
        if md != 0 and x.shape[md] % mesh.shape["model"] == 0:
            spec[md] = "model"
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec)))


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, d: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), cfg.params_dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), cfg.params_dtype)
    return p


def apply_norm(p: Dict[str, jnp.ndarray], x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        var = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------


def rope_tables(positions: jnp.ndarray, dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for given integer positions: (..., dim//2)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, D); cos/sin: (B, S, D//2) — rotate pairs (even, odd).
    The pairs are split by a reshape, not by strided indexing: that lowers
    to a gather, which the partitioner meets by gathering a head-sharded
    ``x`` whole on every rank. The barrier keeps XLA from folding the
    reshape into the projection that made ``x``: in a one-token step it
    would relayout the whole weight matrix on every call instead."""
    x = jax.lax.optimization_barrier(x)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# Chunked flash attention (pure JAX)
# --------------------------------------------------------------------------


def _chunk(x: jnp.ndarray, n: int, c: int) -> jnp.ndarray:
    return x.reshape(x.shape[0], n, c, *x.shape[2:])


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, Hq, D)
    k: jnp.ndarray,  # (B, Skv, Hkv, D)
    v: jnp.ndarray,  # (B, Skv, Hkv, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    chunk: int = 512,
    seg_q: Optional[jnp.ndarray] = None,  # (B, Sq) int, 0 = padding
    seg_kv: Optional[jnp.ndarray] = None,
    block_dtype=jnp.float32,  # probability-block dtype (stats stay f32)
    wedge: bool = False,  # static causal-triangle decomposition (§Perf)
) -> jnp.ndarray:
    """Online-softmax attention without materializing (Sq, Skv).

    Full (windowless) attention scans every kv chunk for every q chunk and
    masks — the causal upper triangle is computed-and-masked (a known 2x
    compute waste; see EXPERIMENTS §Perf for the wedge-schedule optimization).
    Windowed attention visits only the kv chunks the window can reach.
    """
    B, Sq0, Hq, D = q.shape
    Skv0, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    c = min(chunk, Sq0, Skv0)

    if seg_q is None:
        seg_q = jnp.ones((B, Sq0), jnp.int32)
    if seg_kv is None:
        seg_kv = jnp.ones((B, Skv0), jnp.int32)

    # Pad to chunk multiples; padding rides segment id 0 => fully masked.
    def pad_s(x, target):
        if x.shape[1] == target:
            return x
        widths = [(0, 0)] * x.ndim
        widths[1] = (0, target - x.shape[1])
        return jnp.pad(x, widths)

    Sq = ((Sq0 + c - 1) // c) * c
    Skv = ((Skv0 + c - 1) // c) * c
    q, k, v = pad_s(q, Sq), pad_s(k, Skv), pad_s(v, Skv)
    seg_q, seg_kv = pad_s(seg_q, Sq), pad_s(seg_kv, Skv)
    nq, nk = Sq // c, Skv // c
    scale = 1.0 / np.sqrt(D)

    qc = _chunk(q, nq, c).reshape(B, nq, c, Hkv, G, D)
    kc = _chunk(k, nk, c)
    vc = _chunk(v, nk, c)
    sq = _chunk(seg_q[..., None], nq, c)[..., 0]  # (B, nq, c)
    sk = _chunk(seg_kv[..., None], nk, c)[..., 0]

    kv_offset = Skv0 - Sq0  # decode-style alignment: q tokens sit at the end

    def score_block(qi, ki, q_blk, k_blk, sq_blk, sk_blk):
        # q_blk: (B, c, Hkv, G, D); k_blk: (B, c, Hkv, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        iq = qi * c + jax.lax.iota(jnp.int32, c) + kv_offset
        ik = ki * c + jax.lax.iota(jnp.int32, c)
        m = (sq_blk[:, :, None] == sk_blk[:, None, :]) & (sq_blk[:, :, None] > 0)
        if causal:
            m &= iq[:, None] >= ik[None, :]
        if window is not None:
            m &= (iq[:, None] - ik[None, :]) < window
        return jnp.where(m[:, None, None], s, NEG_INF)

    def kv_step(carry, ki_and_blk):
        o, m, l, qi, q_blk, sq_blk = carry
        ki, k_blk, v_blk, sk_blk = ki_and_blk
        s = score_block(qi, ki, q_blk, k_blk, sq_blk, sk_blk)  # (B,Hkv,G,c,c)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        # Probability block in reduced precision: halves the dominant flash
        # HBM traffic; the online-softmax stats (m, l) and the o accumulator
        # stay f32 (§Perf cell A).
        o = o * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(block_dtype), v_blk,
            preferred_element_type=jnp.float32)
        return (o, m_new, l, qi, q_blk, sq_blk), None

    w_chunks = None if window is None else (window + c - 1) // c  # lookback

    def q_step(_, inputs):
        qi, q_blk, sq_blk = inputs
        o0 = jnp.zeros((B, Hkv, G, c, D), jnp.float32)
        m0 = jnp.full((B, Hkv, G, c), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, c), jnp.float32)
        carry = (o0, m0, l0, qi, q_blk, sq_blk)
        if window is None:
            xs = (jnp.arange(nk), kc.transpose(1, 0, 2, 3, 4),
                  vc.transpose(1, 0, 2, 3, 4), sk.transpose(1, 0, 2))
            carry, _ = jax.lax.scan(kv_step, carry, xs)
        else:
            # Only the w_chunks+1 reachable kv chunks; indices may underflow 0
            # and are masked via a sentinel segment (0 never matches seg>=1).
            q_kv_idx = qi + (kv_offset // c)
            for t in range(w_chunks + 1):
                ki = q_kv_idx - w_chunks + t
                ki_c = jnp.clip(ki, 0, nk - 1)
                k_blk = jax.lax.dynamic_index_in_dim(
                    kc, ki_c, axis=1, keepdims=False)
                v_blk = jax.lax.dynamic_index_in_dim(
                    vc, ki_c, axis=1, keepdims=False)
                sk_blk = jax.lax.dynamic_index_in_dim(
                    sk, ki_c, axis=1, keepdims=False)
                sk_blk = jnp.where(ki < 0, 0, sk_blk)
                carry, _ = kv_step(carry, (ki_c, k_blk, v_blk, sk_blk))
        o, m, l, *_ = carry
        o = o / jnp.maximum(l[..., None], 1e-30)
        return None, o  # (B, Hkv, G, c, D)

    if wedge and causal and window is None and Sq == Skv and nq > 2:
        # ---- causal wedge: recursive static triangle decomposition.
        # causal(n) = causal(n/2 upper-left) + FULL rectangle (lower-left)
        #           + causal(n/2 lower-right); leaves (<=2 chunks) stay
        # masked. Visits ~(1/2 + 1/nq) of the chunk grid instead of all of
        # it: ~2x fewer attention FLOPs and block traffic at 4k (§Perf).
        def tasks(lo, hi):
            n = hi - lo
            if n <= 2:
                return [(lo, hi, lo, hi)]
            h = n // 2
            return (tasks(lo, lo + h)
                    + [(lo + h, hi, lo, lo + h)]  # full rectangle
                    + tasks(lo + h, hi))

        def run_range(qi, klo, khi):
            """(o, m, l) for q chunk qi over kv chunks [klo, khi)."""
            q_blk = qc[:, qi]
            sq_blk = sq[:, qi]
            o0 = jnp.zeros((B, Hkv, G, c, D), jnp.float32)
            m0 = jnp.full((B, Hkv, G, c), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, Hkv, G, c), jnp.float32)
            carry = (o0, m0, l0, qi, q_blk, sq_blk)
            xs = (jnp.arange(klo, khi),
                  kc[:, klo:khi].transpose(1, 0, 2, 3, 4),
                  vc[:, klo:khi].transpose(1, 0, 2, 3, 4),
                  sk[:, klo:khi].transpose(1, 0, 2))
            carry, _ = jax.lax.scan(kv_step, carry, xs)
            return carry[0], carry[1], carry[2]

        run_range = jax.checkpoint(
            run_range, policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(0, 1, 2))
        o_parts = [None] * nq  # (o, m, l) accumulated per q chunk
        for (qlo, qhi, klo, khi) in tasks(0, nq):
            for qi in range(qlo, qhi):
                o2, m2, l2 = run_range(qi, klo, khi)
                if o_parts[qi] is None:
                    o_parts[qi] = (o2, m2, l2)
                else:  # online-softmax merge of two kv ranges
                    o1, m1, l1 = o_parts[qi]
                    m = jnp.maximum(m1, m2)
                    a1 = jnp.exp(m1 - m)
                    a2 = jnp.exp(m2 - m)
                    o_parts[qi] = (o1 * a1[..., None] + o2 * a2[..., None],
                                   m, l1 * a1 + l2 * a2)
        outs = []
        for qi in range(nq):
            o, m, l = o_parts[qi]
            outs.append(o / jnp.maximum(l[..., None], 1e-30))
        outs = jnp.stack(outs)  # (nq, B, Hkv, G, c, D)
        out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, Hq, D)
        return out[:, :Sq0].astype(q.dtype)

    # Flash backward = recompute: without this checkpoint the nested scans
    # save every block's scores/probs as residuals (O(S^2) memory, hundreds
    # of GB/chip at 4k x 256 — see EXPERIMENTS §Dry-run).
    q_step = jax.checkpoint(
        q_step, policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = jax.lax.scan(
        q_step, None,
        (jnp.arange(nq), qc.transpose(1, 0, 2, 3, 4, 5), sq.transpose(1, 0, 2)),
    )
    # outs: (nq, B, Hkv, G, c, D) -> (B, Sq, Hq, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, Hq, D)
    return out[:, :Sq0].astype(q.dtype)


def kv_quantize(t: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(..., S, H, D) -> int8 codes + per-(token, head) f32 scales — THE
    serving KV-cache layout (prefill writer, decode writer, TDA kernel,
    benchmarks and tests all share this one definition)."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) + 1e-6
    scale = (amax / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize(q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.float32) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def decode_attention(
    q: jnp.ndarray,  # (B, 1, Hq, D)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D) fp — or int8 codes with k_scale
    v_cache: jnp.ndarray,
    cache_index: jnp.ndarray,  # scalar or (B,) int32: valid cache slots
    *,
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (B, S, Hkv): int8 KV scales
    v_scale: Optional[jnp.ndarray] = None,
    impl: str = "dense",
    block_k: int = 128,
    mesh=None,  # tensor-parallel mesh: dispatch to the sharded merge path
) -> jnp.ndarray:
    """Single-token attention against a (possibly ring-buffered) KV cache.

    ``cache_index`` may be a scalar (every row at the same depth — the
    lock-step serve path) or a ``(B,)`` vector (slot-based continuous
    batching: each row is an independent request at its own depth). Ring
    caches are read by passing ``cache_index = min(len + 1, ring)`` with
    ``window=None`` — canonical ring phase keeps occupancy a contiguous
    ``[0, hi)`` span (the bounds contract in ``kernels/tda/tda.py``).

    ``impl="tda"`` dispatches to the fused Pallas kernel
    (:mod:`repro.kernels.tda`): per-slot length predication skips dead kv
    blocks and int8 codes (``k_scale``/``v_scale`` given) dequantize in
    VMEM. ``impl="dense"`` is this jnp path — with scales it dequantizes
    the whole cache first, which the kernel exists to avoid.

    Paged lane pools are read by :func:`attention_block` itself (the
    paged TDA kernel, or this dense path over gathered lanes).

    ``mesh`` with a >1 ``model`` axis dispatches to the tensor-parallel
    form (:mod:`repro.kernels.tda.sharded`): caches sharded on the KV-head
    axis, per-rank online-softmax partials merged by one cross-rank
    rescale/psum.
    """
    if tensor_parallel_size(mesh) > 1:
        return sharded_decode_attention(
            q, k_cache, v_cache, cache_index, mesh=mesh, window=window,
            k_scale=k_scale, v_scale=v_scale)
    if impl == "tda":
        return fused_decode_attention(
            q, k_cache, v_cache, cache_index, k_scale=k_scale,
            v_scale=v_scale, window=window, block_k=block_k)
    if k_scale is not None:
        k_cache = k_cache.astype(jnp.float32) * k_scale[..., None]
        v_cache = v_cache.astype(jnp.float32) * v_scale[..., None]
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    scale = 1.0 / np.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S)
    idx = jnp.reshape(cache_index, (-1, 1))  # (1, 1) or (B, 1)
    valid = pos[None, :] < idx
    if window is not None:
        valid &= pos[None, :] >= (idx - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, D).astype(q.dtype)


# --------------------------------------------------------------------------
# Attention block (GQA + RoPE + cache), factorization-aware
# --------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg: ModelConfig, bank: Optional[DictionaryBank],
                   prefix: str = "attn") -> Dict:
    d = cfg.d_model
    hd = cfg.head_dim
    fcfg = cfg.factorization
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], d, cfg.n_heads * hd, fcfg, bank, f"{prefix}_q",
                          use_bias=cfg.qkv_bias, dtype=cfg.params_dtype),
        "wk": init_linear(ks[1], d, cfg.kv_heads * hd, fcfg, bank, f"{prefix}_k",
                          use_bias=cfg.qkv_bias, dtype=cfg.params_dtype),
        "wv": init_linear(ks[2], d, cfg.kv_heads * hd, fcfg, bank, f"{prefix}_v",
                          use_bias=cfg.qkv_bias, dtype=cfg.params_dtype),
        "wo": init_linear(ks[3], cfg.n_heads * hd, d, fcfg, bank, f"{prefix}_o",
                          use_bias=cfg.linear_bias, dtype=cfg.params_dtype),
    }


def attention_block(
    p: Dict,
    x: jnp.ndarray,  # (B, S, d)
    *,
    cfg: ModelConfig,
    dicts: Optional[Dict],
    positions: jnp.ndarray,  # (B, S) within-segment positions (RoPE)
    seg_ids: Optional[jnp.ndarray],  # (B, S)
    window: Optional[int] = None,
    cache: Optional[Dict] = None,  # {"k","v"} (L?, B, S_max, Hkv, D)
    cache_index: Optional[jnp.ndarray] = None,  # scalar or (B,) int32
    slot_mask: Optional[jnp.ndarray] = None,  # (B,) bool: rows allowed to
    # write their decode KV (inactive serving slots keep their lane intact)
    pages: Optional[Dict] = None,  # paged decode (engine-only): {"bt":
    # (B, n) int32 block table, "width": logical lane width (static int),
    # "page_size": static int}; cache leaves are then physical page pools
    prefix_kv: Optional[Dict] = None,  # suffix prefill (engine-only):
    # {"k"/"v": (B, Np, Hkv, D) fp post-RoPE cached prefix, "len": int32
    # valid prefix length}; queries attend prefix ∥ causal-suffix
    n_new: Optional[jnp.ndarray] = None,  # mixed step (engine-only, with
    # ``pages``): (B,) count of fresh tokens per row, in [0, S]. Row b's
    # columns [0, n_new[b]) sit at absolute positions [cache_index[b],
    # cache_index[b] + n_new[b]); decode rows pass 1, inert rows 0.
    layer_idx: Optional[jnp.ndarray] = None,  # set when cache is L-stacked
    kv: Optional[jnp.ndarray] = None,  # cross-attention memory (B, Skv, d)
    seg_kv: Optional[jnp.ndarray] = None,
    sparse_train: bool = False,
    prefix: str = "attn",
    mesh=None,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    B, S, d = x.shape
    hd = cfg.head_dim
    fcfg = cfg.factorization
    dt = cfg.compute_dtype

    def lin(name, inp, fam):
        return apply_linear(p[name], inp, dicts, fam, fcfg, sparse_train,
                            compute_dtype=dt).astype(dt)

    x_kv = kv if kv is not None else x
    q = lin("wq", x, f"{prefix}_q").reshape(B, S, cfg.n_heads, hd)
    k = lin("wk", x_kv, f"{prefix}_k").reshape(B, x_kv.shape[1], cfg.kv_heads, hd)
    v = lin("wv", x_kv, f"{prefix}_v").reshape(B, x_kv.shape[1], cfg.kv_heads, hd)
    # A paged serving step on a tensor-parallel mesh pins the head axis
    # too: left to itself the partitioner gathers whole 512-column chunks
    # of q, k and v on every rank before the per-rank kernels.
    mdim = 2 if cfg.constrain_acts or (
        pages is not None and tensor_parallel_size(mesh) > 1) else None
    q, k, v = (constrain_batch(t, mesh, model_dim=mdim) for t in (q, k, v))

    if cfg.rope and kv is None:
        cos, sin = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    def write(buf, upd, starts):
        """In-place DUS into the (possibly L-stacked) cache buffer. The
        update region is the only write traffic — stacked caches ride the
        layer-scan carry, never its ys (which would copy the whole cache
        per layer — see EXPERIMENTS §Dry-run)."""
        upd = upd.astype(buf.dtype)
        if layer_idx is not None:
            upd = upd[None]
            starts = (layer_idx,) + starts
        return jax.lax.dynamic_update_slice(buf, upd, starts)

    def layer_view(buf):
        if layer_idx is None:
            return buf
        return jax.lax.dynamic_index_in_dim(buf, layer_idx, 0, keepdims=False)

    new_cache = None
    ring = cache["k"].shape[-3] if cache is not None else 0
    quant = cache is not None and "k_scale" in cache
    if cache is not None and n_new is not None:
        # ---- mixed step: chunked-prefill and decode tokens in one (B, S)
        # forward over paged lanes (engine-only). Row b carries n_new[b]
        # fresh tokens, left-aligned, at absolute positions [cache_index,
        # cache_index + n_new). Queries attend the PRE-write lane view plus
        # the causal in-row chunk, and only then is the chunk K/V written
        # into the pool — a chunk write may land on a ring position an
        # earlier query still needs, so attend-then-write is load-bearing.
        assert pages is not None, "mixed step requires paged lanes"
        ringw = pages["width"]  # logical lane width (static int)
        bt = pages["bt"]        # (B, n) int32; FREE sentinel == num_pages
        ci = jnp.reshape(cache_index, (-1,)).astype(jnp.int32)
        nn = jnp.reshape(n_new, (-1,)).astype(jnp.int32)
        if slot_mask is not None:
            sm = jnp.reshape(slot_mask, (-1,))
            ci = jnp.where(sm, ci, 0)
            nn = jnp.where(sm, nn, 0)  # inert row: attends nothing, writes
            # nothing — the engine discards its logits either way

        if quant:
            kq, ksc = kv_quantize(k)
            vq, vsc = kv_quantize(v)
            # In-row keys attend as their resident (round-tripped)
            # representation — the same values later chunks will read back
            # out of the pool, so chunk boundaries don't shift attention.
            k_row = kv_dequantize(kq, ksc, dt)
            v_row = kv_dequantize(vq, vsc, dt)
        else:
            k_row, v_row = k, v

        use_kernel = resolve_decode_attn(cfg.decode_attn) == "tda"
        new = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc} \
            if quant else {"k": k, "v": v}

        def attend_then_write(q, pools, k_row, v_row, new, ci, nn, bt,
                              layer):
            # The pool is read and written in place, addressed by (layer,
            # page); the write is ordered after the attention by a data
            # edge (its output), since a chunk may overwrite a ring
            # position an earlier query still reads.
            with jax.named_scope(ATTN_SCOPE):
                o = fused_mixed_attention(
                    q, pools["k"], pools["v"], k_row, v_row, ci, nn,
                    block_table=bt, ring=ringw, window=window,
                    k_scale=pools.get("k_scale"),
                    v_scale=pools.get("v_scale"), layer=layer,
                    use_kernel=use_kernel)
            with jax.named_scope(KV_WRITE_SCOPE):
                pools = write_paged_kv(
                    pools, new, ci, nn, block_table=bt, ring=ringw,
                    layer=layer, after=o, use_kernel=use_kernel)
            # Heads flattened here, per rank: the output projection reads
            # (B, S, heads x D) sharded on its last axis.
            return o.reshape(o.shape[:2] + (-1,)), pools

        hp = _pool_head_axis(layer_idx)
        if use_kernel:
            attend_then_write = per_rank(
                attend_then_write, mesh, (2, hp, 2, 2, 2, None, None, None,
                                          None), (2, hp))
        o, new_cache = attend_then_write(
            q, {name: cache[name] for name in new}, k_row, v_row, new, ci,
            nn, bt, layer_idx)
    elif cache is not None and S == 1 and pages is not None:
        # ---- paged decode: lanes live in a page pool (serve/pages.py) ----
        # Logical lane coordinates are the contiguous layout's (canonical
        # ring phase, [lo, hi) bounds); only the *physical* home of logical
        # page ``p // page_size`` is indirected through the block table.
        # The token is written into the pool in place, then attention
        # reads the written pool at (layer, page).
        ringw = pages["width"]  # logical lane width (static int)
        bt = pages["bt"]        # (B, n) int32; FREE sentinel == num_pages
        impl = resolve_decode_attn(cfg.decode_attn)
        if quant:
            kq, ksc = kv_quantize(k)
            vq, vsc = kv_quantize(v)
            new = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
        else:
            new = {"k": k, "v": v}
        pools = {name: cache[name] for name in new}
        ci = jnp.reshape(cache_index, (-1,))
        # Inactive slots write nothing: the lane stays untouched.
        nn = jnp.ones((B,), jnp.int32) if slot_mask is None \
            else jnp.reshape(slot_mask, (-1,)).astype(jnp.int32)
        if slot_mask is not None:
            cache_index = jnp.where(jnp.reshape(slot_mask, (-1,)),
                                    cache_index, -1)
        # Ring lanes: every position < min(cache_index+1, ring) is valid
        # (canonical ring phase) — same bounds as the contiguous layout.
        hi = cache_index + 1 if window is None \
            else jnp.minimum(cache_index + 1, ringw)
        if impl == "tda":
            def write_then_attend(q, pools, new, ci, nn, hi, bt, layer):
                with jax.named_scope(KV_WRITE_SCOPE):
                    pools = write_paged_kv(pools, new, ci, nn,
                                           block_table=bt, ring=ringw,
                                           layer=layer)
                # The kernel consumes the stacked page pools directly: the
                # layer and block table ride scalar prefetch and one page
                # is one kv block.
                with jax.named_scope(ATTN_SCOPE):
                    o = fused_decode_attention(
                        q, pools["k"], pools["v"], hi,
                        k_scale=pools.get("k_scale"),
                        v_scale=pools.get("v_scale"), block_table=bt,
                        layer=layer)
                return o.reshape(o.shape[:2] + (-1,)), pools

            hp = _pool_head_axis(layer_idx)
            o, new_cache = per_rank(
                write_then_attend, mesh, (2, hp, 2, None, None, None, None,
                                          None), (2, hp))(
                q, pools, new, ci, nn, hi, bt, layer_idx)
        else:
            with jax.named_scope(KV_WRITE_SCOPE):
                new_cache = write_paged_kv(pools, new, ci, nn,
                                           block_table=bt, ring=ringw,
                                           layer=layer_idx, use_kernel=False)
            # Dense path: gather each slot's lane view out of the pool
            # (same data volume as reading a dense lane), then attend.
            def lanes(name):
                return gather_paged_lanes(new_cache[name], bt, layer_idx)

            with jax.named_scope(ATTN_SCOPE):
                if quant:
                    kc = kv_dequantize(lanes("k"), lanes("k_scale"), dt)
                    vc = kv_dequantize(lanes("v"), lanes("v_scale"), dt)
                else:
                    kc, vc = lanes("k"), lanes("v")
                o = decode_attention(q, kc, vc, hi, impl="dense", mesh=mesh)
        o = o.reshape(B, S, cfg.n_heads * hd)
    elif cache is not None and S == 1:
        # Decode: write this step's K/V at cache_index (ring for windowed).
        # The slot write is a one-hot select over S — a dynamic-update-slice
        # at a traced slot on the sharded S axis would force GSPMD to gather
        # the whole cache every layer (EXPERIMENTS §Dry-run). The layer slice
        # is read/written via DUS that is dynamic only on the unsharded L.
        # ``cache_index`` may be per-row (continuous batching): the one-hot
        # broadcasts over the batch dim either way, and ``slot_mask`` zeroes
        # the write for inactive slots so their lanes stay untouched.
        slot = cache_index if window is None else cache_index % ring
        hot = (jax.lax.iota(jnp.int32, ring)[None, :]
               == jnp.reshape(slot, (-1, 1)))  # (1, ring) or (B, ring)
        if slot_mask is not None:
            hot = hot & jnp.reshape(slot_mask, (-1, 1))

        def slot_write_nd(buf, new):
            lv = layer_view(buf)
            hb = hot.reshape(hot.shape + (1,) * (lv.ndim - 2))
            lv = jnp.where(hb, new.astype(buf.dtype), lv)
            if layer_idx is None:
                return lv
            return jax.lax.dynamic_update_slice(
                buf, lv[None], (layer_idx,) + (0,) * lv.ndim)

        impl = resolve_decode_attn(cfg.decode_attn)
        # Inactive serving slots attend nothing: zero their valid span so
        # the predicated kernel skips every block of a dead lane (their
        # outputs are discarded by the engine either way).
        if slot_mask is not None:
            cache_index = jnp.where(jnp.reshape(slot_mask, (-1,)),
                                    cache_index, -1)
        kcs = vcs = None
        if quant:
            kq, ks = kv_quantize(k)
            vq, vs = kv_quantize(v)
            new_cache = {"k": slot_write_nd(cache["k"], kq),
                         "v": slot_write_nd(cache["v"], vq),
                         "k_scale": slot_write_nd(cache["k_scale"], ks),
                         "v_scale": slot_write_nd(cache["v_scale"], vs)}
            if impl == "tda":
                # The fused kernel consumes the codes + scales directly and
                # dequantizes per block in VMEM — the dense fp cache below
                # never materializes on this path.
                kc = layer_view(new_cache["k"])
                vc = layer_view(new_cache["v"])
                kcs = layer_view(new_cache["k_scale"])
                vcs = layer_view(new_cache["v_scale"])
            else:
                kc = kv_dequantize(layer_view(new_cache["k"]),
                                   layer_view(new_cache["k_scale"]), dt)
                vc = kv_dequantize(layer_view(new_cache["v"]),
                                   layer_view(new_cache["v_scale"]), dt)
        else:
            kc_all = slot_write_nd(cache["k"], k)
            vc_all = slot_write_nd(cache["v"], v)
            new_cache = {"k": kc_all, "v": vc_all}
            kc, vc = layer_view(kc_all), layer_view(vc_all)
        if window is None:
            o = decode_attention(q, kc, vc, cache_index + 1,
                                 k_scale=kcs, v_scale=vcs, impl=impl,
                                 block_k=cfg.decode_block_k, mesh=mesh)
        else:
            # Ring buffer: all slots < min(cache_index+1, ring) are valid.
            o = decode_attention(q, kc, vc, jnp.minimum(cache_index + 1, ring),
                                 window=None, k_scale=kcs, v_scale=vcs,
                                 impl=impl, block_k=cfg.decode_block_k,
                                 mesh=mesh)
        o = o.reshape(B, S, cfg.n_heads * hd)
    else:
        if cache is not None:  # prefill writing the cache
            def ring_layout(t):
                """Last ``ring`` tokens in *canonical ring phase*: token at
                (row) position p lands at cache position ``p % ring``, the
                same phase decode's write pointer ``cache_index % ring``
                uses — so the first decoded token overwrites the oldest
                cached one. (The previous un-rotated layout left a stale
                token visible whenever the prompt exceeded the window.)"""
                if t.shape[1] <= ring:
                    return t
                return jnp.roll(t[:, -ring:], t.shape[1] % ring, axis=1)

            kw, vw = ring_layout(k), ring_layout(v)
            if quant:
                kq, ks = kv_quantize(kw)
                vq, vs = kv_quantize(vw)
                new_cache = {"k": write(cache["k"], kq, (0, 0, 0, 0)),
                             "v": write(cache["v"], vq, (0, 0, 0, 0)),
                             "k_scale": write(cache["k_scale"], ks, (0, 0, 0)),
                             "v_scale": write(cache["v_scale"], vs, (0, 0, 0))}
            else:
                new_cache = {"k": write(cache["k"], kw, (0, 0, 0, 0)),
                             "v": write(cache["v"], vw, (0, 0, 0, 0))}
        if prefix_kv is not None:
            # ---- suffix prefill over a shared KV prefix (serve/pages.py):
            # the cache already holds the prefix's post-RoPE K/V (gathered
            # out of shared pages); only the suffix rides this forward, so
            # prepend the prefix to the keys and let flash_attention's
            # decode-style alignment (queries sit at the END of the kv
            # axis) keep suffix causality while every query sees the whole
            # prefix. Positions/RoPE for the suffix are absolute (the
            # engine passes them); the prefix needs none — it was rotated
            # at write time. The window mask is dropped on purpose: ring
            # classes only share prefixes when the *total* sequence fits
            # the window (an unwrapped lane), so it could never bind.
            pk = prefix_kv["k"].astype(dt)  # (B, Np, Hkv, D)
            pv = prefix_kv["v"].astype(dt)
            plen = prefix_kv["len"]
            sq = seg_ids if seg_ids is not None \
                else jnp.ones((B, S), jnp.int32)
            pseg = (jax.lax.iota(jnp.int32, pk.shape[1])[None, :]
                    < jnp.reshape(plen, (-1, 1))).astype(sq.dtype)
            pseg = jnp.broadcast_to(pseg, (B, pk.shape[1]))
            o = flash_attention(
                q, jnp.concatenate([pk, k], axis=1),
                jnp.concatenate([pv, v], axis=1),
                causal=cfg.causal and kv is None,
                window=None,
                chunk=cfg.attn_chunk,
                seg_q=sq,
                seg_kv=jnp.concatenate([pseg, sq], axis=1),
                block_dtype=jnp.dtype(cfg.flash_block_dtype),
            ).reshape(B, S, cfg.n_heads * hd)
        else:
            o = flash_attention(
                q, k, v,
                causal=cfg.causal and kv is None,
                window=window,
                chunk=cfg.attn_chunk,
                seg_q=seg_ids,
                seg_kv=seg_kv if kv is not None else seg_ids,
                block_dtype=jnp.dtype(cfg.flash_block_dtype),
                wedge=cfg.causal_wedge,
            ).reshape(B, S, cfg.n_heads * hd)

    with _row_parallel(mesh):
        y = apply_linear(p["wo"], o, dicts, f"{prefix}_o", fcfg,
                         sparse_train, compute_dtype=dt)
    return y.astype(dt), new_cache


# --------------------------------------------------------------------------
# FFN
# --------------------------------------------------------------------------


def init_ffn(key: jax.Array, cfg: ModelConfig, bank: Optional[DictionaryBank],
             d_ff: Optional[int] = None, prefix: str = "ffn") -> Dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    fcfg = cfg.factorization
    ks = jax.random.split(key, 3)
    bias = cfg.linear_bias
    p = {"w_up": init_linear(ks[0], d, f, fcfg, bank, f"{prefix}_up",
                             use_bias=bias, dtype=cfg.params_dtype),
         "w_down": init_linear(ks[1], f, d, fcfg, bank, f"{prefix}_down",
                               use_bias=bias, dtype=cfg.params_dtype)}
    if cfg.act in ("swiglu", "geglu"):
        p["w_gate"] = init_linear(ks[2], d, f, fcfg, bank, f"{prefix}_gate",
                                  use_bias=bias, dtype=cfg.params_dtype)
    return p


def ffn_block(p: Dict, x: jnp.ndarray, *, cfg: ModelConfig, dicts: Optional[Dict],
              sparse_train: bool = False, prefix: str = "ffn",
              mesh=None) -> jnp.ndarray:
    fcfg = cfg.factorization
    dt = cfg.compute_dtype

    def lin(name, inp, fam):
        return apply_linear(p[name], inp, dicts, fam, fcfg, sparse_train,
                            compute_dtype=dt).astype(dt)

    up = lin("w_up", x, f"{prefix}_up")
    if cfg.act == "swiglu":
        h = jax.nn.silu(lin("w_gate", x, f"{prefix}_gate")) * up
    elif cfg.act == "geglu":
        h = jax.nn.gelu(lin("w_gate", x, f"{prefix}_gate")) * up
    else:
        h = jax.nn.gelu(up)
    if cfg.constrain_acts:
        h = constrain_batch(h, mesh, model_dim=-1)
    with _row_parallel(mesh):
        return lin("w_down", h, f"{prefix}_down")


# --------------------------------------------------------------------------
# Embeddings / logits
# --------------------------------------------------------------------------


def init_embedding(key: jax.Array, cfg: ModelConfig) -> Dict:
    p = {}
    if not cfg.external_embeddings:
        p["tok"] = (jax.random.normal(key, (cfg.vocab_size, cfg.d_model),
                                      cfg.params_dtype) * 0.02)
    if cfg.learned_pos:
        p["pos"] = (jax.random.normal(key, (cfg.max_len, cfg.d_model),
                                      cfg.params_dtype) * 0.02)
    return p


def embed_tokens(p: Dict, tokens: jnp.ndarray, cfg: ModelConfig,
                 positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    x = jnp.take(p["tok"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.learned_pos and positions is not None:
        x = x + jnp.take(p["pos"], positions, axis=0).astype(cfg.compute_dtype)
    return x


def init_lm_head(key: jax.Array, cfg: ModelConfig) -> Dict:
    if cfg.tie_embeddings:
        return {}
    n_heads = cfg.n_codebooks
    shape = (cfg.d_model, cfg.vocab_size)
    if n_heads > 1:
        shape = (n_heads,) + shape
    return {"w": (jax.random.normal(key, shape, cfg.params_dtype)
                  / np.sqrt(cfg.d_model)).astype(cfg.params_dtype)}


def lm_logits(p_head: Dict, p_embed: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.tie_embeddings:
        return xf @ p_embed["tok"].astype(jnp.float32).T
    w = p_head["w"].astype(jnp.float32)
    if cfg.n_codebooks > 1:
        return jnp.einsum("bsd,cdv->bscv", xf, w)
    return xf @ w


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean xent over weighted positions. logits (..., V), labels (...)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if weights is None:
        return nll.mean()
    return (nll * weights).sum() / jnp.maximum(weights.sum(), 1.0)


def chunked_xent(p_head: Dict, p_embed: Dict, h: jnp.ndarray,
                 labels: jnp.ndarray, cfg: ModelConfig,
                 weights: Optional[jnp.ndarray] = None,
                 chunk: int = 512) -> jnp.ndarray:
    """Cross-entropy without materializing the full (B, S, V) logits.

    Scans over sequence chunks, computing logits -> logsumexp -> gold logit
    per chunk; the (B, c, V) chunk is transient (and rematerialized in the
    backward pass). Essential for the 150k-vocab archs at 4k x 256 batch —
    full logits would be hundreds of GB per chip (EXPERIMENTS §Dry-run).
    """
    B, S, d = h.shape
    c = min(chunk, S)
    if S % c != 0:
        return cross_entropy(lm_logits(p_head, p_embed, h, cfg), labels,
                             weights)
    n = S // c
    hc = h.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape((B, n, c) + labels.shape[2:]).swapaxes(0, 1)
    wc = None if weights is None else \
        weights.reshape(B, n, c).transpose(1, 0, 2)

    def body(carry, xs):
        nll_sum, w_sum = carry
        if wc is None:
            h_i, l_i = xs
            w_i = jnp.ones(l_i.shape[:2], jnp.float32)
        else:
            h_i, l_i, w_i = xs
        logits = lm_logits(p_head, p_embed, h_i, cfg)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l_i[..., None], axis=-1)[..., 0]
        nll = lse - gold
        if nll.ndim > w_i.ndim:  # multi-codebook: mean over codebooks
            nll = nll.mean(-1)
        return (nll_sum + (nll * w_i).sum(), w_sum + w_i.sum()), None

    xs = (hc, lc) if wc is None else (hc, lc, wc)
    # Recompute the chunk logits in the backward pass — otherwise the scan
    # saves every chunk's (B, c, V) logits and the chunking buys nothing.
    body = jax.checkpoint(
        body, policy=jax.checkpoint_policies.nothing_saveable)
    (nll_sum, w_sum), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), xs)
    return nll_sum / jnp.maximum(w_sum, 1.0)
