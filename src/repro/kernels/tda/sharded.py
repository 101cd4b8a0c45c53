"""Sharded decode attention: per-rank online-softmax partials + merge.

The tensor-parallel form of the TDA decode kernel (the flash-decode
pattern, after neuronx-distributed's ``flashdecode_attention``): each rank
computes *unnormalized* online-softmax partials

    ``acc = sum_j exp(s_j - m) * v_j``   (B, H, D) f32
    ``m   = max_j s_j``                  (B, H)    f32  (NEG_INF if empty)
    ``l   = sum_j exp(s_j - m)``         (B, H)    f32  (0 if empty)

over the keys/heads it owns, and the final output is assembled with one
cross-rank rescale + ``psum``:

    ``m* = pmax(m)``; ``l* = psum(l * exp(m - m*))``
    ``o  = psum(acc * exp(m - m*)) / max(l*, eps)``

The *empty partial* — a rank that visited zero kv blocks — is the classic
flash-decode bug: with the repo-wide finite sentinel ``NEG_INF = -1e30``
an empty partial is exactly ``(acc=0, m=NEG_INF, l=0)``, its rescale
``exp(NEG_INF - m*)`` underflows to exactly ``0.0`` whenever any other
rank saw a key, and when *no* rank saw one the merge degrades to
``exp(0) = 1``, ``l* = 0``, ``o = 0 / eps = 0`` — the same all-zero row
the single-device kernel emits for a never-attended slot. No NaNs, no
special cases.

Serving shards the **KV-head axis** (``serve/engine.py``): every rank
holds all positions of ``Hkv / n_ranks`` heads, so each head's softmax is
complete on its owner and the merge is *exact* — the owner's rescale is
``exp(0) = 1`` and every other rank contributes a structural zero. The
merge itself is position-split capable (partials over disjoint key ranges
combine associatively), which the unit tests pin by splitting sequences
across simulated ranks; head-sharding just exercises the degenerate —
and bitwise-stable — corner of the same contract.

``sharded_decode_attention`` wraps the partial computation in a
``shard_map`` over the mesh's ``model`` axis and is the drop-in the dense
``decode_attention`` path dispatches to when the mesh is tensor-parallel;
``decode_partials`` / ``merge_partials`` are the pure pieces the unit
tests build on.

The TDA serving path needs no merge at all: :func:`per_rank` runs a
region of the paged step — the page write and the paged decode or mixed
kernel — once per rank in one ``shard_map``, each rank on its own heads
of the stacked page pool, and its output leaves sharded on heads for the
row-parallel output projection.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = ["decode_partials", "merge_partials", "sharded_decode_attention",
           "per_rank"]

NEG_INF = -1e30  # matches models/layers.py: finite masked-score sentinel
_EPS = 1e-30     # matches the TDA kernel's finish division guard


def decode_partials(
    q: jnp.ndarray,        # (B, Hq_loc, D) queries for this rank's heads
    k: jnp.ndarray,        # (B, S_loc, Hkv_loc, D) fp — or int8 codes
    v: jnp.ndarray,        # (B, S_loc, Hkv_loc, D)
    lengths: jnp.ndarray,  # (B,) int32: GLOBAL hi bound (pos < hi valid)
    *,
    k_scale: Optional[jnp.ndarray] = None,  # (B, S_loc, Hkv_loc)
    v_scale: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    pos_offset=0,          # global position of k[:, 0] (sequence splits)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One rank's online-softmax partials over its local keys/heads.

    Per-head math is identical to the dense ``decode_attention`` path
    (einsum scores at 1/sqrt(D), mask to ``NEG_INF``), but the softmax is
    left *unnormalized*: returns ``(acc, m, l)`` in f32 with the empty
    partial exactly ``(0, NEG_INF, 0)`` — a row whose ``[lo, hi)`` span
    misses this rank's ``[pos_offset, pos_offset + S_loc)`` key range
    contributes nothing after the merge rescale.
    """
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[..., None]
        v = v.astype(jnp.float32) * v_scale[..., None]
    B, S, Hkv, D = k.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    scale = 1.0 / np.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    pos = pos_offset + jnp.arange(S)
    idx = jnp.reshape(lengths, (-1, 1))  # (B, 1)
    valid = pos[None, :] < idx
    if window is not None:
        valid &= pos[None, :] >= (idx - window)
    vmask = valid[:, None, None, :]  # (B, 1, 1, S)
    s = jnp.where(vmask, s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B, Hkv, G): NEG_INF when nothing is valid
    # exp(s - m) would be exp(0) = 1 on fully-masked rows; gate on the
    # mask itself so the empty partial is exactly (0, NEG_INF, 0).
    p = jnp.where(vmask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)  # (B, Hkv, G)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return (acc.reshape(B, Hq, D), m.reshape(B, Hq), l.reshape(B, Hq))


def merge_partials(acc: jnp.ndarray,  # (R, B, H, D) f32 per-rank partials
                   m: jnp.ndarray,    # (R, B, H) f32 running maxima
                   l: jnp.ndarray,    # (R, B, H) f32 running denominators
                   ) -> jnp.ndarray:
    """Merge rank-stacked partials into the normalized output (B, H, D).

    The host-side form of the cross-rank merge (rank axis leading instead
    of a mesh axis): ``m* = max_r m``, rescale every partial by
    ``exp(m - m*)``, sum, and divide by ``max(l*, eps)``. All-empty rows
    (every rank at ``m = NEG_INF``) rescale by ``exp(0) = 1`` and land on
    ``0 / eps = 0`` — finite, and identical to the single-device kernel's
    never-attended output.
    """
    m_star = jnp.max(m, axis=0)                      # (B, H)
    scale = jnp.exp(m - m_star[None])                # (R, B, H)
    l_star = jnp.sum(l * scale, axis=0)              # (B, H)
    o = jnp.sum(acc * scale[..., None], axis=0)      # (B, H, D)
    return o / jnp.maximum(l_star, _EPS)[..., None]


def sharded_decode_attention(
    q: jnp.ndarray,        # (B, 1, Hq, D)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D) — Hkv axis sharded over `axis`
    v_cache: jnp.ndarray,
    cache_index: jnp.ndarray,  # scalar or (B,) int32 hi bound
    *,
    mesh,
    axis: str = "model",
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (B, S, Hkv) int8 KV scales
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Tensor-parallel ``decode_attention``: KV-head-sharded caches in,
    replicated (B, 1, Hq, D) out.

    Each rank computes partials for its contiguous head block (GQA groups
    follow their kv head, so q heads split in aligned blocks), scatters
    them into full-width (acc, m, l) buffers whose non-owned rows are the
    empty partial, and one pmax/psum rescale assembles the output — the
    owner's rescale is exp(0) = 1 so head-sharded serving is exact.
    """
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    tp = mesh.shape[axis]
    if Hkv % tp or Hq % tp:
        raise ValueError(
            f"kv_heads={Hkv} / n_heads={Hq} not divisible by the "
            f"{tp}-way '{axis}' mesh axis")
    hq_loc = Hq // tp
    hi = jnp.broadcast_to(jnp.reshape(cache_index, (-1,)).astype(jnp.int32),
                          (B,))
    quant = k_scale is not None

    def body(q_full, kl, vl, hi_l, ksl, vsl):
        r = jax.lax.axis_index(axis)
        q_loc = jax.lax.dynamic_slice_in_dim(q_full[:, 0], r * hq_loc,
                                             hq_loc, axis=1)
        acc_l, m_l, l_l = decode_partials(
            q_loc, kl, vl, hi_l,
            k_scale=ksl if quant else None,
            v_scale=vsl if quant else None, window=window)
        acc = jnp.zeros((B, Hq, D), jnp.float32)
        m = jnp.full((B, Hq), NEG_INF, jnp.float32)
        l = jnp.zeros((B, Hq), jnp.float32)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, acc_l, r * hq_loc, 1)
        m = jax.lax.dynamic_update_slice_in_dim(m, m_l, r * hq_loc, 1)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_l, r * hq_loc, 1)
        # Cross-rank distributed-softmax merge (the psum/pmax twin of
        # merge_partials above, which tests pin against the reference).
        m_star = jax.lax.pmax(m, axis)
        rescale = jnp.exp(m - m_star)
        l_star = jax.lax.psum(l * rescale, axis)
        o = jax.lax.psum(acc * rescale[..., None], axis)
        return o / jnp.maximum(l_star, _EPS)[..., None]

    kv_spec = P(None, None, axis, None)
    sc_spec = P(None, None, axis)
    # int8 scales ride along when quantized; a zero-size placeholder keeps
    # the shard_map arity fixed (specs must match positionally).
    ksl = k_scale if quant else jnp.zeros((B, S, Hkv), jnp.float32)
    vsl = v_scale if quant else jnp.zeros((B, S, Hkv), jnp.float32)
    out = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P(), kv_spec, kv_spec, P(), sc_spec, sc_spec),
        out_specs=P())(q, k_cache, v_cache, hi, ksl, vsl)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


def per_rank(fn, mesh, heads, out_heads, *, axis: str = "model"):
    """``fn`` as one ``shard_map`` over ``mesh``'s ``axis``: each rank runs
    it on its own block of heads. ``heads[i]`` is the head axis of every
    array in the ``i``-th argument (a pytree: a pool dict, a token-major
    tensor), ``None`` for arguments every rank reads whole (block tables,
    bounds, the layer); ``out_heads`` gives the same for each output.

    With the serving layouts — token-major ``(B, S, H, ...)`` tensors and
    head-major pools ``(L?, P, Hkv, page_size, ...)`` split over the
    KV-head axis, query heads in aligned blocks of the GQA group — a rank
    holds whole heads, so nothing crosses ranks. Kernels inside ``fn``
    see per-rank shapes, and a pool aliased to its output stays in place.
    Without a mesh, or on a one-rank axis, ``fn`` is returned as it is.
    """
    if mesh is None or axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return fn

    def spec(h):
        return P() if h is None else P(*(None,) * h, axis)

    return jax.shard_map(fn, mesh=mesh, check_vma=False,
                         in_specs=tuple(spec(h) for h in heads),
                         out_specs=tuple(spec(h) for h in out_heads))
