"""Public TDA op: fused slot-decode attention with padding + bound prep.

``fused_decode_attention`` is the serving-hot-path entry point: it accepts
the exact tensors :func:`repro.models.layers.attention_block` holds at
decode time — (B, 1, Hq, D) queries, the (possibly int8-quantized) KV lanes
of a :class:`~repro.serve.kv_slots.SlotKVCache`, and per-slot depths — pads
the cache axis to a block multiple (padding lands beyond every ``hi`` bound
so the predicate never visits it), and runs the kernel.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.tda.page_write import (
    paged_kv_write,
    paged_kv_write_reference,
)
from repro.kernels.tda.ref import (
    block_stats,
    decode_attention_reference,
    mixed_attention_reference,
)
from repro.kernels.tda.tda import (
    tda_decode_attention,
    tda_mixed_attention,
    tda_paged_decode_attention,
)

__all__ = ["fused_decode_attention", "fused_mixed_attention",
           "gather_paged_lanes", "write_paged_kv", "block_stats"]


def _pad_seq(x: Optional[jnp.ndarray], target: int) -> Optional[jnp.ndarray]:
    if x is None or x.shape[1] == target:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, target - x.shape[1])
    return jnp.pad(x, widths)


def gather_paged_lanes(pool: jnp.ndarray, block_table: jnp.ndarray,
                       layer=None) -> jnp.ndarray:
    """Materialize per-slot lane views out of a head-major page pool:
    ``(P, Hkv, page_size, ...) + (B, n) -> (B, n * page_size, Hkv, ...)``,
    token-major like a contiguous lane. With ``layer`` the pool is the
    layer-stacked ``(L, P, Hkv, page_size, ...)`` and the layer is read in
    the same gather (no layer view first). Sentinel table entries clamp
    into range; the garbage they gather sits beyond every ``hi`` bound (a
    slot's pages are a logical prefix), so the masked softmax never reads
    it. This is the jnp-reference mirror of what the paged kernels'
    scalar-prefetch index maps do per block."""
    P = pool.shape[0 if layer is None else 1]
    ids = jnp.clip(block_table, 0, P - 1)
    pages = pool[ids] if layer is None else pool[layer, ids]
    pages = jnp.swapaxes(pages, 2, 3)  # (B, n, page_size, Hkv, ...)
    B, n, ps = pages.shape[:3]
    return pages.reshape((B, n * ps) + pages.shape[3:])


def _stacked(pools, layer):
    """Kernel operands for a pool and its layer: an unstacked pool
    ``(P, ...)`` becomes a one-layer stack (a bitcast) at layer 0."""
    if layer is None:
        return tuple(None if p is None else p[None] for p in pools), 0
    return tuple(pools), layer


def write_paged_kv(
    pools: Dict[str, jnp.ndarray],  # (L?, P, Hkv, page_size, ...) leaves
    news: Dict[str, jnp.ndarray],   # (B, S, Hkv, ...) token-major values
    cache_index: jnp.ndarray,       # (B,): lane depth before the write
    n_new: jnp.ndarray,             # (B,): columns to write, 0 = none
    *,
    block_table: jnp.ndarray,       # (B, n)
    ring: int,                      # logical lane width
    layer=None,                     # the layer of layer-stacked pools
    after: Optional[jnp.ndarray] = None,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> Dict[str, jnp.ndarray]:
    """Write a step's new K/V into its page pools: row ``b``'s column
    ``j`` lands at lane position ``(cache_index + j) % ring`` (the last
    ``ring`` columns of a wrapping chunk only). With ``use_kernel`` it is
    :func:`~repro.kernels.tda.page_write.paged_kv_write`, in place, and
    ``after`` orders it after a read of the old pools; otherwise (and for
    int8 scales of a multi-token step) one XLA scatter per leaf at
    ``(layer, page, :, offset)``, with the same result. Returns the new
    pools under the names of ``pools``."""
    names = list(pools)
    S = news[names[0]].shape[1]
    if not use_kernel or (S > 1 and "k_scale" in pools):
        out = paged_kv_write_reference(
            [pools[n] for n in names], [news[n] for n in names], layer,
            cache_index, n_new, block_table, ring=ring)
        return dict(zip(names, out))
    stacked, lyr = _stacked([pools[n] for n in names], layer)
    out = paged_kv_write(stacked, [news[n] for n in names], lyr,
                         cache_index, n_new, block_table, after, ring=ring,
                         interpret=resolve_interpret(interpret))
    if layer is None:
        out = [x[0] for x in out]
    return dict(zip(names, out))


def fused_decode_attention(
    q: jnp.ndarray,  # (B, 1, Hq, D) or (B, Hq, D)
    k: jnp.ndarray,  # (B, S, Hkv, D) fp, or int8 codes with k_scale
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # scalar or (B,): valid cache depth per slot
    *,
    k_scale: Optional[jnp.ndarray] = None,  # (B, S, Hkv)
    v_scale: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    lut_table: Optional[jnp.ndarray] = None,  # AFU exp LUT (else exact exp)
    block_k: int = 128,
    block_table: Optional[jnp.ndarray] = None,  # (B, n): paged lane pool
    layer=None,  # paged: the layer of a layer-stacked pool
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Length-predicated decode attention over slot KV lanes.

    Valid positions per slot are ``[max(0, lengths - window), lengths)``
    (``window=None`` -> ``[0, lengths)``). Slots with ``lengths <= 0``
    return zeros. Output matches ``q``'s leading shape, dtype ``q.dtype``.

    Ring-buffered lanes (windowed caches shorter than the lane, stored in
    canonical ring phase — see the bounds contract in ``tda.py`` and
    ``docs/serving.md``) pass ``lengths = min(len + 1, ring)`` with
    ``window=None``: every ring position below the clamp is valid and
    ordering is irrelevant to the softmax, so no per-slot offset input is
    needed. This is what :func:`repro.models.layers.attention_block` does
    on the serving decode path.

    ``block_table`` selects the **paged** layout: ``k``/``v`` (and scales)
    are head-major physical page pools ``(P, Hkv, page_size, ...)`` — or,
    with ``layer``, the layer-stacked ``(L, P, Hkv, page_size, ...)``,
    read in place at that layer — and ``block_table[b, i]`` names the
    physical page backing logical kv block ``i`` of slot ``b`` — one page
    is one kv block, read via scalar prefetch (``block_k`` is ignored; the
    page size is the block size). Bounds stay in logical lane
    coordinates, so the ``[lo, hi)`` contract is unchanged.
    """
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    if block_table is not None:
        B = q.shape[0]
        S = block_table.shape[1] * k.shape[-2]  # logical lane width
        if not use_kernel:
            def lanes(x):
                return None if x is None \
                    else gather_paged_lanes(x, block_table, layer)

            out = decode_attention_reference(
                q, lanes(k), lanes(v), lengths, k_scale=lanes(k_scale),
                v_scale=lanes(v_scale), window=window)
            return (out.astype(q.dtype)[:, None] if squeeze
                    else out.astype(q.dtype))
        hi = jnp.clip(jnp.broadcast_to(jnp.reshape(lengths, (-1,)), (B,)),
                      0, S)
        lo = jnp.zeros_like(hi) if window is None \
            else jnp.maximum(hi - window, 0)
        bounds = jnp.stack([lo, hi], axis=1).astype(jnp.int32)
        (k, v, k_scale, v_scale), layer = _stacked(
            (k, v, k_scale, v_scale), layer)
        out = tda_paged_decode_attention(
            q, k, v, layer, bounds, block_table.astype(jnp.int32), k_scale,
            v_scale, lut_table,
            interpret=resolve_interpret(interpret)).astype(q.dtype)
        return out[:, None] if squeeze else out
    if not use_kernel:
        out = decode_attention_reference(q, k, v, lengths, k_scale=k_scale,
                                         v_scale=v_scale, window=window)
        out = out.astype(q.dtype)
        return out[:, None] if squeeze else out
    B, Hq, D = q.shape
    S = k.shape[1]
    bk = min(block_k, max(S, 1))
    Sp = ((S + bk - 1) // bk) * bk
    k, v = _pad_seq(k, Sp), _pad_seq(v, Sp)
    k_scale, v_scale = _pad_seq(k_scale, Sp), _pad_seq(v_scale, Sp)
    hi = jnp.clip(jnp.broadcast_to(jnp.reshape(lengths, (-1,)), (B,)), 0, S)
    lo = jnp.zeros_like(hi) if window is None \
        else jnp.maximum(hi - window, 0)
    bounds = jnp.stack([lo, hi], axis=1).astype(jnp.int32)
    out = tda_decode_attention(
        q, k, v, bounds, k_scale, v_scale, lut_table, block_k=bk,
        interpret=resolve_interpret(interpret)).astype(q.dtype)
    return out[:, None] if squeeze else out


def fused_mixed_attention(
    q: jnp.ndarray,      # (B, S, Hq, D) chunk queries, left-aligned
    k: jnp.ndarray,      # (L?, P, Hkv, page_size, D) PRE-write page pool
    v: jnp.ndarray,
    k_row: jnp.ndarray,  # (B, S, Hkv, D) fp this-chunk keys
    v_row: jnp.ndarray,
    cache_index: jnp.ndarray,  # (B,): tokens resident in the lane
    n_new: jnp.ndarray,        # (B,): valid chunk columns, in [0, S]
    *,
    block_table: jnp.ndarray,  # (B, n) paged lane pool table
    ring: int,                 # logical lane width
    window: Optional[int] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (L?, P, Hkv, page_size)
    v_scale: Optional[jnp.ndarray] = None,
    lut_table: Optional[jnp.ndarray] = None,
    layer=None,  # the layer of a layer-stacked pool
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Multi-query mixed-step attention over paged slot lanes.

    The mixed serving step's attention entry point: query column ``j`` of
    row ``b`` sits at absolute position ``cache_index[b] + j`` and attends
    the union of the slot's pre-write lane occupancy and the causally
    visible in-row chunk columns ``< n_new[b]`` — so chunked-prefill
    attention is predicated the same way decode is (cache blocks outside
    the occupied span are skipped). Semantics (masks, ring position
    recovery, never-attended zeros) are pinned by
    :func:`repro.kernels.tda.ref.mixed_attention_reference`. Returns
    (B, S, Hq, D) in ``q.dtype``.
    """
    ci = jnp.reshape(cache_index, (-1,)).astype(jnp.int32)
    nn = jnp.reshape(n_new, (-1,)).astype(jnp.int32)
    if not use_kernel:
        def lanes(x):
            return None if x is None \
                else gather_paged_lanes(x, block_table, layer)

        out = mixed_attention_reference(
            q, lanes(k), lanes(v), k_row, v_row, ci, nn, ring=ring,
            window=window, k_scale=lanes(k_scale), v_scale=lanes(v_scale))
        return out.astype(q.dtype)
    bounds = jnp.stack([ci, nn], axis=1).astype(jnp.int32)
    (k, v, k_scale, v_scale), layer = _stacked((k, v, k_scale, v_scale),
                                               layer)
    return tda_mixed_attention(
        q, k, v, jnp.swapaxes(k_row, 1, 2), jnp.swapaxes(v_row, 1, 2),
        layer, bounds, block_table.astype(jnp.int32), k_scale, v_scale,
        lut_table, ring=ring, window=window,
        interpret=resolve_interpret(interpret)).astype(q.dtype)
