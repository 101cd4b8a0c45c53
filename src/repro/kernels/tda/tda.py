"""TDA Pallas kernel: length-predicated slot-decode attention.

One grid step = one (slot, kv-block) pair. Per-slot ``[lo, hi)`` bounds ride
in SMEM; ``pl.when`` skips every block outside the slot's occupied span, so
per-step work scales with actual cache occupancy instead of ``cache_len`` —
the TRF/dynamic-batching analogue of AccelTran's sparsity-aware block
skipping. Online-softmax state (m, l, o) lives in VMEM scratch carried
across the kv-block grid dimension; K/V arrive as int8 codes +
per-(token, head) scales and are dequantized in VMEM, so the dense fp cache
never exists outside the chip. GQA queries are packed (Hkv, G, D) and both
contractions are batched ``dot_general`` over the kv-head axis.

The ``[lo, hi)`` bounds contract (see ``docs/serving.md`` for the serving
side of it): cache positions ``lo <= p < hi`` of slot ``b`` are attended,
everything else is skipped — the kernel itself is agnostic to *why* a span
is valid. The three lane kinds of the slot-state table all reduce to it:

* full-attention lane, ``len`` tokens cached: ``[0, len)`` (after the
  decode step writes its token: ``[0, len + 1)``);
* windowed lane over a full-length cache: ``[max(0, len - window), len)``;
* ring-buffered lane of width ``ring`` in canonical ring phase (token ``t``
  stored at ``t % ring``, the layout ``serve/kv_slots.py`` establishes at
  assign time and ``layers.attention_block``'s write pointer
  ``cache_index % ring`` preserves): ``[0, min(len, ring))``. Ring storage
  order does not matter to attention (softmax is permutation-invariant over
  the valid set, RoPE is applied at write time), so a per-slot ring offset
  never has to reach the kernel — canonical phase makes it identically
  zero, and occupancy stays a contiguous ``[lo, hi)`` span.

``hi <= lo`` marks a never-attended lane (inactive slot): output zeros.

The ``lut_table`` input (optional) routes the two exponentials through the
AFU's 64-entry piecewise-linear exp — the same table
:func:`repro.kernels.afu.ref.exp_lut_table` feeds the fused-softmax kernel —
modelling the chip's LUT-assisted AFU on the decode path.

**Paged variant** (:func:`tda_paged_decode_attention`): KV lanes live in a
layer-stacked pool of head-major physical pages ``(L, P, Hkv, page_size,
D)`` (``serve/pages.py``; the layout is stated in ``docs/serving.md``)
and a per-slot int32 block table maps logical kv block ``i`` to its
physical page — one page is exactly one kv block. The layer index, bounds
and block tables ride *scalar prefetch*
(``pltpu.PrefetchScalarGridSpec``) so the K/V block specs can DMA the
right physical page of the right layer straight out of the stacked pool
before the kernel body runs; everything else — the ``[lo, hi)``
predication over **logical** block positions, online softmax, in-VMEM
int8 dequant — is the contiguous kernel's (the two share one body; only
the block's axis order differs). Unallocated table entries carry an
out-of-bounds sentinel; their logical blocks always sit outside ``[lo,
hi)`` (a slot's pages are a logical prefix), so predication skips them and
the index map only has to clamp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.afu.ref import LUT_SIZE, lut_exp

NEG_INF = -1e30

__all__ = ["tda_decode_attention", "tda_paged_decode_attention",
           "tda_mixed_attention"]


def _exp(x, table):
    """exp(x) for x <= 0: exact, or the AFU's LUT piecewise-linear exp —
    the very function the fused-softmax kernel models, so the two AFU
    paths cannot drift apart."""
    if table is None:
        return jnp.exp(x)
    return lut_exp(x, table)


def _tda_body(lo, hi, q_ref, k_ref, v_ref, ks_ref, vs_ref, table,
              o_ref, o_acc, m_acc, l_acc, *, bk: int, groups: int,
              quant: bool, paged: bool):
    """Shared kernel body: init / predicated online-softmax block / finish.
    The contiguous and paged kernels differ only in how ``lo``/``hi`` (and
    the K/V blocks) reach the grid step, and in the block's layout: a
    contiguous block is token-major ``(bk, Hkv, D)``, a page is head-major
    ``(Hkv, page_size, D)``; the math is this one function."""
    lead = (0, 0) if paged else (0,)  # unit block dims before the block
    ha = 0 if paged else 1            # kv-head axis of a K/V block
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    blk0 = ki * bk

    # Predication: a block is visited only if it intersects the slot's
    # occupied span [lo, hi). Skipped blocks cost a grid step, not FLOPs or
    # VMEM traffic — decode work follows occupancy, not cache_len.
    @pl.when((blk0 < hi) & (blk0 + bk > lo))
    def _attend():
        q = q_ref[0].astype(jnp.float32)          # (Hq, D)
        k = k_ref[lead].astype(jnp.float32)  # (bk, Hkv, D) | (Hkv, bk, D)
        v = v_ref[lead].astype(jnp.float32)
        if quant:  # in-VMEM dequant: codes * per-(token, head) scale
            k = k * ks_ref[lead][..., None]
            v = v * vs_ref[lead][..., None]
        Hq, D = q.shape
        Hkv = k.shape[ha]
        qg = q.reshape(Hkv, groups, D)
        # scores (Hkv, G, bk): batch over kv heads, contract head_dim
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (ha,))),
            preferred_element_type=jnp.float32) * (1.0 / np.sqrt(D))
        pos = blk0 + jax.lax.iota(jnp.int32, bk)
        valid = (pos >= lo) & (pos < hi)
        s = jnp.where(valid[None, None, :], s, NEG_INF)
        m_prev = m_acc[...].reshape(Hkv, groups)
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = _exp(s - m_new[..., None], table)
        p = jnp.where(valid[None, None, :], p, 0.0)
        alpha = _exp(m_prev - m_new, table)
        l_acc[...] = (l_acc[...].reshape(Hkv, groups) * alpha
                      + p.sum(-1)).reshape(Hq, 1)
        # P@V (Hkv, G, D): contract the block axis, batch over kv heads
        pv = jax.lax.dot_general(
            p, v, (((2,), (1 - ha,)), ((0,), (ha,))),
            preferred_element_type=jnp.float32)
        o_acc[...] = (o_acc[...].reshape(Hkv, groups, D) * alpha[..., None]
                      + pv).reshape(Hq, D)
        m_acc[...] = m_new.reshape(Hq, 1)

    @pl.when(ki == nk - 1)
    def _finish():
        # Never-attended lanes (hi <= lo) keep l == 0 -> output zeros.
        o_ref[0] = (o_acc[...] /
                    jnp.maximum(l_acc[...], 1e-30)).astype(o_ref.dtype)


def _tda_kernel(*refs, bk: int, groups: int, quant: bool, lut: bool,
                paged: bool):
    """One grid step of either layout. Bounds arrive as a scalar-prefetch
    ref indexed by the slot program id; the paged kernel's layer and
    block-table prefetch refs are consumed by the K/V index maps, not the
    body, and predication runs over *logical* block positions in both
    layouts."""
    refs = list(refs)
    if paged:
        refs.pop(0)  # layer
    bounds_ref = refs.pop(0)
    if paged:
        refs.pop(0)  # block table
    q_ref, k_ref, v_ref = refs[:3]
    rest = refs[3:]
    ks_ref = rest.pop(0) if quant else None
    vs_ref = rest.pop(0) if quant else None
    table = rest.pop(0)[...] if lut else None
    o_ref, o_acc, m_acc, l_acc = rest
    b = pl.program_id(0)
    _tda_body(bounds_ref[b, 0], bounds_ref[b, 1], q_ref, k_ref, v_ref,
              ks_ref, vs_ref, table, o_ref, o_acc, m_acc, l_acc,
              bk=bk, groups=groups, quant=quant, paged=paged)


def _live_block(kb, lo, hi, bk: int, nk: int):
    """K/V block a grid step fetches: ``kb`` held inside the blocks that
    intersect ``[lo, hi)``. Steps the body skips repeat the nearest live
    block's index, and Pallas issues no DMA when a block index repeats from
    one step to the next, so K/V traffic follows occupancy as the FLOPs
    do. An empty span pins one block."""
    first = jnp.minimum(lo // bk, nk - 1)
    last = jnp.clip((hi + bk - 1) // bk - 1, first, nk - 1)
    return jnp.clip(kb, first, last)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def tda_decode_attention(q, k, v, bounds, k_scale=None, v_scale=None,
                         lut_table=None, *, block_k: int = 128,
                         interpret: bool = True) -> jnp.ndarray:
    """Fused slot-decode attention.

    q (B, Hq, D); k/v (B, S, Hkv, D) fp or int8 codes (then
    ``k_scale``/``v_scale`` (B, S, Hkv) must be given); bounds (B, 2) int32
    per-slot ``[lo, hi)`` valid spans; ``S % block_k == 0``. Returns
    (B, Hq, D) f32.
    """
    B, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    assert S % block_k == 0, (S, block_k)
    assert Hq % Hkv == 0, (Hq, Hkv)
    quant = k_scale is not None
    lut = lut_table is not None
    nk = S // block_k

    def block(b, kb, bounds):
        return _live_block(kb, bounds[b, 0], bounds[b, 1], block_k, nk)

    in_specs = [
        pl.BlockSpec((1, Hq, D), lambda b, kb, bounds: (b, 0, 0)),
        pl.BlockSpec((1, block_k, Hkv, D),
                     lambda b, kb, bounds: (b, block(b, kb, bounds), 0, 0)),
        pl.BlockSpec((1, block_k, Hkv, D),
                     lambda b, kb, bounds: (b, block(b, kb, bounds), 0, 0)),
    ]
    args = [bounds, q, k, v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, block_k, Hkv),
                         lambda b, kb, bounds: (b, block(b, kb, bounds), 0)),
            pl.BlockSpec((1, block_k, Hkv),
                         lambda b, kb, bounds: (b, block(b, kb, bounds), 0)),
        ]
        args += [k_scale, v_scale]
    if lut:
        in_specs.append(pl.BlockSpec((LUT_SIZE,), lambda b, kb, bounds: (0,)))
        args.append(lut_table)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # bounds ride SMEM prefetch
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, kb, bounds: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),  # o accumulator
            pltpu.VMEM((Hq, 1), jnp.float32),  # running max
            pltpu.VMEM((Hq, 1), jnp.float32),  # running denominator
        ],
    )
    return pl.pallas_call(
        functools.partial(_tda_kernel, bk=block_k, groups=Hq // Hkv,
                          quant=quant, lut=lut, paged=False),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), jnp.float32),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tda_paged_decode_attention(q, k, v, layer, bounds, block_table,
                               k_scale=None, v_scale=None, lut_table=None,
                               *, interpret: bool = True) -> jnp.ndarray:
    """Fused slot-decode attention over a paged KV lane pool, read in place.

    q (B, Hq, D); k/v are the layer-stacked physical page pools (L, P, Hkv,
    page_size, D) in the head-major page layout (``docs/serving.md``) —
    fp or int8 codes (then ``k_scale``/``v_scale`` (L, P, Hkv, page_size)
    must be given); ``layer`` (int32 scalar or (1,)) picks the layer, and
    rides scalar prefetch with the bounds and the table, so no layer view
    of the pool is ever materialized. bounds (B, 2) int32 per-slot
    ``[lo, hi)`` spans in *logical* lane coordinates; block_table (B, n)
    int32 maps logical kv block ``i`` of slot ``b`` to its physical page
    (one page = one kv block; ``block_k == page_size``). Entries whose
    logical block lies outside ``[lo, hi)`` may carry any value —
    including the allocator's out-of-bounds FREE sentinel — because
    predication skips them; the index map clamps so the prefetch itself
    stays in range. Returns (B, Hq, D) f32.
    """
    B, Hq, D = q.shape
    P, Hkv, ps = k.shape[1], k.shape[2], k.shape[3]
    nk = block_table.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    quant = k_scale is not None
    lut = lut_table is not None
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def page(b, kb, bounds_ref, bt_ref):
        live = _live_block(kb, bounds_ref[b, 0], bounds_ref[b, 1], ps, nk)
        return jnp.clip(bt_ref[b, live], 0, P - 1)

    def pool_map(b, kb, lyr, bounds, bt):
        return (lyr[0], page(b, kb, bounds, bt), 0, 0, 0)

    def scale_map(b, kb, lyr, bounds, bt):
        return (lyr[0], page(b, kb, bounds, bt), 0, 0)

    def slot_map(b, kb, lyr, bounds, bt):
        return (b, 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hq, D), slot_map),
        pl.BlockSpec((1, 1, Hkv, ps, D), pool_map),
        pl.BlockSpec((1, 1, Hkv, ps, D), pool_map),
    ]
    args = [layer, bounds, block_table, q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, Hkv, ps), scale_map),
                     pl.BlockSpec((1, 1, Hkv, ps), scale_map)]
        args += [k_scale, v_scale]
    if lut:
        in_specs.append(pl.BlockSpec((LUT_SIZE,), lambda *_: (0,)))
        args.append(lut_table)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, bounds, block table ride SMEM
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, D), slot_map),
        scratch_shapes=[
            pltpu.VMEM((Hq, D), jnp.float32),  # o accumulator
            pltpu.VMEM((Hq, 1), jnp.float32),  # running max
            pltpu.VMEM((Hq, 1), jnp.float32),  # running denominator
        ],
    )
    return pl.pallas_call(
        functools.partial(_tda_kernel, bk=ps, groups=Hq // Hkv,
                          quant=quant, lut=lut, paged=True),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), jnp.float32),
        interpret=interpret,
    )(*args)


def _tda_mixed_kernel(layer_ref, bounds_ref, bt_ref, q_ref, k_ref, v_ref,
                      kr_ref, vr_ref, *rest, bk: int, groups: int,
                      quant: bool, lut: bool, ring: int, window, tq: int,
                      nk: int):
    """Mixed (multi-query) grid step over one ``tq``-column query tile:
    cache blocks ``0..nk-1`` are predicated on the slot's pre-write
    occupancy exactly like decode, then the in-row chunk's key tiles at or
    before the query tile fold in, and the last step normalizes.
    Online-softmax state is per (query column, head) of this tile only —
    scratch rows are laid out (Hkv, tq, G) — so VMEM is bounded by ``tq``,
    not by the chunk width. Pool pages and in-row key tiles are both
    head-major, ``(Hkv, keys, D)``."""
    del layer_ref, bt_ref  # consumed by the in_specs index maps
    rest = list(rest)
    ks_ref = rest.pop(0) if quant else None
    vs_ref = rest.pop(0) if quant else None
    table = rest.pop(0)[...] if lut else None
    o_ref, o_acc, m_acc, l_acc = rest
    b = pl.program_id(0)
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    ci = bounds_ref[b, 0]  # tokens resident in the lane (pre-write)
    nn = bounds_ref[b, 1]  # fresh chunk columns this step
    q0 = qi * tq           # first chunk column of this query tile
    # Query tiles wholly past the fresh columns are garbage the caller
    # ignores: they attend nothing (a decode row in a mixed step works in
    # its first tile only).
    live = q0 < nn

    @pl.when(kb == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    def attend(kblk, vblk, valid):
        """One online-softmax block over ``kblk`` (Hkv, bkk, D) with a
        per-(query, key) ``valid`` mask (tq, bkk)."""
        q = q_ref[0].astype(jnp.float32)  # (tq, Hq, D)
        Hq, D = q.shape[1], q.shape[2]
        Hkv = kblk.shape[0]
        qg = q.reshape(tq, Hkv, groups, D).transpose(1, 0, 2, 3)
        qg = qg.reshape(Hkv, tq * groups, D)
        s = jax.lax.dot_general(
            qg, kblk, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * (1.0 / np.sqrt(D))
        vmask = valid[None, :, None, :]  # (1, tq, 1, bkk)
        s4 = s.reshape(Hkv, tq, groups, -1)
        s = jnp.where(vmask, s4, NEG_INF).reshape(Hkv, tq * groups, -1)
        m_prev = m_acc[...].reshape(Hkv, tq * groups)
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = _exp(s - m_new[..., None], table)
        p = jnp.where(vmask, p.reshape(Hkv, tq, groups, -1),
                      0.0).reshape(Hkv, tq * groups, -1)
        alpha = _exp(m_prev - m_new, table)
        l_acc[...] = (l_acc[...].reshape(Hkv, tq * groups) * alpha
                      + p.sum(-1)).reshape(tq * Hq, 1)
        pv = jax.lax.dot_general(
            p, vblk, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        o_acc[...] = (o_acc[...].reshape(Hkv, tq * groups, D)
                      * alpha[..., None] + pv).reshape(tq * Hq, D)
        m_acc[...] = m_new.reshape(tq * Hq, 1)

    blk0 = kb * bk
    hi = jnp.minimum(ci, ring)  # pre-write occupancy: [0, min(ci, ring))

    @pl.when(live & (kb < nk) & (blk0 < hi))
    def _cache_block():
        k = k_ref[0, 0].astype(jnp.float32)  # (Hkv, bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0, 0][..., None]
            v = v * vs_ref[0, 0][..., None]
        # Lane position r holds absolute token p_r = ci-1 - ((ci-1-r) % ring)
        # (canonical ring phase walked back from the newest resident token);
        # one formula covers full lanes (p_r == r for r < ci) and wrapped
        # rings. Valid iff p_r >= 0 (and inside the window of query p_q).
        r = blk0 + jax.lax.broadcasted_iota(jnp.int32, (tq, bk), 1)
        p_r = (ci - 1) - jnp.mod(ci - 1 - r, ring)
        valid = (p_r >= 0) & (r < ring)
        if window is not None:
            j = q0 + jax.lax.broadcasted_iota(jnp.int32, (tq, bk), 0)
            valid &= p_r > (ci + j - window)
        attend(k, v, valid)

    rt = kb - nk  # in-row key tile of this step (cache steps: negative)

    @pl.when(live & (rt >= 0) & (rt <= qi) & (rt * tq < nn))
    def _row_block():
        kr = kr_ref[0].astype(jnp.float32)  # (Hkv, tq, D)
        vr = vr_ref[0].astype(jnp.float32)
        j = q0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
        i = rt * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)
        valid = (i <= j) & (i < nn)
        if window is not None:
            valid &= (j - i) < window
        attend(kr, vr, valid)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        # Rows with no resident and no fresh keys keep l == 0 -> zeros.
        Hq = q_ref.shape[2]
        D = q_ref.shape[3]
        Hkv = Hq // groups
        o = o_acc[...] / jnp.maximum(l_acc[...], 1e-30)
        o = o.reshape(Hkv, tq, groups, D).transpose(1, 0, 2, 3)
        o_ref[0] = o.reshape(tq, Hq, D).astype(o_ref.dtype)


# Query rows (tile columns x query heads) one mixed grid step holds. The
# accumulators, the f32 query copy and the score block all scale with it;
# on a v5e, 48 heads x 32 columns already overflow the default scoped
# VMEM, and Mosaic compile time grows with the tile as well.
MIXED_TILE_ROWS = 1024


def mixed_query_tile(n_heads: int) -> int:
    """Columns per query tile of the mixed kernel: the largest power of
    two whose (columns x heads) rows fit :data:`MIXED_TILE_ROWS`."""
    t = 1
    while 2 * t * n_heads <= MIXED_TILE_ROWS:
        t *= 2
    return t


def _pad_cols(x, target: int, axis: int = 1):
    if x.shape[axis] == target:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, widths)


def _mixed_maps(*, nk: int, nq: int, tq: int, ps: int, ring: int, P: int):
    """Index maps of the mixed kernel's grid ``(B, nq, nk + nq)``: query
    tile, pool page (K/V and their scales, at the prefetched layer),
    in-row key tile, output tile.

    DMA follows occupancy: a step the kernel skips repeats the previous
    step's block index, so Pallas fetches nothing for it. Query tiles
    past the fresh columns are dead and repeat the last live tile's
    blocks; cache steps past the resident span, and the in-row steps,
    repeat the last resident page; in-row steps past the query tile
    repeat its diagonal key tile.
    """
    def last_tile(b, bounds):
        return jnp.maximum((bounds[b, 1] + tq - 1) // tq - 1, 0)

    def page(b, qi, kb, bounds, bt):
        kb = jnp.where(qi * tq < bounds[b, 1], kb, nk - 1)
        kb = _live_block(kb, 0, jnp.minimum(bounds[b, 0], ring), ps, nk)
        return jnp.clip(bt[b, kb], 0, P - 1)

    def pool_map(b, qi, kb, lyr, bounds, bt):
        return (lyr[0], page(b, qi, kb, bounds, bt), 0, 0, 0)

    def pool_scale_map(b, qi, kb, lyr, bounds, bt):
        return (lyr[0], page(b, qi, kb, bounds, bt), 0, 0)

    def row_map(b, qi, kb, lyr, bounds, bt):
        qt = jnp.minimum(qi, last_tile(b, bounds))
        rt = jnp.where(qi * tq < bounds[b, 1], kb - nk, nq)
        return (b, 0, jnp.clip(rt, 0, qt), 0)

    def q_map(b, qi, kb, lyr, bounds, bt):
        return (b, jnp.minimum(qi, last_tile(b, bounds)), 0, 0)

    def out_map(b, qi, kb, lyr, bounds, bt):
        return (b, qi, 0, 0)

    return q_map, pool_map, pool_scale_map, row_map, out_map


@functools.partial(jax.jit,
                   static_argnames=("ring", "window", "block_q", "interpret"))
def tda_mixed_attention(q, k, v, k_row, v_row, layer, bounds, block_table,
                        k_scale=None, v_scale=None, lut_table=None, *,
                        ring: int, window=None, block_q=None,
                        interpret: bool = True) -> jnp.ndarray:
    """Fused multi-query mixed-step attention over a paged KV lane pool.

    q (B, S, Hq, D) chunk queries (column j of row b sits at absolute
    position ``bounds[b, 0] + j``); k/v the layer-stacked physical page
    pools (L, P, Hkv, page_size, D), head-major, fp or int8 codes (+ pool
    scales (L, P, Hkv, page_size)), read in place at ``layer`` as in
    :func:`tda_paged_decode_attention`; k_row/v_row (B, Hkv, S, D) the
    chunk's own fp keys/values, head-major like the pages; bounds (B, 2)
    int32 ``[cache_index, n_new]``; block_table (B, n) as in
    :func:`tda_paged_decode_attention`. ``ring`` is the logical lane
    width. Chunked-prefill attention is predicated the same way decode is:
    cache blocks outside ``[0, min(cache_index, ring))`` are skipped, and
    the in-row chunk rides extra grid steps, one per key tile at or before
    the query tile. The chunk axis is cut into ``block_q``-column query
    tiles (padded up to a multiple; default :func:`mixed_query_tile`), so
    the kernel's VMEM does not grow with S. Columns ``>= n_new`` are
    garbage the caller ignores. Returns (B, S, Hq, D) f32.
    """
    B, S, Hq, D = q.shape
    P, Hkv, ps = k.shape[1], k.shape[2], k.shape[3]
    nk = block_table.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    quant = k_scale is not None
    lut = lut_table is not None
    tq = min(block_q or mixed_query_tile(Hq), S)
    nq = -(-S // tq)
    Sp = nq * tq
    q = _pad_cols(q, Sp)
    k_row, v_row = (_pad_cols(x, Sp, axis=2) for x in (k_row, v_row))
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    q_map, pool_map, pool_scale_map, row_map, out_map = _mixed_maps(
        nk=nk, nq=nq, tq=tq, ps=ps, ring=ring, P=P)
    in_specs = [
        pl.BlockSpec((1, tq, Hq, D), q_map),
        pl.BlockSpec((1, 1, Hkv, ps, D), pool_map),
        pl.BlockSpec((1, 1, Hkv, ps, D), pool_map),
        pl.BlockSpec((1, Hkv, tq, D), row_map),
        pl.BlockSpec((1, Hkv, tq, D), row_map),
    ]
    args = [layer, bounds, block_table, q, k, v, k_row, v_row]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, Hkv, ps), pool_scale_map),
                     pl.BlockSpec((1, 1, Hkv, ps), pool_scale_map)]
        args += [k_scale, v_scale]
    if lut:
        in_specs.append(pl.BlockSpec((LUT_SIZE,), lambda *_: (0,)))
        args.append(lut_table)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nq, nk + nq),  # cache blocks, then in-row key tiles
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tq, Hq, D), out_map),
        scratch_shapes=[
            pltpu.VMEM((tq * Hq, D), jnp.float32),  # o accumulator
            pltpu.VMEM((tq * Hq, 1), jnp.float32),  # running max
            pltpu.VMEM((tq * Hq, 1), jnp.float32),  # running denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_tda_mixed_kernel, bk=ps, groups=Hq // Hkv,
                          quant=quant, lut=lut, ring=ring, window=window,
                          tq=tq, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sp, Hq, D), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:, :S]
