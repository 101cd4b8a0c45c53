"""In-place write of new K/V into the paged KV pool.

The pool's layout is stated once, in ``docs/serving.md``: each paged kv
leaf is a layer-stacked pool of head-major pages ``(L, P, Hkv, page_size,
D)`` (int8 scales ``(L, P, Hkv, page_size)``), so a page's minor dims
``(page_size, D)`` hold no padding in row-major order and the pool's HBM
layout is the one Mosaic reads. :func:`paged_kv_write` is the only writer a
served step needs: a Pallas kernel aliased onto the stacked pool that reads
and writes only the native tiles holding the new tokens, addressed by
``(layer, page, tile)``. No layer of the pool is sliced, relaid out or
written back around it.

A row ``b`` writes its columns ``j`` in ``[max(n_new - ring, 0), n_new)``
at logical lane position ``(cache_index + j) % ring`` — only the last
``ring`` columns of a wrapping chunk write, so each lane position is
written once — through the block table: position ``p`` lives in page
``block_table[b, p // page_size]`` at offset ``p % page_size``. Rows with
``n_new == 0`` and positions whose page is out of range (the allocator's
FREE sentinel) write nothing: the semantics of the ``mode="drop"``
scatter, which :func:`paged_kv_write_reference` keeps for the dense and
the sharded paths.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_kv_write", "paged_kv_write_reference"]


def _tile_rows(dtype, page_size: int) -> int:
    """Rows of one native ``(rows, 128)`` tile of ``dtype`` — 8 for 32-bit,
    16 for 16-bit, 32 for 8-bit types; a DMA into the pool starts and ends
    on them — capped at the page."""
    rows = min(32 // jnp.dtype(dtype).itemsize, page_size)
    assert page_size % rows == 0, (page_size, rows)
    return rows


def _write_positions(cache_index, n_new, block_table, *, S: int,
                     ring: int, page_size: int, num_pages: int):
    """Physical ``(page, offset)`` of every column of a step's rows:
    ``(B, S)`` each. Columns that write nothing (past ``n_new``, shadowed
    by a later column of a wrapping chunk, or on an unallocated page)
    carry page ``num_pages``, which every writer drops."""
    ci = jnp.reshape(cache_index, (-1, 1)).astype(jnp.int32)
    nn = jnp.reshape(n_new, (-1, 1)).astype(jnp.int32)
    cols = jax.lax.iota(jnp.int32, S)[None, :]
    lanepos = (ci + cols) % ring
    wvalid = (cols < nn) & (cols >= nn - ring)
    page = jnp.take_along_axis(block_table, lanepos // page_size, axis=1)
    page = jnp.where(wvalid & (page >= 0) & (page < num_pages), page,
                     num_pages)
    return page.astype(jnp.int32), lanepos % page_size


def paged_kv_write_reference(pools: Sequence[jnp.ndarray],
                             news: Sequence[jnp.ndarray], layer,
                             cache_index, n_new, block_table, *,
                             ring: int) -> Tuple[jnp.ndarray, ...]:
    """The write as one XLA scatter per leaf into the stacked pool at
    ``(layer, page, :, offset)`` (``layer=None``: an unstacked pool).
    ``news`` are token-major ``(B, S, Hkv, ...)``. Used where the TDA
    kernels are not (the dense path, a tensor-parallel mesh)."""
    stacked = layer is not None
    B, S = news[0].shape[:2]
    ps = pools[0].shape[-2]  # the first leaf is K: (..., page_size, D)
    P = pools[0].shape[int(stacked)]
    page, off = _write_positions(cache_index, n_new, block_table, S=S,
                                 ring=ring, page_size=ps, num_pages=P)
    page, off = page.reshape(-1), off.reshape(-1)
    out = []
    for pool, new in zip(pools, news):
        vals = new.astype(pool.dtype).reshape((B * S,) + new.shape[2:])
        if stacked:
            pool = pool.at[layer, page, :, off].set(vals, mode="drop")
        else:
            pool = pool.at[page, :, off].set(vals, mode="drop")
        out.append(pool)
    return tuple(out)


def _decode_write_kernel(layer_ref, page_ref, off_ref, home_ref, *refs,
                         n: int, B: int, P: int, rows: Tuple[int, ...]):
    """Single-token rows, all at once: read the tile holding each row's
    token (each distinct tile once), set the token's row (``(Hkv, D)``
    leaves) or lane (``(Hkv,)`` scale leaves), write the tiles back.
    ``home[i, b]`` is the first row whose token lies in row ``b``'s tile
    of leaf ``i``, so rows that meet in one tile update one copy of it."""
    # The pool inputs alias the outputs, and the ordering operand is
    # never read.
    news, refs = refs[:n], refs[2 * n + 1:]
    outs, bufs, sem = refs[:n], refs[n:2 * n], refs[2 * n]
    lyr = layer_ref[0]

    def live(b):
        return (page_ref[b] >= 0) & (page_ref[b] < P)

    def first_row(i, b):
        return pl.multiple_of((off_ref[b] // rows[i]) * rows[i], rows[i])

    def tile(i, b):
        """Leaf i's pool tile holding row b's token."""
        if len(outs[i].shape) == 5:  # (L, P, Hkv, ps, D): a row tile
            return outs[i].at[lyr, page_ref[b], :,
                              pl.ds(first_row(i, b), rows[i]), :]
        return outs[i].at[lyr, page_ref[b]]  # (L, P, Hkv, ps): the page

    def each_row(body):  # a loop, not B unrolled copies of the body
        def step(b, carry):
            body(b)
            return carry
        jax.lax.fori_loop(0, B, step, 0)

    def copies(inward, op):
        def body(b):
            for i in range(n):
                @pl.when(live(b) & (home_ref[i, b] == b))
                def _():
                    ends = (tile(i, b), bufs[i].at[b])
                    op(pltpu.make_async_copy(
                        *(ends if inward else ends[::-1]), sem.at[i, b]))
        each_row(body)

    def update(b):
        @pl.when(live(b))
        def _():
            for i in range(n):
                h = home_ref[i, b]
                cur = bufs[i][h]
                at = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
                if cur.ndim == 3:  # (Hkv, rows, D): set one row
                    val = jnp.broadcast_to(news[i][b][:, None, :],
                                           cur.shape)
                    hit = at == off_ref[b] - first_row(i, b)
                else:              # (Hkv, ps): set one lane
                    val = jnp.broadcast_to(news[i][b], cur.shape)
                    hit = at == off_ref[b]
                bufs[i][h] = jnp.where(hit, val.astype(cur.dtype), cur)

    copies(True, lambda c: c.start())
    copies(True, lambda c: c.wait())
    each_row(update)
    copies(False, lambda c: c.start())
    copies(False, lambda c: c.wait())


def _chunk_write_kernel(layer_ref, bounds_ref, bt_ref, *refs, n: int,
                        P: int, ps: int, R: int, ring: int, Sp: int):
    """One row's chunk: a loop over the tiles its written lane positions
    touch. The positions form at most two runs (the second after a ring
    wrap); within a run, lane position ``p`` takes chunk column ``p + c``.
    Each tile is read, its positions in the run are set from a 2R-column
    window of the chunk rolled into place, and it is written back before
    the next tile is read."""
    # The pool inputs alias the outputs, and the ordering operand is
    # never read.
    news, refs = refs[:n], refs[2 * n + 1:]
    outs, tbufs, wbufs, sem = (refs[:n], refs[n:2 * n], refs[2 * n:3 * n],
                               refs[3 * n])
    b = pl.program_id(0)
    lyr = layer_ref[0]
    ci, nn = bounds_ref[b, 0], bounds_ref[b, 1]
    j0 = jnp.maximum(nn - ring, 0)
    m = nn - j0                       # columns that write
    s = (ci + j0) % ring              # lane position of column j0
    end_a = jnp.minimum(s + m, ring)  # run A: [s, end_a); run B: [0, len_b)
    len_b = jnp.maximum(s + m - ring, 0)
    n_a = jnp.where(m > 0, (end_a + R - 1) // R - s // R, 0)
    n_b = (len_b + R - 1) // R

    def body(t, carry):
        in_a = t < n_a
        r0 = jnp.where(in_a, s // R + t, t - n_a) * R
        lo = jnp.where(in_a, s, 0)
        hi = jnp.where(in_a, end_a, len_b)
        c = jnp.where(in_a, j0 - s, j0 - s + ring)  # column = position + c
        page = bt_ref[b, r0 // ps]
        o0 = pl.multiple_of(r0 % ps, R)
        a = jnp.minimum((jnp.maximum(r0 + c, 0) // R) * R, Sp - 2 * R)
        a = pl.multiple_of(a, R)
        shift = jnp.mod(a - r0 - c, 2 * R)  # window row r0 + c - a -> row 0

        @pl.when((page >= 0) & (page < P))
        def _():
            copies = []
            for i in range(n):
                dst = outs[i].at[lyr, page, :, pl.ds(o0, R), :]
                copies += [
                    pltpu.make_async_copy(dst, tbufs[i], sem.at[0, i]),
                    pltpu.make_async_copy(
                        news[i].at[b, :, pl.ds(a, 2 * R), :], wbufs[i],
                        sem.at[1, i])]
            for cp in copies:
                cp.start()
            for cp in copies:
                cp.wait()
            back = []
            for i in range(n):
                w = wbufs[i][...].astype(jnp.float32)
                w = pltpu.roll(w, shift, 1)[:, :R, :]
                cur = tbufs[i][...]
                pos = r0 + jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
                hit = (pos >= lo) & (pos < hi)
                tbufs[i][...] = jnp.where(hit, w.astype(cur.dtype), cur)
                back.append(pltpu.make_async_copy(
                    tbufs[i], outs[i].at[lyr, page, :, pl.ds(o0, R), :],
                    sem.at[0, i]))
            for cp in back:
                cp.start()
            for cp in back:
                cp.wait()
        return carry

    jax.lax.fori_loop(0, n_a + n_b, body, 0)


@functools.partial(jax.jit, static_argnames=("ring", "interpret"))
def paged_kv_write(pools: Sequence[jnp.ndarray], news: Sequence[jnp.ndarray],
                   layer, cache_index, n_new, block_table,
                   after: Optional[jnp.ndarray] = None, *, ring: int,
                   interpret: bool = True) -> Tuple[jnp.ndarray, ...]:
    """Write a step's new K/V into the stacked page pools in place.

    ``pools``: stacked pools ``(L, P, Hkv, page_size, D)`` and, for int8
    KV, their scales ``(L, P, Hkv, page_size)``; ``news``: the matching
    token-major values ``(B, S, Hkv, ...)``; ``layer`` an int32 scalar;
    ``cache_index`` / ``n_new`` (B,) per-row lane depth and fresh columns
    (0 for a row that writes nothing); ``block_table`` (B, n). Each pool
    is aliased to its output, so the compiled program updates it where it
    lies. ``after``, if given, is an operand the kernel never reads: it
    orders the write after whatever produced it (the mixed step's
    attention over the pre-write pool), so XLA need not keep the old pool
    alive in a copy. Scale leaves are written by single-token steps only
    (int8 KV decodes; it has no mixed step). Returns the new pools.
    """
    pools, news = tuple(pools), tuple(news)
    n = len(pools)
    B, S = news[0].shape[:2]
    P, ps = pools[0].shape[1], pools[0].shape[3]
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    ci = jnp.reshape(cache_index, (-1,)).astype(jnp.int32)
    nn = jnp.reshape(n_new, (-1,)).astype(jnp.int32)
    bt = block_table.astype(jnp.int32)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out_shape = tuple(jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools)
    if after is None:
        after = jnp.zeros((1,), jnp.int32)
    if S == 1:
        page, off = _write_positions(ci, nn, bt, S=1, ring=ring,
                                     page_size=ps, num_pages=P)
        page, off = page[:, 0], off[:, 0]
        rows = tuple(_tile_rows(p.dtype, ps) if p.ndim == 5 else ps
                     for p in pools)
        # Rows meeting in one tile of a leaf share its copy; the first of
        # them is its home.
        same_page = page[:, None] == page[None, :]
        home = jnp.stack([jnp.argmax(
            same_page & ((off // r)[:, None] == (off // r)[None, :]), axis=1)
            for r in rows]).astype(jnp.int32)
        # New values head-major in VMEM: (B, Hkv, D) rows, (B, Hkv, 1)
        # scale columns.
        vals = tuple(x[:, 0] if x.ndim == 4 else x[:, 0, :, None]
                     for x in news)
        kernel = functools.partial(_decode_write_kernel, n=n, B=B, P=P,
                                   rows=rows)
        prefetch = (layer, page, off, home)
        in_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)] * n
        scratch = [pltpu.VMEM((B,) + p.shape[2:3] + (r,) + p.shape[4:]
                              if p.ndim == 5 else (B,) + p.shape[2:],
                              p.dtype) for p, r in zip(pools, rows)]
        scratch.append(pltpu.SemaphoreType.DMA((n, B)))
        grid = (1,)
    else:
        assert all(p.ndim == 5 for p in pools), \
            "scale leaves are written by single-token steps only"
        R = max(_tile_rows(p.dtype, ps) for p in pools)
        Sp = max(-(-S // R) * R, 2 * R)
        vals = tuple(jnp.pad(jnp.swapaxes(x, 1, 2),
                             ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
                     for x in news)  # (B, Hkv, Sp, D)
        kernel = functools.partial(_chunk_write_kernel, n=n, P=P, ps=ps,
                                   R=R, ring=ring, Sp=Sp)
        prefetch = (layer, jnp.stack([ci, nn], axis=1), bt)
        in_specs = [any_spec] * n
        scratch = [pltpu.VMEM(p.shape[2:3] + (k * R,) + p.shape[4:],
                              p.dtype) for k in (1, 2) for p in pools]
        scratch.append(pltpu.SemaphoreType.DMA((2, n)))
        grid = (B,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=grid,
        in_specs=in_specs + [any_spec] * (n + 1),
        out_specs=tuple([any_spec] * n), scratch_shapes=scratch)
    args = prefetch + vals + pools + (after,)
    aliases = {len(prefetch) + n + i: i for i in range(n)}
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)
