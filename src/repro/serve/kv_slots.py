"""Universal slot-state table: per-request cache lanes for continuous
batching, for *every* cache kind a model can carry.

The decode-side counterpart of the paper's dynamic batching: a fixed-capacity
``SlotKVCache`` holds ``num_slots`` independent lanes inside one fixed-shape
model cache (batch dim = slots), so the engine's decode step is a single
jitted call over *all* slots regardless of which requests occupy them.
Request lifecycles only touch host-side metadata plus a lane copy:

* ``assign`` / ``assign_many`` gather request state out of a prefill cache —
  rows of a packed prefill interleave several requests, and
  ``request_slots`` says where each one's tokens landed — and write it into
  free lanes; a whole admission round is one fused per-leaf gather +
  scatter, not a per-slot loop.
* ``release`` just flips the host-side ``active`` bit; the stale lane is
  masked out of the decode step via ``slot_mask`` and overwritten by the
  next ``assign``.

Every cache leaf is typed by a *lane spec* from
:meth:`repro.models.transformer.Model.cache_lane_specs`:

* ``"kv"`` — per-token lanes (sequence axis right after the batch axis).
  Full-attention leaves have width ``cache_len``; windowed leaves are ring
  buffers of width ``ring = min(window, cache_len)``. Assign gathers the
  request's last ``min(len, ring)`` tokens into **canonical ring phase**
  (token ``t`` at position ``t % ring``), which is exactly the phase of the
  decode step's write pointer ``cache_index % ring`` — the per-slot ring
  offset is folded into the gather once, so it is identically zero on the
  jitted decode path and the TDA kernel's ``[lo, hi)`` occupancy bounds
  stay ``[0, min(len, ring))``. The source must be a *full-length* prefill
  cache (``Model.init_cache(..., ring=False)``) so every row position is
  addressable.
* ``"state"`` — fixed-shape recurrent states (RG-LRU hidden state, SSD
  state, causal-conv taps): no sequence segment to slice; assign is a
  batched gather of whole per-row states (the engine right-aligns recurrent
  prefill rows so the end-of-row state *is* the end-of-request state) and
  the per-token ``advance`` is a no-op on the lane contents.

``page_size`` switches the kv lanes to the **paged layout**
(:mod:`repro.serve.pages`): each kv leaf becomes a pool of
``page_size``-token physical pages, head-major — ``(L?, num_pages, Hkv,
page_size, D)``, int8 scales ``(L?, num_pages, Hkv, page_size)``; the
layout and why are stated in ``docs/serving.md`` ("Page layout") — and a
per-slot block table maps logical page ``p // page_size`` to its physical
page. Logical lane coordinates — canonical ring phase, the
TDA ``[lo, hi)`` bounds — are untouched; ``assign``/``release`` also
allocate/free pages, and the fused assign copy scatters through the block
tables (unallocated entries carry the out-of-bounds ``FREE`` sentinel, so
their updates are dropped). ``"state"`` lanes are never paged.

Prefix sharing rides the same fused copy: an assignment may carry a
*destination offset* — the first ``offset`` lane positions are backed by
shared pages another request already wrote (``PagePool.map_shared``), the
prefill computed only the suffix, and the scatter drops every position
outside ``[offset, total)`` so shared pages are never touched. The two
device-side helpers the sharing machinery needs also live here:
:meth:`copy_pages` (the copy half of copy-on-write) and
:meth:`gather_prefix` (materialize a dequantized prefix-KV view out of
the pool for the suffix prefill's attention).

Per-step slot occupancy (`utilization()`) is the serving analogue of the
paper's PE-utilization metric: idle lanes are idle PEs under a shared weight
sweep; in paged mode ``pool.memory_ratio()`` is the matching *footprint*
metric (pages in use over pool capacity).
"""
from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tda.ops import gather_paged_lanes
from repro.models.layers import kv_dequantize
from repro.models.transformer import Model
from repro.serve.pages import PagePool

__all__ = ["SlotKVCache", "SlotStateTable"]

# (slot, request, row, start, length[, offset]) — one admitted request's
# lane copy. ``offset`` (default 0) is the lane position the copied
# segment starts at: positions [0, offset) are already backed by shared
# prefix pages (paged mode only) and must not be written.
Assignment = Tuple[int, Any, int, int, int]


class SlotKVCache:
    """Fixed-capacity table of per-request cache lanes (any cache kind).

    ``caches`` is a regular model cache pytree with batch dim ``num_slots``;
    per-token leaves have sequence dim ``cache_len`` (or their ring width);
    lane ``s`` belongs to whatever request ``request[s]`` points at.
    ``lengths[s]`` is the number of tokens request ``s`` has pushed through
    the model (== the decode step's ``cache_index``; for ring lanes the
    write pointer is ``lengths % ring``, for state lanes it only feeds RoPE
    positions).
    """

    def __init__(self, model: Model, num_slots: int, cache_len: int,
                 page_size: Optional[int] = None, pool_frac: float = 1.0,
                 page_cap: Optional[int] = None, mesh=None):
        if num_slots <= 0 or cache_len <= 0:
            raise ValueError("num_slots and cache_len must be positive")
        self.mesh = mesh
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.page_size = page_size
        cfg = model.cfg
        self._dtype = cfg.compute_dtype
        self._stacked = cfg.uniform_layers  # leaves carry a leading L dim
        self.specs = model.cache_lane_specs()  # "kv" | "state" per leaf
        ba = 1 if self._stacked else 0
        # Shapes only — materializing the dense cache just to read widths
        # would transiently hold dense + pool memory at once, defeating
        # the footprint the paged layout exists to shrink.
        template = jax.eval_shape(
            lambda: model.init_cache(num_slots, cache_len))
        # Per-leaf logical lane width (kv leaves only; 0 for state leaves).
        self.widths = jax.tree.map(
            lambda leaf, spec: leaf.shape[ba + 1] if spec == "kv" else 0,
            template, self.specs)
        self.pool: Optional[PagePool] = None
        if page_size is not None:
            kv_widths = [w for w in jax.tree.leaves(self.widths) if w > 0]
            self.pool = PagePool(kv_widths, num_slots, page_size,
                                 pool_frac=pool_frac, page_cap=page_cap)

            def paged_leaf(leaf, spec, w):
                if spec != "kv":
                    return jnp.zeros(leaf.shape, leaf.dtype)
                P = self.pool.classes[w].num_pages
                # (L?, B, W, Hkv, ...) lanes -> (L?, P, Hkv, page_size,
                # ...) head-major pages
                shape = (leaf.shape[:ba] + (P, leaf.shape[ba + 2],
                                            page_size)
                         + leaf.shape[ba + 3:])
                return jnp.zeros(shape, leaf.dtype)

            def make():
                return jax.tree.map(paged_leaf, template, self.specs,
                                    self.widths)
        else:
            def make():
                return model.init_cache(num_slots, cache_len)
        # Tensor-parallel placement: kv leaves (pool pages or contiguous
        # lanes) are KV-head-sharded over the mesh's ``model`` axis — each
        # rank owns its heads' slice of every page — while block tables
        # and all host-side slot metadata stay replicated. The zeros are
        # created straight into those shards (never whole on one device),
        # and placing the leaves here (not in the engine) means every
        # downstream jit (the fused assign copy, CoW page copiers, the
        # decode step) sees committed shardings and keeps them.
        from repro.launch.mesh import tensor_parallel_size
        if tensor_parallel_size(mesh) > 1:
            from repro.launch import sharding as shd
            specs = shd.slot_cache_specs(jax.eval_shape(make), mesh,
                                         paged=self.pool is not None)
            self.caches = jax.jit(make,
                                  out_shardings=shd.named(specs, mesh))()
        else:
            self.caches = make()
        # host-side slot metadata
        self.active = np.zeros(num_slots, bool)
        self.lengths = np.zeros(num_slots, np.int32)
        self.request: List[Optional[Any]] = [None] * num_slots
        # Lane copies run as one fused jit (one compile per source width);
        # donating the slot cache lets accelerators update it in place (CPU
        # doesn't implement donation, so skip the warning there).
        donate = (0,) if jax.default_backend() != "cpu" else ()
        self._donate = donate
        fn = self._copy_lane_paged if self.pool is not None \
            else self._copy_lane
        self._copy = jax.jit(fn, donate_argnums=donate)
        # Lazily built per width class: the device half of copy-on-write
        # (one jitted whole-page copy across every kv leaf of that width)
        # and the jitted prefix-KV gather for suffix prefills.
        self._copiers: dict = {}
        self._prefix_gather = jax.jit(self._gather_prefix_fn) \
            if self.pool is not None else None

    # ------------------------------------------------------------------

    def free_slots(self) -> np.ndarray:
        return np.flatnonzero(~self.active)

    def utilization(self) -> float:
        return float(self.active.mean())

    def _gather_lanes(self, src, rows, starts, lengths, width, out_width,
                      dtype, offs=None):
        """Gather assignment segments into canonical ring phase: lane
        position ``p`` holds token ``base + ((p - base) % width)`` with
        ``base = max(len - width, 0)`` — for full lanes (``width`` >= len)
        this degenerates to token ``p`` at position ``p``. Positions past
        ``min(len, width)`` (and the ``out_width > width`` tail of a
        page-quantized lane) are invalid (zeroed by the contiguous copy,
        dropped by the paged scatter). Shared by the contiguous and paged
        fused copies so the phase math cannot drift between layouts.

        ``offs`` (suffix assigns onto a shared prefix): ``lengths`` is the
        *total* lane depth but the source row holds only the suffix
        tokens ``[offs, lengths)`` starting at row position ``starts``;
        positions below ``offs`` are invalid (they live in shared pages).
        Sharing guarantees ``lengths <= width`` whenever ``offs > 0``
        (an unwrapped lane), so the ring-phase base is 0 on that path.
        Returns ``(lanes, valid)``: the gathered values and the validity
        mask, both over ``(J, out_width)``."""
        ba = 1 if self._stacked else 0  # batch axis of every cache leaf
        J = rows.shape[0]
        wsrc = src.shape[ba + 1]
        if offs is None:
            offs = jnp.zeros_like(lengths)
        base = jnp.maximum(lengths - width, 0)[:, None]  # (J, 1)
        pgrid = jnp.arange(out_width)[None, :]  # (1, out_width)
        tok = base + jnp.mod(pgrid - base, width)  # (J, out_width) token ix
        # source row position of token ``tok`` (row holds [offs, lengths))
        seq_pos = starts[:, None] + tok - offs[:, None]
        valid = ((pgrid < jnp.minimum(lengths, width)[:, None])
                 & (pgrid >= offs[:, None]))
        sel = jnp.take(src, rows, axis=ba)  # (L?, J, wsrc, ...)
        idx = jnp.clip(seq_pos, 0, wsrc - 1)
        ishape = (1,) * ba + (J, out_width) + (1,) * (sel.ndim - ba - 2)
        lanes = jnp.take_along_axis(sel, idx.reshape(ishape),
                                    axis=ba + 1)  # (L?, J, out_width, ...)
        vshape = (1,) * ba + (J, out_width) + (1,) * (lanes.ndim - ba - 2)
        lanes = jnp.where(valid.reshape(vshape), lanes, 0).astype(dtype)
        return lanes, valid

    def _copy_lane(self, dst_caches, src_caches, slots, rows, starts,
                   lengths, offs=None):
        """Copy every assignment j's state out of ``src[rows[j]]`` into lane
        ``slots[j]`` in one fused gather + scatter per cache leaf — no
        per-slot Python loop, no O(num_slots) one-hot select. Static shapes
        throughout, so one jit covers every admission round of a given size
        and source width.

        * ``"kv"`` leaves: gather the segment's last ``min(len, ring)``
          tokens (``ring`` = the leaf's own width) from row positions
          ``[starts[j], starts[j] + lengths[j])`` into canonical ring phase
          (:meth:`_gather_lanes`).
        * ``"state"`` leaves: gather the whole per-row state.
        """
        ba = 1 if self._stacked else 0  # batch axis of every cache leaf

        def per_leaf(dst, src, spec):
            if spec == "state":
                sel = jnp.take(src, rows, axis=ba)  # (L?, J, ...)
                if ba == 0:
                    return dst.at[slots].set(sel.astype(dst.dtype))
                return dst.at[:, slots].set(sel.astype(dst.dtype))
            # "kv": per-token lane; ring width is the leaf's own seq dim.
            # (offs is always zero here: prefix sharing is paged-only, so
            # whole-lane overwrite with zeroed invalid positions is safe.)
            ring = dst.shape[ba + 1]
            lanes, _ = self._gather_lanes(src, rows, starts, lengths, ring,
                                          ring, dst.dtype)
            # Padding entries carry slot == num_slots: out-of-bounds
            # scatter updates are dropped (JAX default), so they cost
            # nothing and real slots stay unique.
            if ba == 0:
                return dst.at[slots].set(lanes)
            return dst.at[:, slots].set(lanes)

        return jax.tree.map(per_leaf, dst_caches, src_caches, self.specs)

    def _copy_lane_paged(self, dst_caches, src_caches, slots, rows, starts,
                         lengths, offs, tables):
        """Paged variant of :meth:`_copy_lane`: the gather side
        (:meth:`_gather_lanes` over the leaf's *logical* width) is shared;
        the scatter side routes every lane position through the slot's
        block table — position ``p`` lands in physical page ``bt[slot, p //
        page_size]`` at offset ``p % page_size``. Sentinel table entries
        (unallocated pages, and the padded ``slot == num_slots`` row)
        name a page out of bounds, which the scatter drops — and so does
        every position outside ``[offs, total)``, which is what keeps
        shared prefix pages (below ``offs``) byte-identical while the
        suffix lands around them."""
        ba = 1 if self._stacked else 0
        ps = self.page_size

        def per_leaf(dst, src, spec, w):
            if spec == "state":
                sel = jnp.take(src, rows, axis=ba)
                if ba == 0:
                    return dst.at[slots].set(sel.astype(dst.dtype))
                return dst.at[:, slots].set(sel.astype(dst.dtype))
            bt = tables[w]  # (num_slots + 1, lane_pages), sentinel row last
            W = bt.shape[1] * ps  # page-quantized width (tail never read)
            lanes, valid = self._gather_lanes(src, rows, starts, lengths,
                                              w, W, dst.dtype, offs)
            P = dst.shape[ba]
            pos = jnp.arange(W)
            # Invalid positions name no page (dropped) instead of writing
            # zeros: a lane's pages may be shared with other slots.
            page = jnp.where(valid, jnp.take(bt, slots, axis=0)[:, pos // ps],
                             P).reshape(-1)
            off = jnp.broadcast_to(pos % ps, valid.shape).reshape(-1)
            J = valid.shape[0]
            if ba == 0:
                vals = lanes.reshape((J * W,) + lanes.shape[2:])
                return dst.at[page, :, off].set(vals, mode="drop")
            # (L, J, W, Hkv, ...) -> (J * W, L, Hkv, ...): the indexed
            # axes lead the update, as numpy puts split index axes first.
            vals = jnp.moveaxis(lanes.reshape(
                (lanes.shape[0], J * W) + lanes.shape[3:]), 0, 1)
            return dst.at[:, page, :, off].set(vals, mode="drop")

        return jax.tree.map(per_leaf, dst_caches, src_caches, self.specs,
                            self.widths)

    def assign(self, slot: int, request, src_caches, row: int, start: int,
               length: int) -> None:
        """Claim ``slot`` for ``request``; copy its cached state — the KV
        segment ``src_caches[row, start:start+length]`` for per-token lanes,
        the whole ``src_caches[row]`` state for recurrent lanes — into the
        lane."""
        self.assign_many([(slot, request, row, start, length)], src_caches)

    def assign_many(self, assignments: Sequence[Assignment],
                    src_caches) -> None:
        """Claim several slots in one fused lane copy.

        ``assignments`` is a list of ``(slot, request, row, start, length)``
        (optionally ``+ (offset,)``) drawn from ONE prefill's
        ``src_caches``. For per-token lanes, rows of a packed prefill
        interleave several requests and segment masking made each one's
        K/V identical to an unpacked computation; the source must be
        full-length (``init_cache(..., ring=False)``) so windowed segments
        are addressable. For recurrent state lanes the engine prefills one
        request per row (right-aligned, padding masked to identity
        updates), so ``src_caches[row]``'s end-of-row state is exactly the
        request's state. Either way the gathered lanes decode exactly as
        if each request had been prefilled alone, and the whole admission
        round is a single jitted gather+scatter instead of one dispatch
        per request. A reassigned lane is overwritten wholesale — no state
        survives a release→assign cycle.

        A nonzero ``offset`` (paged mode only) means lane positions
        ``[0, offset)`` are already backed by shared prefix pages the
        engine mapped via ``PagePool.map_shared``: the source row holds
        only the suffix ``[offset, offset + length)``, the lane's total
        depth becomes ``offset + length``, and shared pages overlapping
        the write range ``[offset, total]`` are copy-on-written first so
        no other holder ever observes the write.
        """
        if not assignments:
            return
        norm = [(a[0], a[1], a[2], a[3], a[4],
                 a[5] if len(a) > 5 else 0) for a in assignments]
        for slot, _, _, _, length, off in norm:
            if self.active[slot]:
                raise ValueError(f"slot {slot} is already occupied")
            if off and self.pool is None:
                raise ValueError("offset assigns require the paged layout")
            if off + length > self.cache_len:
                raise ValueError(
                    f"request length {off + length} exceeds cache_len "
                    f"{self.cache_len}")
        slots = [a[0] for a in norm]
        if len(set(slots)) != len(slots):
            raise ValueError(f"duplicate slots in one admission: {slots}")
        if self.pool is not None:
            # Page in each lane's logical prefix before the fused copy —
            # one position past the prompt, so the page the engine's
            # admission reserved for the first decode write is actually
            # *held*, not just virtually counted (otherwise an older lane
            # growing in the same step could still snatch it) — and
            # copy-on-write any shared page the suffix (or that first
            # decode write) lands in. An exhausted pool rolls the whole
            # round back (the engine's page budget makes that unreachable
            # in normal operation).
            attempted = []
            copies = []
            try:
                for slot, _, _, _, length, off in norm:
                    total = off + length
                    attempted.append(slot)
                    self.pool.alloc_prefix(slot,
                                           min(total + 1, self.cache_len))
                    if off:
                        # [off, total] — the suffix scatter plus the first
                        # decode write (position ``total``, ring-wrapped).
                        copies += self.pool.make_range_writable(
                            slot, off, total + 1)
            except RuntimeError:
                for slot in attempted:
                    self.pool.release(slot)
                raise
            if copies:
                self.copy_pages(copies)
        # Pad the round to a power of two: bounds jit variants of the fused
        # copy to log2(num_slots)+1 per source width (same idiom as the
        # engine's packed-prefill row padding). Padding entries scatter to
        # the out-of-bounds sentinel slot and are dropped.
        J = 1 << (len(norm) - 1).bit_length()
        pad = J - len(norm)
        args = (
            jnp.asarray(slots + [self.num_slots] * pad, jnp.int32),
            jnp.asarray([a[2] for a in norm] + [0] * pad, jnp.int32),
            jnp.asarray([a[3] for a in norm] + [0] * pad, jnp.int32),
            jnp.asarray([a[4] + a[5] for a in norm] + [0] * pad, jnp.int32),
            jnp.asarray([a[5] for a in norm] + [0] * pad, jnp.int32))
        if self.pool is not None:
            self.caches = self._copy(self.caches, src_caches, *args,
                                     self.pool.device_tables())
        else:
            self.caches = self._copy(self.caches, src_caches, *args)
        for slot, request, _, _, length, off in norm:
            self.active[slot] = True
            self.lengths[slot] = off + length
            self.request[slot] = request

    # -- prefix-sharing device helpers ---------------------------------

    def copy_pages(self, copies: Sequence[Tuple[int, int, int]]) -> None:
        """Execute copy-on-write page copies: for each ``(width, src,
        dst)``, duplicate physical page ``src`` into ``dst`` across every
        kv leaf of that width class (k/v and their scales move in
        lockstep). Copies are batched — one jitted gather+scatter per
        width per call, page-id arrays padded to a power of two (same
        compile-bounding idiom as the fused assign copy); padding scatters
        out of bounds and is dropped."""
        by_width: dict = {}
        for w, src, dst in copies:
            by_width.setdefault(w, []).append((src, dst))
        for w, pairs in by_width.items():
            fn = self._copiers.get(w)
            if fn is None:
                ba = 1 if self._stacked else 0

                def copier(caches, srcs, dsts, _w=w):
                    def per_leaf(leaf, spec, lw):
                        if spec != "kv" or lw != _w:
                            return leaf
                        # OOB padding: gather clamps (garbage), scatter
                        # drops — the pad pair writes nowhere.
                        if ba == 0:
                            return leaf.at[dsts].set(leaf[srcs],
                                                     mode="drop")
                        return leaf.at[:, dsts].set(leaf[:, srcs],
                                                    mode="drop")

                    return jax.tree.map(per_leaf, caches, self.specs,
                                        self.widths)

                fn = jax.jit(copier, donate_argnums=self._donate)
                self._copiers[w] = fn
            P = self.pool.classes[w].num_pages
            n = 1 << (len(pairs) - 1).bit_length()
            srcs = np.full(n, P, np.int32)
            dsts = np.full(n, P, np.int32)
            srcs[:len(pairs)] = [p[0] for p in pairs]
            dsts[:len(pairs)] = [p[1] for p in pairs]
            self.caches = fn(self.caches, jnp.asarray(srcs),
                             jnp.asarray(dsts))

    def gather_prefix(self, page_ids):
        """Materialize a dense, dequantized prefix-KV view out of the page
        pool for a suffix prefill: ``page_ids`` maps each width class to a
        padded int32 array of physical pages (``FREE``-padded entries
        clamp to garbage the prefill masks via its segment ids). 1-D ids
        ``(n_pages,)`` produce batch-1 views ``(L?, 1, n_pages *
        page_size, Hkv, D)``; 2-D ids ``(R, n_pages)`` (a batched suffix
        sweep — one prefix per row) produce ``(L?, R, n_pages *
        page_size, Hkv, D)``: token-major, like a prefill cache."""
        ids = {w: jnp.asarray(v, jnp.int32) for w, v in page_ids.items()}
        return self._prefix_gather(self.caches, ids)

    def _gather_prefix_fn(self, caches, ids):
        def block(d, widths_d):
            w = widths_d.get("k", 0) if isinstance(widths_d, dict) else 0
            if not w:
                return None, None  # state-lane layer: sharing is gated off
            # (R, n): one prefix per row — the row axis IS the batch axis
            # of a batched suffix sweep; (n,): one row
            page_ix = ids[w] if ids[w].ndim == 2 else ids[w][None]
            gather = functools.partial(gather_paged_lanes,
                                       block_table=page_ix)
            if self._stacked:
                gather = jax.vmap(gather)  # every layer of the stack

            def lanes(name):
                return gather(d[name])  # (L?, R|1, n * page_size, Hkv, ..)

            k, v = lanes("k"), lanes("v")
            if "k_scale" in d:
                k = kv_dequantize(k, lanes("k_scale"), self._dtype)
                v = kv_dequantize(v, lanes("v_scale"), self._dtype)
            return k, v

        if self._stacked:
            return block(caches, self.widths)
        out_k, out_v = {}, {}
        for name, d in caches.items():
            out_k[name], out_v[name] = block(d, self.widths[name])
        return out_k, out_v

    def read_page(self, width: int, page: int) -> List[np.ndarray]:
        """Host copy of one physical page's bytes across every kv leaf of
        the width class (k/v and any scales), in ``jax.tree`` traversal
        order — the byte payload a
        :class:`~repro.serve.pages.FleetPrefixIndex` publish mirrors.
        Quantized leaves copy their codes/scales verbatim, so a restore
        is bit-identical by construction."""
        ba = 1 if self._stacked else 0
        out: List[np.ndarray] = []

        def per_leaf(leaf, spec, w):
            if spec == "kv" and w == width:
                sl = leaf[page] if ba == 0 else leaf[:, page]
                out.append(np.asarray(sl))
            return leaf

        jax.tree.map(per_leaf, self.caches, self.specs, self.widths)
        return out

    def write_page(self, width: int, page: int,
                   host: Sequence[np.ndarray]) -> None:
        """Inverse of :meth:`read_page`: write host page bytes into one
        physical page of every kv leaf of the width class (same traversal
        order). Used by the fleet-restore path after
        ``PagePool.adopt_published`` hands the bytes a local page."""
        ba = 1 if self._stacked else 0
        it = iter(host)

        def per_leaf(leaf, spec, w):
            if spec != "kv" or w != width:
                return leaf
            val = jnp.asarray(next(it), leaf.dtype)
            if ba == 0:
                return leaf.at[page].set(val)
            return leaf.at[:, page].set(val)

        self.caches = jax.tree.map(per_leaf, self.caches, self.specs,
                                   self.widths)

    def claim(self, slot: int, request, length: int = 0) -> None:
        """Claim ``slot`` for ``request`` without copying any lane state
        (mixed-step chunked prefill: the model writes the chunk K/V
        straight into the slot's paged lane, so there is no prefill cache
        to gather from). ``length`` is the lane depth already resident —
        0 for a cold admission, ``n_shared`` when the engine mapped a
        shared prefix into the lane first."""
        if self.active[slot]:
            raise ValueError(f"slot {slot} is already occupied")
        if length > self.cache_len:
            raise ValueError(f"claim length {length} exceeds cache_len "
                             f"{self.cache_len}")
        self.active[slot] = True
        self.lengths[slot] = length
        self.request[slot] = request

    def advance(self, slot: int) -> None:
        """One decoded token was written into the lane at ``lengths[slot]``
        (``% ring`` for ring lanes; recurrent lanes updated in place)."""
        self.lengths[slot] += 1

    def advance_n(self, slot: int, n: int) -> None:
        """``n`` chunk tokens were written into the lane at positions
        ``[lengths[slot], lengths[slot] + n)`` (``% ring`` for ring lanes)
        by a mixed step."""
        self.lengths[slot] += n

    def release(self, slot: int) -> None:
        self.active[slot] = False
        # Zero the depth so the decode step's predicated attention (and the
        # blocks-visited accounting) see an empty lane, not a stale one.
        self.lengths[slot] = 0
        self.request[slot] = None
        if self.pool is not None:
            self.pool.release(slot)


# The class predates the recurrent/ring lane kinds; this alias is the
# name the docs use for the generalized structure.
SlotStateTable = SlotKVCache
