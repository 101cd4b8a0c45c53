"""SMM Pallas kernel: fused delta-decode + 6b dequant + densify + matmul.

TPU adaptation of the T-REX SMM core (DESIGN §2). The chip skips MACs on the
zeros of W_D using relative addressing off the delta-encoded indices; the MXU
cannot skip MACs, so the kernel instead *densifies in VMEM* and runs the
matmul dense:

  HBM traffic  = compressed stream only (first/deltas/vq ~ 11b per NZ)
  VMEM         = the transient dense (r, bn) tile
  MXU          = full-utilization dense dot

i.e. the paper's EMA reduction is preserved exactly while the compute side is
traded from MAC-skipping to full MXU occupancy — the codesign argument in
DESIGN §2. Densification is a compare-select accumulation over the nnz axis
(VPU-friendly; no scatter, which TPUs lack in-kernel) that walks the stream
row by row and carries the running row index as it goes — the delta decode
is that running sum, so no prefix-sum primitive is needed. The scale,
offset and level-count scalars ride SMEM.

Grid: (N/bn, M/bm), M innermost: a column block's dense (r, bn) tile is
decoded once, at its first M block, into VMEM scratch and reused by every
other M block of that column (each step holds the full r — the
factorization rank is small by construction, that is the paper's point).
VMEM per step (bm=bn=256, r=1024, nnz=128):
  y tile 256x1024x4 = 1 MiB, dense scratch 1024x256x4 = 1 MiB,
  streams (128x256 x2) = 64 KiB, out 256x256x4 = 256 KiB   (~2.3 MiB).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VALUE_BITS = 6


def _smm_kernel(y_ref, first_ref, deltas_ref, vq_ref, consts_ref, o_ref,
                dense_ref, dscr_ref, vscr_ref, *, r: int, nnz: int):
    # consts_ref (SMEM): scale, offset, dequant level count 2^bits - 1 —
    # the value width is part of the stream, not the program.
    scale, offset, levels = consts_ref[0], consts_ref[1], consts_ref[2]

    @pl.when(pl.program_id(1) == 0)
    def _densify():
        bn = dense_ref.shape[1]
        # Widen the 8-bit streams to 32-bit scratch once per column block:
        # Mosaic loads single rows only at 32-bit, and casts 8-bit ints
        # only to int32.
        dscr_ref[...] = deltas_ref[...].astype(jnp.int32)
        vq = vq_ref[...].astype(jnp.int32).astype(jnp.float32)
        vscr_ref[...] = vq / levels * scale + offset
        rows = jax.lax.broadcasted_iota(jnp.int32, (r, bn), 0)

        def body(k, carry):
            dense, idx = carry
            idx = idx + dscr_ref[pl.ds(k - 1, 1), :]
            hit = rows == idx
            return dense + jnp.where(hit, vscr_ref[pl.ds(k, 1), :], 0.0), idx

        idx0 = first_ref[...]  # (1, bn) int32: row of the first nonzero
        dense0 = jnp.where(rows == idx0, vscr_ref[0:1, :], 0.0)
        dense_ref[...], _ = jax.lax.fori_loop(1, nnz, body, (dense0, idx0))

    # ---- dense MXU matmul
    o_ref[...] = jnp.dot(y_ref[...].astype(jnp.float32), dense_ref[...],
                         preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret"))
def smm_matmul(y: jnp.ndarray, first: jnp.ndarray, deltas: jnp.ndarray,
               vq: jnp.ndarray, scale: jnp.ndarray, offset: jnp.ndarray,
               levels: Optional[jnp.ndarray] = None,
               *, bm: int = 256, bn: int = 256,
               interpret: bool = True) -> jnp.ndarray:
    """z = y @ densify(stream). y (M, r); stream columns N -> (M, N) f32.

    ``levels`` is the dequant level count ``2^value_bits - 1`` as an f32
    scalar (possibly traced — value width is per-layer data on the serving
    path); ``None`` defaults to the module's 6b convention."""
    M, r = y.shape
    nnz, N = vq.shape
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    if levels is None:
        levels = jnp.float32((1 << VALUE_BITS) - 1)
    if nnz == 1:  # no deltas: keep a (1, N) operand so the spec is valid
        deltas = jnp.zeros((1, N), deltas.dtype)
    consts = jnp.stack([jnp.asarray(c, jnp.float32).reshape(())
                        for c in (scale, offset, levels)])
    return pl.pallas_call(
        functools.partial(_smm_kernel, r=r, nnz=nnz),
        grid=(N // bn, M // bm),
        in_specs=[
            pl.BlockSpec((bm, r), lambda n, m: (m, 0)),
            pl.BlockSpec((1, bn), lambda n, m: (0, n)),
            pl.BlockSpec((deltas.shape[0], bn), lambda n, m: (0, n)),
            pl.BlockSpec((nnz, bn), lambda n, m: (0, n)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda n, m: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        scratch_shapes=[pltpu.VMEM((r, bn), jnp.float32),       # dense W_D
                        pltpu.VMEM((deltas.shape[0], bn), jnp.int32),
                        pltpu.VMEM((nnz, bn), jnp.float32)],     # values
        interpret=interpret,
    )(y, first.astype(jnp.int32).reshape(1, N), deltas, vq, consts)
