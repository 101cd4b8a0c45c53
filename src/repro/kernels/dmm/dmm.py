"""DMM Pallas kernel: fused 4b-LUT non-uniform dequant + tiled matmul.

TPU adaptation of the T-REX DMM core (DESIGN §2): the chip streams 4b codes
from DRAM through a 16-entry LUT dequantizer straight into the PE array; here
the nibble-packed codes stream HBM -> VMEM, are expanded and LUT-dequantized
*inside* the kernel, and feed the MXU — the dense fp W_S tile never exists in
HBM, so HBM weight traffic is exactly the compressed bytes (the paper's EMA
claim, realized as the memory-roofline term).

Layout contract (the TRF analogue): the output tile is produced in the
(row-major M x N) layout the SMM kernel consumes as its (M x r) input, so no
relayout op sits between the chained kernels.

The packed code byte ``i`` of a column holds rows ``2i`` (high nibble) and
``2i + 1`` (low nibble). Rather than re-interleaving the two nibble planes
into one (bk, bn) tile in VMEM (a sublane shuffle of every tile), the
wrapper splits x into its even and odd columns and the kernel runs two
half-depth dots, one per nibble plane: ``x[:, 0::2] @ LUT[hi] + x[:, 1::2]
@ LUT[lo]``. The 16-entry LUT rides SMEM and dequantizes by a 16-way
compare-select (Mosaic has no vector gather).

Grid: (M/bm, N/bn, K/bk), K innermost for accumulate-in-place.
VMEM per step (defaults bm=bn=256, bk=512, bf16 x):
  x tiles 2x256x256x2 = 256 KiB, code tile 256x256 = 64 KiB,
  dequant tiles 2x256x256x4 = 512 KiB, out tile 256x256x4 = 256 KiB
  (~1.1 MiB).
MXU alignment: all tile dims multiples of 128 (bk/2 multiples of 128 too).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lut_dequant(codes, lut_ref):
    """LUT[codes] for int32 codes in [0, 16) as a compare-select chain."""
    w = jnp.zeros(codes.shape, jnp.float32)
    for c in range(16):
        w = jnp.where(codes == c, lut_ref[c], w)
    return w


def _dmm_kernel(xe_ref, xo_ref, codes_ref, lut_ref, o_ref):
    k = pl.program_id(2)
    packed = codes_ref[...].astype(jnp.int32)  # (bk//2, bn)
    w_hi = _lut_dequant(packed >> 4, lut_ref)   # rows 2i
    w_lo = _lut_dequant(packed & 0xF, lut_ref)  # rows 2i + 1
    partial = (jnp.dot(xe_ref[...].astype(jnp.float32), w_hi,
                       preferred_element_type=jnp.float32)
               + jnp.dot(xo_ref[...].astype(jnp.float32), w_lo,
                         preferred_element_type=jnp.float32))

    @pl.when(k == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def dmm_matmul(x: jnp.ndarray, codes_packed: jnp.ndarray, lut: jnp.ndarray,
               *, bm: int = 256, bn: int = 256, bk: int = 512,
               interpret: bool = True) -> jnp.ndarray:
    """y = x @ LUT[unpack(codes_packed)].

    x (M, K) bf16/f32; codes_packed (K//2, N) uint8; lut (16,) f32 -> (M, N) f32.
    M, N, K must be multiples of the tile sizes (ops.py pads).
    """
    M, K = x.shape
    N = codes_packed.shape[1]
    assert codes_packed.shape[0] * 2 == K
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk % 2 == 0, bk
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _dmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // 2), lambda m, n, k: (m, k)),
            pl.BlockSpec((bm, bk // 2), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk // 2, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x[:, 0::2], x[:, 1::2], codes_packed, lut.astype(jnp.float32))
