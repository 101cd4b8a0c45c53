"""TDA — TRF decode-attention: length-predicated slot-decode kernel.

DESIGN
------
T-REX keeps decode state resident in the two-direction accessible register
file (TRF) so the PE array never re-streams it from DRAM, and its dynamic
batching keeps the array full of whichever requests are live. The serving
analogue (PR 1) got the *batching* half — a slot table decoded by one
fixed-shape jitted step — but computed attention densely: every layer, every
step, every slot paid ``cache_len`` worth of score/PV work and, with int8 KV,
a full-cache dequant materialized in HBM-visible form first.

This package is the *memory* half. The kernel grids over (slot, kv-block)
and per grid step:

1. reads the slot's ``[lo, hi)`` occupancy bounds from SMEM and skips the
   block via ``pl.when`` unless it intersects — work per decode step is
   ``sum_s ceil(len_s / bk)`` blocks, not ``num_slots * ceil(cache_len/bk)``;
2. DMAs the block's K/V *codes* (int8) + per-(token, head) scales into VMEM
   and dequantizes there — the fp cache never exists in HBM;
3. carries online-softmax state (m, l, o) in VMEM scratch across the
   kv-block dimension, GQA-packed so both contractions are batched
   ``dot_general`` over kv heads (MXU-shaped on TPU);
4. optionally routes both exponentials through the AFU's 64-entry LUT
   (``lut_table=exp_lut_table()``), modelling the chip's LUT-assisted AFU.

Traffic accounting (per decode step, per layer, quantized cache)
----------------------------------------------------------------
  dense path HBM:  S*Hkv*D bytes codes (k+v: 2x) read
                 + 2*S*Hkv*D*4 bytes fp dequant written + re-read by the
                   score/PV einsums  ->  ~10x the code bytes
  TDA HBM:         sum_s ceil(len_s/bk)*bk*Hkv*(2D + 8) bytes (codes +
                   scales), nothing written back
  TDA VMEM:        one (bk, Hkv, D) f32 K and V tile + (Hq, D) accumulators
                   (~bk=128, Hkv=8, D=128: 1 MiB/tile — fits comfortably)

so HBM traffic drops by the blocks-visited ratio *and* the dequant
round-trip; the occupancy ratio is reported by ``block_stats``.

Interpret mode runs the same kernel body on CPU (tests, CI); on TPU the
backend-aware default (``kernels/common.py``) compiles it.
"""
from repro.kernels.tda.ops import block_stats, fused_decode_attention  # noqa: F401
from repro.kernels.tda.ref import decode_attention_reference  # noqa: F401
from repro.kernels.tda.sharded import (  # noqa: F401
    decode_partials, merge_partials, sharded_decode_attention)
