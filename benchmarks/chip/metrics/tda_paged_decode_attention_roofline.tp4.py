"""The paged TDA decode kernel's least time over its device time, on a
tensor-parallel mesh (kernels/tda). Each chip runs the kernel on its own
KV heads, so it reads a chip's share of the resident keys and values and
does that share of the operations: the least time is the one-chip
reader's over ``ctx.chips``, against the kernel's seconds on one chip
(``kernel_calls`` averages them over the chips). Memory sets it at
decode. Resident tokens are counted, not page-rounded ones."""

import work

KERNEL = "tda_paged_decode_attention"


def read(ctx):
    if ctx.trace is None:
        return None
    calls, secs = ctx.trace.kernel_calls(KERNEL)
    if not calls or secs <= 0:
        return None
    keys = sum(start + n for s in ctx.steps if s.kind == "decode"
               for start, n in s.rows)
    s = ctx.shape
    nbytes = keys * work.kv_token_bytes(s) * s.layers / ctx.chips
    least, _bound = work.roofline_time(
        work.attn_flops(s, keys) / ctx.chips, nbytes,
        ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
