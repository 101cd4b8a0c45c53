"""The paged TDA decode kernel's least time over its summed device time in
the window (kernels/tda). The least time is the larger of its operations
over peak compute and the resident keys and values it must read over peak
HBM bandwidth: memory sets it at decode (about 1 operation per byte).
Resident tokens are counted, not page-rounded ones."""

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
    nbytes = keys * work.kv_token_bytes(s) * s.layers
    least, _bound = work.roofline_time(
        work.attn_flops(s, keys), nbytes, ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
