"""The mixed TDA kernel's least time over its summed device time in the
window (kernels/tda). Every row of a mixed step goes through it: a chunk
row's tokens at positions [start, start + n) attend causally over the
resident lane and the chunk, a decode row is one such token. The least
time is the larger of the attention's operations (``work.ctx_sum``) over
peak compute and each row's resident and new keys and values, read once
per row and layer, over peak HBM bandwidth: compute sets it for long
chunks, memory for decode rows. Rows are the window's mixed-step records
(``(start, n)`` per row, as ``StepResult.starts`` and ``n_new`` give
them)."""

import work

KERNEL = "tda_mixed_attention"


def read(ctx):
    if ctx.trace is None:
        return None
    calls, secs = ctx.trace.kernel_calls(KERNEL)
    if not calls or secs <= 0:
        return None
    rows = [r for s in ctx.steps if s.kind == "mixed" for r in s.rows]
    if not rows:
        return None
    s = ctx.shape
    keys = sum(work.ctx_sum(s, start, n) for start, n in rows)
    nbytes = (sum(start + n for start, n in rows) * work.kv_token_bytes(s)
              * s.layers)
    least, _bound = work.roofline_time(
        work.attn_flops(s, keys), nbytes, ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
