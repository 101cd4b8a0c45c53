"""Operations and bytes that served tokens need, counted from the shapes in
a configuration file.

Only work that a token which was really processed needs is counted: the
layers' matrix products for every processed token, attention over the
token's real context (resident tokens, not page-rounded ones), and the
output head at the positions whose token was emitted. Padded columns,
inert rows and the head's work at positions mid-prompt are not counted,
so a share built on these counts cannot pass 100% of a peak unless the
time is wrong.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Optional, Tuple

__all__ = ["Shape", "layer_matmul_params", "token_flops", "attn_flops",
           "head_flops", "ctx_sum", "kv_token_bytes", "step_flops",
           "roofline_time"]


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated_ffn: bool
    window: Optional[int] = None
    act_bytes: int = 2  # bf16 activations and KV

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        heads = int(cfg["num_attention_heads"])
        d = int(cfg["hidden_size"])
        return cls(
            layers=int(cfg["num_hidden_layers"]), d_model=d, heads=heads,
            kv_heads=int(cfg.get("num_key_value_heads", heads)),
            head_dim=int(cfg.get("head_dim", d // heads)),
            d_ff=int(cfg["intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
            gated_ffn=cfg.get("hidden_act") == "silu",
            window=cfg.get("sliding_window"))


def layer_matmul_params(s: Shape) -> int:
    """Weights one token multiplies by in one layer: q, k, v, o and the
    feed-forward matrices (biases and norms are not matrix products)."""
    attn = s.d_model * s.head_dim * (2 * s.heads + 2 * s.kv_heads)
    ffn = (3 if s.gated_ffn else 2) * s.d_model * s.d_ff
    return attn + ffn


def token_flops(s: Shape, tokens: int) -> float:
    """Matrix-product operations of ``tokens`` processed tokens through all
    layers (2 per multiply-add)."""
    return 2.0 * s.layers * layer_matmul_params(s) * tokens


def ctx_sum(s: Shape, start: int, n: int) -> int:
    """Keys attended in all, summed over ``n`` tokens at positions
    ``[start, start + n)`` of one sequence: causal, each attends
    ``position + 1`` keys, or the window where it is shorter."""
    if n <= 0:
        return 0
    lo, hi = start + 1, start + n  # key counts of the first and last token
    w = s.window
    if w is None or hi <= w:
        return (lo + hi) * n // 2
    if lo >= w:
        return w * n
    below = (lo + w) * (w - lo + 1) // 2
    return below + w * (hi - w)


def attn_flops(s: Shape, keys: int) -> float:
    """Score and value products over ``keys`` attended keys in all layers:
    2 operations x 2 products x heads x head_dim per key."""
    return 4.0 * s.layers * s.heads * s.head_dim * keys


def head_flops(s: Shape, emitted: int) -> float:
    """The output head at ``emitted`` positions (the untied head, once)."""
    return 2.0 * s.d_model * s.vocab * emitted


def kv_token_bytes(s: Shape) -> int:
    """Bytes of one resident token's keys and values in one layer."""
    return 2 * s.kv_heads * s.head_dim * s.act_bytes


def step_flops(s: Shape, rows: Iterable[Tuple[int, int]],
               emitted: int) -> float:
    """One step's needed operations: ``rows`` are ``(start, n)`` spans of
    processed tokens per sequence; ``emitted`` tokens left the head."""
    tok = keys = 0
    for start, n in rows:
        tok += n
        keys += ctx_sum(s, start, n)
    return token_flops(s, tok) + attn_flops(s, keys) + head_flops(s, emitted)


def roofline_time(flops: float, nbytes: float, peak_flops: float,
                  peak_bw: float) -> Tuple[float, str]:
    """Least time on the chip and which bound sets it."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
