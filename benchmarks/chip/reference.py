"""The plain reference the served tokens are compared with.

A decoder forward pass written straight from the published description in
``jax.numpy``, in float32 with every matrix product at ``HIGHEST``
precision: no kernels, no cache, no paging, no batching across requests.
It imports nothing of the program. It reads the benchmark's own weights
(``weights.py``) by their names in the program's layout, and the sizes
from the configuration file.

Departures from the published models, each one the program's as well:

* RoPE rotates interleaved (even, odd) pairs of each head, where the
  published code rotates the two halves. The two are one model up to a
  fixed permutation of each head's query and key columns.
* starcoder2-15b has no bias on the attention output and feed-forward
  maps here (its weights would hold them; they are zero in effect).

The comparison: run the reference once over a request's prompt and served
tokens, and read at each served position how far the served token's logit
lies below the reference's best (``gap``; 0 where the reference agrees).
The control (``control_gaps``) puts the reference computed in float8 e4m3
(the precision below the configurations' bfloat16: both operands of every
matrix product rounded, activations per token and weights per output
column) in the program's place, and reads the same gap for the token that
it ranks first. Rounding weights alone to int8 per output column moved
40-layer logits too little to tell from bfloat16 rounding (PERF.md).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Reference"]

HIGHEST = jax.lax.Precision.HIGHEST
NORMS = {"qwen2": "rmsnorm", "starcoder2": "layernorm"}


def _dot(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


def fp8_round(x, axis):
    """Round to float8 e4m3 (4 significant bits, largest 448, subnormals
    below 2^-6) with one scale per slice along ``axis``, and back to
    float32. Written with ``frexp``: a TPU elides a float32 -> float8 ->
    float32 conversion pair, and this rounding must happen."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    y = x / scale
    m, e = jnp.frexp(y)  # y = m * 2**e, 0.5 <= |m| < 1
    y = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    y = jnp.where(jnp.abs(y) < 2.0 ** -6, jnp.round(y * 2.0 ** 9) / 2.0 ** 9,
                  y)
    return y * scale


class Reference:
    """Teacher-forced logit gaps of one configuration, at one padded
    sequence length ``seq_len`` and at most ``max_scored`` served
    positions per request (both fixed, so each program compiles once)."""

    def __init__(self, cfg: dict, seq_len: int, max_scored: int):
        self.cfg = cfg
        self.seq_len = int(seq_len)
        self.max_scored = int(max_scored)
        d = int(cfg["hidden_size"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg.get("num_key_value_heads", self.heads))
        self.head_dim = int(cfg.get("head_dim", d // self.heads))
        self.theta = float(cfg["rope_theta"])
        self.window = cfg.get("sliding_window")
        self.norm = NORMS[cfg["model_type"]]
        self.eps = float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon",
                                                         1e-5)))
        self.act = cfg["hidden_act"]
        self._gaps = jax.jit(functools.partial(self._run, control=False))
        self._both = jax.jit(functools.partial(self._run, control=True))

    # -- the model ------------------------------------------------------

    def _apply_norm(self, p, x):
        if self.norm == "layernorm":
            mu = x.mean(-1, keepdims=True)
            var = ((x - mu) ** 2).mean(-1, keepdims=True)
            return ((x - mu) / jnp.sqrt(var + self.eps)
                    * p["scale"].astype(jnp.float32)
                    + p["bias"].astype(jnp.float32))
        var = (x * x).mean(-1, keepdims=True)
        return x / jnp.sqrt(var + self.eps) * p["scale"].astype(jnp.float32)

    def _lin(self, p, x, low):
        w = p["w"]
        if low:  # both operands: activations per token, weights per column
            x, w = fp8_round(x, axis=-1), fp8_round(w, axis=-2)
        y = _dot(x, w)
        if "b" in p:
            y = y + p["b"].astype(jnp.float32)
        return y

    def _rope(self, x, pos):
        hd = self.head_dim
        freqs = 1.0 / (self.theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                      / hd))
        ang = pos[:, None].astype(jnp.float32) * freqs  # (T, hd/2)
        c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                         axis=-1).reshape(x.shape)

    def _layer(self, x, lp, low):
        T = x.shape[0]
        H, Hkv, hd = self.heads, self.kv_heads, self.head_dim
        pos = jnp.arange(T)
        h = self._apply_norm(lp["norm1"], x)
        a = lp["attn"]
        q = self._rope(self._lin(a["wq"], h, low).reshape(T, H, hd), pos)
        k = self._rope(self._lin(a["wk"], h, low).reshape(T, Hkv, hd), pos)
        v = self._lin(a["wv"], h, low).reshape(T, Hkv, hd)
        g = H // Hkv
        q = q.reshape(T, Hkv, g, hd)
        s = jnp.einsum("qhgd,khd->hgqk", q, k,
                       precision=HIGHEST) / np.sqrt(hd)
        mask = pos[None, :] <= pos[:, None]
        if self.window:
            mask &= pos[None, :] > pos[:, None] - int(self.window)
        s = jnp.where(mask, s, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v,
                       precision=HIGHEST).reshape(T, H * hd)
        x = x + self._lin(a["wo"], o, low)
        h = self._apply_norm(lp["norm2"], x)
        f = lp["ffn"]
        if self.act == "silu":
            u = jax.nn.silu(self._lin(f["w_gate"], h, low)) \
                * self._lin(f["w_up"], h, low)
        elif self.act == "gelu_pytorch_tanh":
            u = jax.nn.gelu(self._lin(f["w_up"], h, low), approximate=True)
        else:
            raise ValueError(f"no reference for activation {self.act!r}")
        return x + self._lin(f["w_down"], u, low)

    def _logits(self, w, toks, pos, low):
        emb = w["embed"]["tok"]
        rows = jnp.take(emb, toks, axis=0).astype(jnp.float32)
        if low:  # one scale per embedding row
            rows = fp8_round(rows, axis=-1)
        x, _ = jax.lax.scan(lambda x, lp: (self._layer(x, lp, low), None),
                            rows, w["layers"])
        x = self._apply_norm(w["final_norm"], jnp.take(x, pos, axis=0))
        return self._lin(w["lm_head"], x, low)

    def _run(self, w, toks, pos, chosen, *, control):
        ref = self._logits(w, toks, pos, False)
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, chosen[:, None], 1)[:, 0]
        if not control:
            return gap
        top = jnp.argmax(self._logits(w, toks, pos, True), axis=-1)
        return gap, best - jnp.take_along_axis(ref, top[:, None], 1)[:, 0]

    # -- the comparison ---------------------------------------------------

    def _args(self, prompt, output):
        prompt = np.asarray(prompt, np.int32)
        out = np.asarray(output, np.int32)[:self.max_scored]
        n = len(out)
        seq = np.concatenate([prompt, out])[:self.seq_len]
        toks = np.zeros(self.seq_len, np.int32)
        toks[:len(seq)] = seq
        # The token served at output index i was read from position
        # len(prompt) - 1 + i.
        pos = np.full(self.max_scored, len(prompt) - 1, np.int32)
        pos[:n] += np.arange(n, dtype=np.int32)
        chosen = np.zeros(self.max_scored, np.int32)
        chosen[:n] = out
        return n, (toks, pos, chosen)

    def gaps(self, weights, prompt, output) -> np.ndarray:
        """Gap of each served token below the reference's best logit."""
        n, args = self._args(prompt, output)
        return np.asarray(self._gaps(weights, *args))[:n]

    def control_gaps(self, weights, prompt, output
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """The served tokens' gaps, and the gaps of the tokens that the
        float8-rounded reference ranks first at the same positions."""
        n, args = self._args(prompt, output)
        g, c = self._both(weights, *args)
        return np.asarray(g)[:n], np.asarray(c)[:n]
