"""Factorized linear layers — the paper's primary contribution, as a composable
JAX parameterization.

Every weight matrix ``W (d_in, d_out)`` is replaced by ``W = W_S @ W_D`` with

- ``W_S (d_in, r)``: dense **dictionary**, shared across *all layers* of the
  network (and across all experts, for MoE archs). One dictionary per matrix
  *family* (e.g. ``"attn_q"``, ``"ffn_up"``, separately for encoder/decoder),
  exactly as the paper defines separate W_S per attention/FFN and per
  encoder/decoder.
- ``W_D (r, d_out)``: per-layer, trained to a fixed number of non-zeros per
  column (see :mod:`repro.core.sparsity`).

The runtime computation is the *sequential* MM ``(X @ W_S) @ W_D`` — chosen by
the paper over ``X @ (W_S @ W_D)`` because ``r`` is much smaller than the
output width, which also makes it 1–2.14x fewer MACs than the dense ``X @ W``.

Parameter-tree convention
-------------------------
Models store dictionaries under ``params["dicts"][family]`` (one array each)
and per-layer factors under the layer subtree as ``{"wd": (r, d_out)}``
(stacked to ``(L, r, d_out)`` when the layer stack is scanned). Biases are
never factorized. ``apply_linear`` dispatches on which keys are present, so
dense and factorized checkpoints share the same model code.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as comp
from repro.core import sparsity

__all__ = [
    "FactorizationConfig",
    "DictionaryBank",
    "init_linear",
    "apply_linear",
    "linear_macs",
    "linear_param_bits",
    "compress_linear",
    "apply_compressed_linear",
    "compress_model_params",
    "decompress_model_params",
    "decompress_ws_entry",
    "decompress_wd_leaf",
    "params_stream_bits",
    "project_wd_leaves",
]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class FactorizationConfig:
    """First-class feature switch for the T-REX technique.

    rank r = ``rank`` if set, else ``rank_ratio * d_in`` rounded up to a
    multiple of 128 (MXU-aligned). nnz/column = ``nnz`` if set, else
    ``nnz_ratio * r`` (>=1). Matrices with min(d_in, d_out) < ``min_dim`` stay
    dense (norm gains, small gates, biases).
    """

    enabled: bool = False
    rank_ratio: float = 0.625
    rank: Optional[int] = None
    nnz_ratio: float = 0.125
    nnz: Optional[int] = None
    min_dim: int = 256
    reg_coeff: float = 1e-4  # out-of-support L1 weight in the train loss
    # When True the forward pass applies the top-k STE projection (training);
    # inference params are stored already-projected.
    ste_in_forward: bool = True

    def rank_for(self, d_in: int, d_out: Optional[int] = None) -> int:
        """r = ratio * min(d_in, d_out): the factorization only wins MACs when
        r is small relative to the *output* width ("the hidden size of W_S is
        much smaller"), so down-projections rank against d_out."""
        if self.rank is not None:
            return self.rank
        base = d_in if d_out is None else min(d_in, d_out)
        return max(128, _round_up(int(self.rank_ratio * base), 128))

    def nnz_for(self, r: int) -> int:
        if self.nnz is not None:
            return min(self.nnz, r)
        return max(1, int(self.nnz_ratio * r))

    def applies_to(self, d_in: int, d_out: int) -> bool:
        return self.enabled and min(d_in, d_out) >= self.min_dim


class DictionaryBank:
    """Init-time registry of shared W_S dictionaries, keyed by family name.

    The first ``ensure`` for a family creates the dictionary; later calls
    assert shape compatibility (all layers share it). The bank's ``dicts``
    dict becomes ``params["dicts"]``.
    """

    def __init__(self, fcfg: FactorizationConfig, dtype=jnp.float32):
        self.fcfg = fcfg
        self.dtype = dtype
        self.dicts: Dict[str, jnp.ndarray] = {}

    def ensure(self, key: jax.Array, family: str, d_in: int,
               d_out: Optional[int] = None) -> int:
        r = self.fcfg.rank_for(d_in, d_out)
        if family not in self.dicts:
            scale = 1.0 / np.sqrt(d_in)
            self.dicts[family] = (
                jax.random.normal(key, (d_in, r), self.dtype) * scale
            ).astype(self.dtype)
        else:
            got = self.dicts[family].shape
            if got != (d_in, r):
                raise ValueError(
                    f"dictionary {family!r} shape {got} != requested {(d_in, r)}"
                )
        return r


def init_linear(
    key: jax.Array,
    d_in: int,
    d_out: int,
    fcfg: FactorizationConfig,
    bank: Optional[DictionaryBank],
    family: str,
    use_bias: bool = False,
    dtype=jnp.float32,
) -> Dict[str, jnp.ndarray]:
    """Create one linear layer's per-layer params (dense w or factorized wd)."""
    kd, kb = jax.random.split(key)
    p: Dict[str, jnp.ndarray] = {}
    if fcfg.applies_to(d_in, d_out) and bank is not None:
        r = bank.ensure(kd, family, d_in, d_out)
        # var(W) target 1/d_in; W_S contributes r * (1/d_in) * var(W_D).
        p["wd"] = (jax.random.normal(kd, (r, d_out), dtype)
                   / np.sqrt(r)).astype(dtype)
    else:
        p["w"] = (jax.random.normal(kd, (d_in, d_out), dtype)
                  / np.sqrt(d_in)).astype(dtype)
    if use_bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def apply_linear(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    dicts: Optional[Dict[str, jnp.ndarray]],
    family: str,
    fcfg: FactorizationConfig,
    sparse_train: bool = False,
    compute_dtype=None,
) -> jnp.ndarray:
    """y = x @ W (+ b), where W may be factorized through the family dictionary.

    Dispatches on the keys present in ``p``: dense (``w``), factorized
    (``wd``), or the compressed streaming format (``wd_vq``, produced by
    :func:`compress_model_params`) — so dense, factorized, and compressed
    checkpoints all share the same model code.
    """
    if "wd_vq" in p:
        return apply_compressed_linear(
            p, x, dicts, family,
            compute_dtype=compute_dtype if compute_dtype is not None
            else x.dtype)
    if "w" in p:
        y = x @ p["w"]
    else:
        ws = dicts[family]
        wd = p["wd"]
        if sparse_train and fcfg.ste_in_forward:
            wd = sparsity.ste_sparse(wd, fcfg.nnz_for(wd.shape[0]))
        # Sequential MM — (X @ W_S) @ W_D, the paper's compute order.
        y = (x @ ws) @ wd
    if "b" in p:
        y = y + p["b"]
    return y


def linear_macs(tokens: int, d_in: int, d_out: int, fcfg: FactorizationConfig) -> int:
    """MAC count for one linear application (feeds bench_macs)."""
    if not fcfg.applies_to(d_in, d_out):
        return tokens * d_in * d_out
    r = fcfg.rank_for(d_in, d_out)
    nnz = fcfg.nnz_for(r)
    return tokens * (d_in * r + nnz * d_out)


def linear_param_bits(
    d_in: int, d_out: int, n_layers: int, fcfg: FactorizationConfig,
    dense_bits: int = 16, compressed: bool = True,
) -> int:
    """Stored bits for this matrix family across all layers."""
    if not fcfg.applies_to(d_in, d_out):
        return n_layers * d_in * d_out * dense_bits
    r = fcfg.rank_for(d_in, d_out)
    nnz = fcfg.nnz_for(r)
    if compressed:
        ws_bits = d_in * r * 4 + 16 * 16
        first = comp.bits_needed(r - 1)
        wd_bits = d_out * (first + (nnz - 1) * 5 + nnz * 6) + 32
    else:
        ws_bits = d_in * r * dense_bits
        wd_bits = nnz * d_out * (dense_bits + 8)  # values + 8b indices
    return ws_bits + n_layers * wd_bits


# --------------------------------------------------------------------------
# Compressed runtime representation (serve path)
# --------------------------------------------------------------------------


def compress_linear(
    p: Dict[str, np.ndarray],
    dicts_np: Dict[str, np.ndarray],
    family: str,
    fcfg: FactorizationConfig,
    reorder: bool = True,
    value_bits: int = 6,
) -> Dict[str, np.ndarray]:
    """Offline: turn one factorized layer into the T-REX streaming format.

    Returns a jnp-friendly dict:
      ``wd_first`` int32 (d_out,)        absolute first row index per column
      ``wd_deltas`` uint8|int16 (nnz-1, d_out)  delta-encoded remaining indices
      ``wd_vq`` uint8 (nnz, d_out)       uniform value codes
      ``wd_scale``, ``wd_offset`` f32    per-layer dequant constants
      ``wd_bits`` int32                  value quantizer width (``value_bits``)
    Dense layers pass through unchanged. The shared-dictionary compression
    (4b nibble-packed codes + LUT) is done once per family by the caller.
    """
    if "w" in p:
        return dict(p)
    wd = np.asarray(p["wd"], np.float32)
    r = wd.shape[0]
    nnz = fcfg.nnz_for(r)
    order = None
    if reorder:
        dense_idx = np.sort(np.argsort(-np.abs(wd), axis=0)[:nnz], axis=0)
        order = comp.reorder_for_delta(dense_idx, r)
    cwd = comp.compress_wd(wd, nnz, value_bits=value_bits, order=order)
    out = {
        "wd_first": comp.delta_decode(cwd.deltas)[0].astype(np.int32),
        "wd_deltas": cwd.deltas[1:].astype(
            np.uint8 if cwd.achieved_delta_bits <= 8 else np.int16
        ),
        "wd_vq": cwd.values_q,
        "wd_scale": np.float32(cwd.scale),
        "wd_offset": np.float32(cwd.offset),
        "wd_bits": np.int32(value_bits),
    }
    if "b" in p:
        out["b"] = np.asarray(p["b"])
    if order is not None:
        out["_order"] = order.astype(np.int32)  # caller permutes W_S columns
    return out


def pack_nibbles(codes: np.ndarray) -> np.ndarray:
    """Pack 4b codes two-per-byte along the leading axis.

    An odd leading axis is padded with one zero-code row; consumers crop to
    the true length after :func:`unpack_nibbles` (the kernel path instead
    pads ``x`` with a zero column, which nullifies the pad row's weights)."""
    codes = np.asarray(codes)
    if codes.shape[0] % 2:
        codes = np.concatenate(
            [codes, np.zeros((1,) + codes.shape[1:], codes.dtype)], axis=0)
    hi = codes[0::2].astype(np.uint8)
    lo = codes[1::2].astype(np.uint8)
    return (hi << 4) | lo


def unpack_nibbles(packed: jnp.ndarray) -> jnp.ndarray:
    hi = packed >> 4
    lo = packed & 0xF
    return jnp.stack([hi, lo], axis=1).reshape((-1,) + packed.shape[1:])


def decompress_ws_entry(entry, d_in: int, dtype=jnp.float32) -> jnp.ndarray:
    """Dense (d_in, r) W_S from a ``cdicts`` entry — either a raw array or a
    ``{"codes_packed", "lut"}`` compressed dict (cropping the odd-``d_in``
    nibble pad)."""
    if isinstance(entry, dict):
        ws = comp.dequantize_nonuniform(
            unpack_nibbles(entry["codes_packed"]), entry["lut"])
        return ws[:d_in].astype(dtype)
    return entry.astype(dtype)


def decompress_wd_leaf(p: Dict[str, jnp.ndarray], r: int,
                       dtype=jnp.float32) -> jnp.ndarray:
    """Dense (r, d_out) W_D from one compressed leaf group (``wd_first``,
    ``wd_deltas``, ``wd_vq``, ``wd_scale``, ``wd_offset``, ``wd_bits``).

    Matches :func:`repro.core.compression.decompress_wd_dense` bit-for-bit;
    this variant consumes the stacked in-tree layout (and a possibly traced
    ``wd_bits``) instead of a host-side :class:`CompressedWD`."""
    first = p["wd_first"][None].astype(jnp.int32)
    idx = jnp.concatenate(
        [first, first + jnp.cumsum(p["wd_deltas"].astype(jnp.int32), axis=0)],
        axis=0)  # (nnz, d_out)
    vals = comp.dequantize_uniform(p["wd_vq"], p["wd_scale"], p["wd_offset"],
                                   p.get("wd_bits", 6))
    d_out = idx.shape[1]
    dense = jnp.zeros((r, d_out), jnp.float32)
    cols = jnp.broadcast_to(jnp.arange(d_out), idx.shape)
    dense = dense.at[idx.reshape(-1), cols.reshape(-1)].add(vals.reshape(-1))
    return dense.astype(dtype)


def apply_compressed_linear(
    p: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    cdicts: Dict[str, Dict[str, jnp.ndarray]],
    family: str,
    compute_dtype=jnp.bfloat16,
    use_kernel: Optional[bool] = None,
) -> jnp.ndarray:
    """Runtime decompress-and-matmul over the T-REX streams.

    HBM traffic: nibble-packed W_S codes + delta/``wd_bits`` W_D streams only;
    the dense matrices exist only transiently. ``use_kernel=None`` follows the
    backend dispatch in :mod:`repro.kernels.common` — the fused dmm/smm Pallas
    kernels on TPU, the pure-jnp reference elsewhere (XLA fuses the gathers
    into the consumers); an explicit bool always wins (tests run the kernels
    in interpret mode on CPU with ``use_kernel=True``).
    """
    if "w" in p:
        y = x @ p["w"].astype(compute_dtype)
    else:
        cd = cdicts[family]
        d_in = x.shape[-1]
        if use_kernel is None:
            from repro.kernels.common import pallas_interpret_default
            use_kernel = isinstance(cd, dict) and not pallas_interpret_default()
        if use_kernel and isinstance(cd, dict):
            from repro.kernels.dmm.ops import lut_matmul
            from repro.kernels.smm.ops import compressed_matmul
            lead = x.shape[:-1]
            x2 = x.reshape((-1, d_in))
            y1 = lut_matmul(x2, cd["codes_packed"], cd["lut"])  # (M, r) f32
            z = compressed_matmul(
                y1, p["wd_first"].astype(jnp.int32), p["wd_deltas"],
                p["wd_vq"], p["wd_scale"], p["wd_offset"],
                value_bits=p.get("wd_bits", 6))
            y = z.reshape(lead + (z.shape[-1],)).astype(compute_dtype)
        else:
            ws = decompress_ws_entry(cd, d_in, compute_dtype)
            y1 = x @ ws
            dense = decompress_wd_leaf(p, ws.shape[1], compute_dtype)
            y = y1 @ dense
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


# --------------------------------------------------------------------------
# Whole-model compression (serve path) + stream-bits accounting
# --------------------------------------------------------------------------


def _leaf_bits(a) -> int:
    return int(np.prod(a.shape, dtype=np.int64)) * np.dtype(a.dtype).itemsize * 8


def params_stream_bits(params) -> int:
    """Estimated bits streamed per decode step if every weight leaf is read
    once — the generic (byte-aligned) fallback when no audited accounting is
    available. :func:`compress_model_params` returns the audited number for
    compressed trees (sub-byte streams are NOT byte-aligned on the chip)."""
    return sum(_leaf_bits(leaf) for leaf in jax.tree.leaves(params))


def project_wd_leaves(params, fcfg: FactorizationConfig):
    """End-of-training projection: every W_D leaf snapped to its top-nnz
    column support, so the offline compression is exact on the indices
    (idempotent with :func:`repro.core.compression.compress_wd`)."""
    def proj(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if not names or names[-1] != "wd":
            return leaf
        r, d_out = leaf.shape[-2], leaf.shape[-1]
        nnz = fcfg.nnz_for(r)
        flat = leaf.reshape((-1, r, d_out))
        out = jax.vmap(lambda w: sparsity.project_topk_columns(w, nnz))(flat)
        return out.reshape(leaf.shape)
    return jax.tree_util.tree_map_with_path(proj, params)


def compress_model_params(params, fcfg: FactorizationConfig,
                          value_bits: int = 6):
    """Offline host-side walk: factorized param tree -> T-REX streaming tree.

    * ``params["dicts"]``: each family dictionary becomes ``{"codes_packed",
      "lut"}`` (4b non-uniform codes, nibble-packed along d_in).
    * Every ``{"wd": (..., r, d_out)}`` group (plain, layer-stacked
      ``(L, r, d_out)``, or MoE ``(E, r, d_out)`` — any leading dims) becomes
      the ``wd_first/wd_deltas/wd_vq/wd_scale/wd_offset/wd_bits`` streams
      with the same leading dims, so scan/unroll slicing and per-expert vmaps
      keep working unchanged.
    * Everything else (embeddings, norms, biases, dense ``w``) passes through.

    No reorder pass runs: the per-layer permutation from
    :func:`reorder_for_delta` would demand a different W_S column order per
    layer, which a family-shared dictionary cannot satisfy — so the stream
    accounting prices deltas at their *achieved* width
    (``wd_compressed_bits(..., use_achieved_delta_bits=True)``).

    Returns ``(cparams, stats)`` where ``stats`` has ``weight_stream_bits``
    (audited bits to stream every weight once, compressed),
    ``weight_stream_bits_dense`` (same tree uncompressed), and their ratio.
    """
    if not isinstance(params, dict) or "dicts" not in params:
        raise ValueError("compress_model_params needs a factorized param tree "
                         "(params['dicts'] missing — init the model with "
                         "factorization.enabled=True)")
    bits_c = 0  # compressed stream bits
    bits_d = 0  # dense stream bits for the same leaves

    cdicts = {}
    for fam, ws in params["dicts"].items():
        cws = comp.compress_ws(np.asarray(ws, np.float32))
        cdicts[fam] = {"codes_packed": jnp.asarray(pack_nibbles(cws.codes)),
                       "lut": jnp.asarray(cws.lut)}
        bits_c += comp.ws_compressed_bits(cws)
        bits_d += _leaf_bits(ws)

    def compress_group(d: Dict) -> Dict:
        nonlocal bits_c, bits_d
        wd = np.asarray(d["wd"], np.float32)
        lead, (r, d_out) = wd.shape[:-2], wd.shape[-2:]
        nnz = fcfg.nnz_for(r)
        parts = [comp.compress_wd(w2, nnz, value_bits=value_bits)
                 for w2 in wd.reshape((-1, r, d_out))]
        bits_c += sum(comp.wd_compressed_bits(c, use_achieved_delta_bits=True)
                      for c in parts)
        bits_d += _leaf_bits(d["wd"])
        # One dtype across the stack: the widest any slice needs.
        ddt = np.uint8 if max(c.achieved_delta_bits for c in parts) <= 8 \
            else np.int16

        def stack(f):
            arrs = [np.asarray(f(c)) for c in parts]
            return np.stack(arrs).reshape(lead + arrs[0].shape)

        out = {
            "wd_first": stack(
                lambda c: comp.delta_decode(c.deltas)[0].astype(np.int32)),
            "wd_deltas": stack(lambda c: c.deltas[1:].astype(ddt)),
            "wd_vq": stack(lambda c: c.values_q),
            "wd_scale": stack(lambda c: np.float32(c.scale)),
            "wd_offset": stack(lambda c: np.float32(c.offset)),
            "wd_bits": stack(lambda c: np.int32(c.value_bits)),
        }
        out = {k: jnp.asarray(v) for k, v in out.items()}
        for k, v in d.items():  # passthrough (biases)
            if k != "wd":
                out[k] = v
                bits_c += _leaf_bits(v)
                bits_d += _leaf_bits(v)
        return out

    def walk(node):
        nonlocal bits_c, bits_d
        if isinstance(node, dict):
            if "wd" in node:
                return compress_group(node)
            return {k: walk(v) for k, v in node.items()}
        bits_c += _leaf_bits(node)
        bits_d += _leaf_bits(node)
        return node

    cparams = {k: (cdicts if k == "dicts" else walk(v))
               for k, v in params.items()}
    stats = {
        "weight_stream_bits": int(bits_c),
        "weight_stream_bits_dense": int(bits_d),
        "weight_compression_ratio": bits_d / max(bits_c, 1),
        "value_bits": value_bits,
    }
    return cparams, stats


_STREAM_KEYS = ("wd_first", "wd_deltas", "wd_vq", "wd_scale", "wd_offset",
                "wd_bits")


def decompress_model_params(cparams, like):
    """Inverse walk of :func:`compress_model_params`: the streaming tree
    back to a dense factorized tree that ``apply_linear`` runs as plain
    ``(x @ W_S) @ W_D`` — the jnp reference the compressed kernels are
    checked against.

    ``like`` is the factorized tree (arrays or ``jax.eval_shape`` shapes)
    the streams were made from: it supplies each W_D's rank and the dtype
    the dense factors come back in. Decompression is exact up to float
    rounding, so both trees compute the same function."""
    def wd_dense(ref, c):
        lead = ref.shape[:-2]
        r = ref.shape[-2]
        flat = {k: jnp.reshape(c[k], (-1,) + c[k].shape[len(lead):])
                for k in _STREAM_KEYS}
        dense = jax.vmap(lambda q: decompress_wd_leaf(q, r))(flat)
        return dense.reshape(ref.shape).astype(ref.dtype)

    def walk(ref, c):
        if isinstance(ref, dict):
            if "wd" in ref and "wd_vq" in c:
                out = {k: v for k, v in c.items() if k not in _STREAM_KEYS}
                out["wd"] = wd_dense(ref["wd"], c)
                return out
            return {k: walk(ref[k], c[k]) for k in ref}
        return c

    out = {k: walk(like[k], cparams[k]) for k in like if k != "dicts"}
    out["dicts"] = {f: decompress_ws_entry(cparams["dicts"][f], ws.shape[0],
                                           ws.dtype)
                    for f, ws in like["dicts"].items()}
    return out
