"""Parameter / activation / optimizer-state sharding rules.

2-D sharding: every big weight is TP-sharded on ``model`` along its wide
feature dim and FSDP-sharded on ``data`` along the other dim. The shared
factorization dictionaries W_S are TP-sharded on ``model`` (rank axis) and
deliberately **replicated over data** — they are small, read by every layer,
and their all-gather hoists out of the layer scan (the paper's "load W_S
once", DESIGN §2). Per-layer W_D factors are Megatron row-parallel pairs with
W_S (one psum per factorized matmul chain).

MoE experts: E over ``data`` (EP), expert-FFN contraction over ``model`` —
must match the shard_map specs in models/moe.py. ``pod`` is pure DP for
params; optimizer state additionally ZeRO-shards over ``pod``.

KV caches: sequence-sharded over ``model`` (decode reads are the memory
bottleneck; S-sharding splits them evenly — GSPMD inserts the softmax-stat
all-reduces).

Rules are path-based over the param pytree; every spec is validated for
divisibility and falls back to replication (with a note) when a dim cannot be
evenly sharded — GSPMD Auto handles the padded cases that remain.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["param_specs", "batch_spec", "cache_specs", "slot_cache_specs",
           "opt_state_specs", "named", "dp_axes"]

_COL_NAMES = {"wq", "wk", "wv", "w_up", "w_gate", "w_y", "w_x", "w_a", "w_i",
              "in_proj"}
_ROW_NAMES = {"wo", "w_down", "w_out", "out_proj"}


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_ok(mesh: Mesh, axis, dim: int) -> bool:
    if axis is None:
        return True
    axes = axis if isinstance(axis, tuple) else (axis,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return dim % size == 0


def _validated(mesh: Mesh, spec: Tuple, shape: Tuple[int, ...]) -> P:
    """Drop axes that don't divide their dim (GSPMD padding is legal but we
    prefer clean specs; heads etc. stay replicated instead of padded)."""
    out = []
    for axis, dim in zip(spec, shape):
        out.append(axis if _axis_ok(mesh, axis, dim) else None)
    return P(*out)


def _leaf_spec(path_names: List[str], shape: Tuple[int, ...], mesh: Mesh) -> P:
    name = path_names[-1] if path_names else ""
    parents = path_names[:-1]
    in_moe = "moe" in parents
    stacked = len(parents) > 0 and parents[0] == "layers" and \
        not any(p.startswith("layer_") for p in parents)

    def pad(spec: Tuple) -> P:
        """Left-pad with None for stacking dims (scan L, expert E...)."""
        extra = len(shape) - len(spec)
        return _validated(mesh, (None,) * extra + tuple(spec), shape)

    # ---- dictionaries (shared W_S): (d_in, r) — rank TP-sharded.
    if parents and parents[0] == "dicts":
        return _validated(mesh, (None, "model"), shape)

    # ---- embeddings / heads
    if parents and parents[-1] == "embed" and name == "tok":
        return _validated(mesh, ("model", "data"), shape)
    if parents and parents[-1] == "embed" and name == "pos":
        return P()
    if parents and parents[-1] == "lm_head":
        return pad(("data", "model"))

    # ---- MoE experts: (E, d_in, d_out) dense / (E, r, d_out) factorized.
    if in_moe:
        if name == "router":
            return P()
        if name == "w":
            par = parents[-1]
            if par == "w_down":
                return pad(("data", "model", None))
            return pad(("data", None, "model"))
        if name == "wd":
            return pad(("data", "model", None))
        if name == "b":
            return P()

    # ---- dense / factorized linears
    if name == "w":
        par = parents[-1] if parents else ""
        if par in _COL_NAMES:
            return pad(("data", "model"))
        if par in _ROW_NAMES:
            return pad(("model", "data"))
        return pad((None, None))
    if name == "wd":
        par = parents[-1] if parents else ""
        if par in _ROW_NAMES:
            return pad((None, "data"))  # r unsharded after f-psum
        return pad(("model", "data"))  # Megatron row-parallel vs W_S col
    if name == "b":
        par = parents[-1] if parents else ""
        if par in _COL_NAMES:
            return pad(("model",))
        return pad((None,))

    # ---- everything else (norms, gates, conv taps, A_log, ...): replicate
    return P(*([None] * len(shape)))


def _path_names(path) -> List[str]:
    out = []
    for k in path:
        out.append(getattr(k, "key", getattr(k, "name", str(k))))
    return out


def param_specs(param_shapes: Any, mesh: Mesh) -> Any:
    """Pytree of PartitionSpec matching a pytree of ShapeDtypeStructs."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(param_shapes)
    specs = [_leaf_spec(_path_names(p), tuple(l.shape), mesh) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_spec(batch_shapes: Any, mesh: Mesh) -> Any:
    """Inputs: batch dim over (pod, data); batch=1 (long_500k) replicates."""
    dp = dp_axes(mesh)

    def spec(leaf):
        b = leaf.shape[0] if leaf.ndim else 1
        first = dp if _axis_ok(mesh, dp, b) else None
        return P(*((first,) + (None,) * (leaf.ndim - 1)))

    return jax.tree.map(spec, batch_shapes)


def cache_specs(cache_shapes: Any, mesh: Mesh, stacked: bool = True,
                decode: bool = False) -> Any:
    """KV caches (L?, B, S, H, D).

    Prefill/train: B over dp, S over model. Decode (weight-stationary mode,
    batch replicated): S over ("data","model") so every chip reads exactly
    cache/n_chips bytes per step — the decode memory wall splits evenly and
    only softmax stats cross the wire. Recurrent states (L?, B, ...): B over
    data, last (width) dim over model. ``stacked``: leading layer dim."""
    dp = dp_axes(mesh)
    seq_axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)

    def spec(path, leaf):
        names = _path_names(path)
        nd = leaf.ndim
        s = [None] * nd
        if names and names[-1] in ("k", "v") and nd >= 4:
            # (L?, B, S, H, D)
            if decode:
                s[nd - 3] = seq_axes if _axis_ok(mesh, seq_axes,
                                                 leaf.shape[nd - 3]) else None
            else:
                s[nd - 4] = dp if _axis_ok(mesh, dp, leaf.shape[nd - 4]) \
                    else None
                s[nd - 3] = "model" if _axis_ok(mesh, "model",
                                                leaf.shape[nd - 3]) else None
            return P(*s)
        if names and names[-1] in ("k_scale", "v_scale") and nd >= 3:
            # (L?, B, S, H) — mirror the k/v (B, S) sharding
            s[nd - 3] = dp if _axis_ok(mesh, dp, leaf.shape[nd - 3]) else None
            s[nd - 2] = "model" if _axis_ok(mesh, "model",
                                            leaf.shape[nd - 2]) else None
            return P(*s)
        bdim = 1 if (stacked and nd >= 2) else 0
        if nd > bdim:
            s[bdim] = dp if _axis_ok(mesh, dp, leaf.shape[bdim]) else None
        if nd - 1 > bdim and _axis_ok(mesh, "model", leaf.shape[-1]):
            s[-1] = "model"
        return P(*s)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


def slot_cache_specs(cache_shapes: Any, mesh: Mesh,
                     paged: bool = False) -> Any:
    """Serving slot/page caches: **KV-head**-sharded over ``model``.

    The continuous-batching engine's cache leaves are head-major
    ``(L?, P, Hkv, page_size, D)`` page pools (``paged``) or
    ``(L?, B, ring, Hkv, D)`` contiguous lanes — the slot/batch axis is
    tiny (num_slots) and the decode step's per-slot scatter writes would
    gather the whole cache if sequence were split, so unlike
    :func:`cache_specs` the shard axis is the KV head: each rank owns all
    pages of ``Hkv / tp`` heads (its head-slice of every physical page)
    and block tables and scalar slot metadata replicate. These are the
    shards the TDA kernels run on, once per rank
    (``kernels/tda/sharded.per_rank``); the dense path merges per-rank
    softmax partials instead. int8 KV scale leaves (``(L?, P, Hkv,
    page_size)`` / ``(..., Hkv)``) mirror their codes; recurrent state
    leaves replicate (they are neither paged nor head-structured).
    """
    def spec(path, leaf):
        names = _path_names(path)
        nd = leaf.ndim
        s = [None] * nd
        if names and names[-1] in ("k", "v") and nd >= 4:
            head = nd - 3 if paged else nd - 2
        elif names and names[-1] in ("k_scale", "v_scale") and nd >= 3:
            head = nd - 2 if paged else nd - 1
        else:
            return P(*s)
        if _axis_ok(mesh, "model", leaf.shape[head]):
            s[head] = "model"
        return P(*s)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


def opt_state_specs(pspecs: Any, mesh: Mesh,
                    param_shapes: Any = None) -> Any:
    """Optimizer moments: like params but additionally ZeRO-sharded over
    ``pod`` (fold pod into the data/FSDP axis when present and divisible)."""
    if "pod" not in mesh.axis_names:
        return pspecs

    def widen(spec: P, leaf=None):
        parts = []
        for i, ax in enumerate(spec):
            if ax == "data" and (
                    leaf is None
                    or _axis_ok(mesh, ("data", "pod"), leaf.shape[i])):
                parts.append(("data", "pod"))
            else:
                parts.append(ax)
        return P(*parts)

    if param_shapes is None:
        return jax.tree.map(widen, pspecs, is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(
        lambda s, l: widen(s, l), pspecs, param_shapes,
        is_leaf=lambda x: isinstance(x, P))


def named(tree_specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))
