"""Mixture-of-Experts FFN: GShard-style capacity dispatch with expert
parallelism, as used by dbrx-132b (16e top-4) and arctic-480b (128e top-2 +
dense residual).

Distribution (see DESIGN §5): experts are sharded over the **data** axis
(EP: 16 -> 1/shard for dbrx, 128 -> 8/shard for arctic) and the expert FFN is
tensor-parallel over the **model** axis. Tokens move to their experts via
``all_to_all`` over the data axis with per-(source, expert) capacity, compute
runs TP with a single ``psum`` over model, and a second ``all_to_all`` brings
results home. Experts are replicated over the ``pod`` axis (pure DP).

The T-REX factorization applies *inside* the experts: one dictionary per
matrix family is shared across **layers and experts** — the strongest version
of the paper's amortize-the-dense-part argument — and the per-expert sparse
W_D is the only expert-distinct weight. The factorized pair is computed
Megatron-style: ``x @ W_S`` column-parallel (r over model), ``@ W_D``
row-parallel, one psum.

``moe_ffn(..., mesh=None)`` runs a pure-local oracle with identical capacity
semantics — used by the smoke tests and as the shard_map correctness
reference.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import factorized as factorized_mod
from repro.core.errors import UnsupportedConfigError
from repro.core.factorized import DictionaryBank, init_linear
from repro.core import sparsity
from repro.models.common import ModelConfig

__all__ = ["init_moe", "moe_ffn"]


def init_moe(key: jax.Array, cfg: ModelConfig, bank: Optional[DictionaryBank]) -> Dict:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.n_experts
    fcfg = cfg.factorization
    ks = jax.random.split(key, 5)
    p: Dict = {"router": jax.random.normal(ks[0], (d, E), cfg.params_dtype) * 0.02}

    def expert_mats(k, d_in, d_out, family):
        """(E, ...) stacked per-expert factors sharing one dictionary."""
        if fcfg.applies_to(d_in, d_out) and bank is not None:
            r = bank.ensure(k, family, d_in, d_out)
            return {"wd": (jax.random.normal(k, (E, r, d_out),
                                             cfg.params_dtype)
                           / np.sqrt(r)).astype(cfg.params_dtype)}
        return {"w": (jax.random.normal(k, (E, d_in, d_out), cfg.params_dtype)
                      / np.sqrt(d_in)).astype(cfg.params_dtype)}

    p["w_gate"] = expert_mats(ks[1], d, f, "moe_gate")
    p["w_up"] = expert_mats(ks[2], d, f, "moe_up")
    p["w_down"] = expert_mats(ks[3], f, d, "moe_down")
    return p


def _router(tokens, router_w, m) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    logits = (tokens.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gates, eidx = jax.lax.top_k(probs, m.top_k)  # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Load-balance aux loss (Switch): E * sum_e f_e * p_e.
    E = router_w.shape[1]
    me = probs.mean(0)
    ce = jnp.zeros((E,)).at[eidx.reshape(-1)].add(1.0) / eidx.size
    aux = E * jnp.sum(me * ce)
    return gates, eidx, aux


def _dispatch(tokens, gates, eidx, E: int, C: int):
    """Scatter tokens into an (E, C, d) buffer; returns buf, pos, keep."""
    T, d = tokens.shape
    k = eidx.shape[1]
    buf = jnp.zeros((E, C, d), tokens.dtype)
    counts = jnp.zeros((E,), jnp.int32)
    pos_all, keep_all = [], []
    for j in range(k):
        e = eidx[:, j]
        oh = jax.nn.one_hot(e, E, dtype=jnp.int32)  # (T, E)
        rank = jnp.cumsum(oh, axis=0) - oh  # exclusive rank among slot-j claims
        pos = counts[e] + jnp.take_along_axis(rank, e[:, None], axis=1)[:, 0]
        keep = pos < C
        pos_c = jnp.where(keep, pos, 0)
        contrib = jnp.where(keep[:, None], tokens, 0)
        buf = buf.at[e, pos_c].add(contrib, mode="drop")
        counts = counts + oh.sum(0)
        pos_all.append(pos_c)
        keep_all.append(keep)
    return buf, jnp.stack(pos_all, 1), jnp.stack(keep_all, 1)  # (T,k)


def _combine(buf_out, gates, eidx, pos, keep):
    T, k = eidx.shape
    out = jnp.zeros((T, buf_out.shape[-1]), jnp.float32)
    for j in range(k):
        g = buf_out[eidx[:, j], pos[:, j]]  # (T, d)
        out += jnp.where(keep[:, j, None], g.astype(jnp.float32), 0) \
            * gates[:, j, None]
    return out


def _expert_ffn(buf, p, dicts, cfg, sparse_train, tp_axis: Optional[str]):
    """buf: (E_loc, C, d) -> (E_loc, C, d). TP over ``tp_axis`` when given.

    Dense experts: f sharded over model -> one psum after w_down.
    Factorized: r sharded over model -> psum after each W_D contraction
    (classic Megatron col/row pairing, applied to the paper's sequential MM).
    """
    m = cfg.moe
    dt = cfg.compute_dtype
    fcfg = cfg.factorization

    def mat(pp, x, family):
        # x: (E_loc, C, d_in)
        if "w" in pp:
            return jnp.einsum("ecd,edf->ecf", x, pp["w"].astype(dt))
        if "wd_vq" in pp:
            # Compressed serving: the per-expert W_D streams (and the nibble-
            # packed family dictionary) are the HBM traffic; the dense forms
            # below are transient decompression products.
            ws = factorized_mod.decompress_ws_entry(
                dicts[family], x.shape[-1], dt)  # (d_in, r)
            y1 = jnp.einsum("ecd,dr->ecr", x, ws)
            streams = {k: pp[k] for k in
                       ("wd_first", "wd_deltas", "wd_vq", "wd_scale",
                        "wd_offset", "wd_bits") if k in pp}
            wd = jax.vmap(lambda q: factorized_mod.decompress_wd_leaf(
                q, ws.shape[1], dt))(streams)  # (E, r, d_out)
            return jnp.einsum("ecr,erf->ecf", y1, wd)
        ws = dicts[family].astype(dt)  # (d_in, r[_loc])
        wd = pp["wd"]
        if sparse_train and fcfg.ste_in_forward and tp_axis is None:
            # Top-k-per-column STE needs the full r axis; under TP (r sharded
            # over model) the projection is applied post-update by the train
            # loop instead (optim/adamw.py project_fn) — same fixed point.
            nnz = fcfg.nnz_for(wd.shape[1])
            wd = sparsity.ste_sparse(
                wd.reshape(-1, wd.shape[-1]), max(1, nnz)).reshape(wd.shape)
        y1 = jnp.einsum("ecd,dr->ecr", x, ws)
        y = jnp.einsum("ecr,erf->ecf", y1, wd.astype(dt))
        if tp_axis is not None:
            y = jax.lax.psum(y, tp_axis)
        return y

    factorized = "wd" in p["w_up"] or "wd_vq" in p["w_up"]
    up = mat(p["w_up"], buf, "moe_up")
    gate = mat(p["w_gate"], buf, "moe_gate")
    h = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(dt)
    down = mat(p["w_down"], h, "moe_down")
    if tp_axis is not None and not factorized:
        down = jax.lax.psum(down, tp_axis)
    elif tp_axis is not None and factorized:
        pass  # already psummed inside mat()
    return down


def _capacity(T: int, k: int, E: int, cf: float) -> int:
    return max(1, int(np.ceil(T * k / E * cf)))


# --------------------------------------------------------------------------
# Local oracle (mesh=None)
# --------------------------------------------------------------------------


def _moe_local(p, x, cfg, dicts, sparse_train):
    B, S, d = x.shape
    m = cfg.moe
    tokens = x.reshape(B * S, d)
    gates, eidx, aux = _router(tokens, p["router"], m)
    C = _capacity(B * S, m.top_k, m.n_experts, m.capacity_factor)
    buf, pos, keep = _dispatch(tokens, gates, eidx, m.n_experts, C)
    buf_out = _expert_ffn(buf, p, dicts, cfg, sparse_train, tp_axis=None)
    out = _combine(buf_out, gates, eidx, pos, keep)
    return out.reshape(B, S, d).astype(cfg.compute_dtype), aux


# --------------------------------------------------------------------------
# Distributed shard_map version
# --------------------------------------------------------------------------


def _moe_sharded_body(x_loc, router_w, pw_gate, pw_up, pw_down, dicts_loc,
                      *, cfg, sparse_train, ep_axis, tp_axis, n_ep, dp_axes):
    """Per-shard body. x_loc: (B_loc, S, d) — replicated over tp_axis."""
    m = cfg.moe
    B, S, d = x_loc.shape
    tokens = x_loc.reshape(B * S, d)
    gates, eidx, aux = _router(tokens, router_w, m)
    E = m.n_experts
    E_loc = E // n_ep
    # Per-(source-shard, expert) capacity.
    C_se = _capacity(B * S, m.top_k, E, m.capacity_factor)
    buf, pos, keep = _dispatch(tokens, gates, eidx, E, C_se)  # (E, C_se, d)

    # ---- EP exchange: rows are globally expert-ordered; owner j holds
    # experts [j*E_loc, (j+1)*E_loc). tiled all_to_all swaps E-blocks.
    recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                              tiled=True)  # (n_ep*E_loc, C_se, d) by source
    recv = recv.reshape(n_ep, E_loc, C_se, d).transpose(1, 0, 2, 3)
    recv = recv.reshape(E_loc, n_ep * C_se, d)

    p_loc = {"w_gate": pw_gate, "w_up": pw_up, "w_down": pw_down}
    out_buf = _expert_ffn(recv, p_loc, dicts_loc, cfg, sparse_train, tp_axis)

    # ---- send back
    back = out_buf.reshape(E_loc, n_ep, C_se, d).transpose(1, 0, 2, 3)
    back = back.reshape(n_ep * E_loc, C_se, d)
    buf_out = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0,
                                 tiled=True)  # (E, C_se, d), our tokens

    out = _combine(buf_out, gates, eidx, pos, keep)
    aux = jax.lax.pmean(aux, dp_axes)  # replicated for the P() out_spec
    return out.reshape(B, S, d).astype(cfg.compute_dtype), aux


def moe_ffn(
    p: Dict,
    x: jnp.ndarray,
    *,
    cfg: ModelConfig,
    dicts: Optional[Dict],
    mesh: Optional[jax.sharding.Mesh] = None,
    sparse_train: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed-expert FFN. Returns (y, aux_loss). mesh=None -> local oracle."""
    if mesh is None or mesh.devices.size == 1:
        return _moe_local(p, x, cfg, dicts, sparse_train)
    if "wd_vq" in p["w_up"]:
        # Engine(...) raises this at construction so a bad deployment
        # fails before serving a token; this raise is the mid-decode
        # backstop for callers that bypass the engine.
        raise UnsupportedConfigError(
            "compressed expert weights (wd_vq streams) are local-only for "
            "now: the EP/TP in_specs shard the dense 'wd' leaf, not the "
            "streaming format. Either serve compressed MoE without a mesh "
            "(mesh=None / a 1-device mesh), or serve dense-factorized "
            "params (skip Model.compress_params) on the mesh.")

    P = jax.sharding.PartitionSpec
    axes = mesh.axis_names
    dp = tuple(a for a in axes if a in ("pod", "data"))
    ep_axis, tp_axis = "data", "model"
    n_ep = mesh.shape[ep_axis]
    factorized = "wd" in p["w_up"]

    # Expert weights: E over data (EP); contraction factor over model (TP).
    if factorized:
        wspec = {"wd": P(ep_axis, tp_axis, None)}  # (E, r, f): r over model
        wspec_down = {"wd": P(ep_axis, tp_axis, None)}
        dict_spec = {k: P(None, tp_axis) for k in (dicts or {})}
    else:
        wspec = {"w": P(ep_axis, None, tp_axis)}  # (E, d, f): f over model
        wspec_down = {"w": P(ep_axis, tp_axis, None)}  # (E, f, d)
        dict_spec = {}

    body = functools.partial(
        _moe_sharded_body, cfg=cfg, sparse_train=sparse_train,
        ep_axis=ep_axis, tp_axis=tp_axis, n_ep=n_ep, dp_axes=dp)
    fn = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P(dp, None, None), P(None, None),
                  wspec, wspec, wspec_down, dict_spec),
        out_specs=(P(dp, None, None), P()),
    )
    dicts_in = {k: dicts[k] for k in (dicts or {})} if factorized else {}
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], dicts_in)
