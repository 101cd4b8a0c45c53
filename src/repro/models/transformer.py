"""The composable model: embeds -> scanned block stack -> norm -> logits.

Covers every assigned arch through ``ModelConfig``:
- dense GQA decoders (qwen2.5/starcoder2/yi/qwen1.5, llava & musicgen backbones)
- MoE (dbrx, arctic incl. dense-residual)
- SSM (mamba2: pure SSD stack, attn-free)
- hybrid (recurrentgemma: rglru/rglru/local pattern)

Uniform stacks are `lax.scan`ned over layers with the remat policy from the
config — the scan is what realizes the paper's "load W_S once" property on
TPU: the shared dictionaries are loop invariants hoisted out of the layer
loop, while per-layer sparse W_D factors stream through it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.factorized import DictionaryBank, FactorizationConfig
from repro.core import sparsity
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssd as S
from repro.models.common import ModelConfig

__all__ = ["Model", "factorization_regularizer"]


def factorization_regularizer(params: Dict, fcfg: FactorizationConfig) -> jnp.ndarray:
    """Sum of out-of-support L1 over every W_D leaf (any stacking)."""
    total = jnp.float32(0.0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if names and names[-1] == "wd":
            r, d_out = leaf.shape[-2], leaf.shape[-1]
            nnz = fcfg.nnz_for(r)
            flat = leaf.reshape(-1, r, d_out)
            total = total + jax.vmap(
                lambda w: sparsity.out_of_support_l1(w, nnz))(flat).sum()
    return total


class Model:
    def __init__(self, cfg: ModelConfig):
        if cfg.weight_format not in ("dense", "compressed"):
            raise ValueError(
                f"weight_format must be 'dense' or 'compressed', "
                f"got {cfg.weight_format!r}")
        self.cfg = cfg

    def with_weight_format(self, fmt: str) -> "Model":
        """Same model, different weight representation (``dense`` /
        ``compressed``). The forward pass dispatches per leaf, so this is
        metadata — but carrying it in the config lets the serving engine
        label its stats and keeps the mode explicit."""
        if fmt == self.cfg.weight_format:
            return self
        return Model(dataclasses.replace(self.cfg, weight_format=fmt))

    def compress_params(self, params: Dict, value_bits: int = 6):
        """Offline: factorized params -> T-REX streaming format.

        Returns ``(model, cparams, stats)`` — a ``weight_format="compressed"``
        model, the compressed tree (nibble-packed W_S codes + delta/quantized
        W_D streams; everything else passes through), and the stream-bits
        accounting from
        :func:`repro.core.factorized.compress_model_params`. Feed
        ``stats["weight_stream_bits"]`` to the serving engine's
        ``weight_stream_bits`` for audited bytes-per-token numbers."""
        from repro.core.factorized import compress_model_params
        cparams, stats = compress_model_params(
            params, self.cfg.factorization, value_bits=value_bits)
        return self.with_weight_format("compressed"), cparams, stats

    def with_decode_attn(self, mode: str,
                         block_k: Optional[int] = None) -> "Model":
        """Same model, different decode-attention impl (``dense``/``tda``/
        ``auto``) and optional predication-block size. Params and caches
        are layout-compatible across modes — only the S==1 attention math
        changes — so the serving engine can run prefill on ``self`` and
        decode on the returned model."""
        block_k = block_k or self.cfg.decode_block_k
        if mode == self.cfg.decode_attn and block_k == self.cfg.decode_block_k:
            return self
        return Model(dataclasses.replace(self.cfg, decode_attn=mode,
                                         decode_block_k=block_k))

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def _init_block(self, key: jax.Array, kind: str,
                    bank: Optional[DictionaryBank]) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        p: Dict[str, Any] = {"norm1": L.init_norm(cfg)}
        if kind in ("attn", "local"):
            p["attn"] = L.init_attention(ks[0], cfg, bank)
            p["norm2"] = L.init_norm(cfg)
            if cfg.moe is not None:
                p["moe"] = M.init_moe(ks[1], cfg, bank)
                if cfg.moe.dense_residual:
                    p["dense_ffn"] = L.init_ffn(ks[2], cfg, bank,
                                                d_ff=cfg.moe.d_ff_dense,
                                                prefix="densefn")
            else:
                p["ffn"] = L.init_ffn(ks[1], cfg, bank)
        elif kind == "ssd":
            p["ssd"] = S.init_ssd(ks[0], cfg, bank)
        elif kind == "rglru":
            p["rglru"] = R.init_rglru(ks[0], cfg, bank)
            p["norm2"] = L.init_norm(cfg)
            p["ffn"] = L.init_ffn(ks[1], cfg, bank)
        else:
            raise ValueError(kind)
        return p

    def init(self, key: jax.Array) -> Dict:
        cfg = self.cfg
        bank = DictionaryBank(cfg.factorization, cfg.params_dtype) \
            if cfg.factorization.enabled else None
        k_emb, k_head, k_layers = jax.random.split(key, 3)
        params: Dict[str, Any] = {"embed": L.init_embedding(k_emb, cfg)}
        lkeys = jax.random.split(k_layers, cfg.n_layers)
        if cfg.uniform_layers:
            kind = cfg.block_kind(0)
            if bank is not None:
                # The shared dictionaries are drawn from layer 0's keys as
                # the bank first sees each family; the vmapped init below
                # then finds every family present and draws no dictionary.
                self._init_block(lkeys[0], kind, bank)
            # One batched init of the stacked layers: the same values as
            # stacking per-layer inits, without holding both at once.
            params["layers"] = jax.vmap(
                lambda k: self._init_block(k, kind, bank))(lkeys)
        else:
            params["layers"] = {
                f"layer_{i:02d}": self._init_block(lkeys[i], cfg.block_kind(i),
                                                   bank)
                for i in range(cfg.n_layers)
            }
        params["final_norm"] = L.init_norm(cfg)
        params["lm_head"] = L.init_lm_head(k_head, cfg)
        if bank is not None:
            params["dicts"] = bank.dicts
        return params

    def param_shapes(self, seed: int = 0):
        return jax.eval_shape(lambda k: self.init(k), jax.random.key(seed))

    # ------------------------------------------------------------------
    # one block
    # ------------------------------------------------------------------

    def _block(self, lp: Dict, x: jnp.ndarray, kind: str, *, dicts, positions,
               seg_ids, cache_l, cache_index, mesh, sparse_train,
               layer_idx=None, slot_mask=None, pages_l=None, prefix_l=None,
               n_new=None):
        cfg = self.cfg
        aux = jnp.float32(0.0)
        new_cache = None
        if kind in ("attn", "local"):
            window = cfg.local_window if kind == "local" else cfg.sliding_window
            h = L.apply_norm(lp["norm1"], x, cfg.norm_eps)
            a_out, new_cache = L.attention_block(
                lp["attn"], h, cfg=cfg, dicts=dicts, positions=positions,
                seg_ids=seg_ids, window=window, cache=cache_l,
                cache_index=cache_index, slot_mask=slot_mask,
                layer_idx=layer_idx, pages=pages_l, prefix_kv=prefix_l,
                n_new=n_new, sparse_train=sparse_train, mesh=mesh)
            x = x + a_out
            h2 = L.apply_norm(lp["norm2"], x, cfg.norm_eps)
            if cfg.moe is not None:
                mo, aux = M.moe_ffn(lp["moe"], h2, cfg=cfg, dicts=dicts,
                                    mesh=mesh, sparse_train=sparse_train)
                x = x + mo
                if cfg.moe.dense_residual:
                    x = x + L.ffn_block(lp["dense_ffn"], h2, cfg=cfg,
                                        dicts=dicts, sparse_train=sparse_train,
                                        prefix="densefn", mesh=mesh)
            else:
                x = x + L.ffn_block(lp["ffn"], h2, cfg=cfg, dicts=dicts,
                                    sparse_train=sparse_train, mesh=mesh)
        elif kind == "ssd":
            h = L.apply_norm(lp["norm1"], x, cfg.norm_eps)
            out, new_cache = S.ssd_block(
                lp["ssd"], h, cfg=cfg, dicts=dicts, cache=cache_l,
                cache_index=cache_index, layer_idx=layer_idx,
                seg_ids=seg_ids, slot_mask=slot_mask,
                sparse_train=sparse_train)
            x = x + out
        elif kind == "rglru":
            h = L.apply_norm(lp["norm1"], x, cfg.norm_eps)
            out, new_cache = R.rglru_block(lp["rglru"], h, cfg=cfg, dicts=dicts,
                                           cache=cache_l, seg_ids=seg_ids,
                                           slot_mask=slot_mask,
                                           sparse_train=sparse_train)
            x = x + out
            h2 = L.apply_norm(lp["norm2"], x, cfg.norm_eps)
            x = x + L.ffn_block(lp["ffn"], h2, cfg=cfg, dicts=dicts,
                                sparse_train=sparse_train, mesh=mesh)
        return x, new_cache, aux

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------

    def _embed_in(self, params, batch, positions):
        cfg = self.cfg
        if cfg.external_embeddings:
            return batch["embeds"].astype(cfg.compute_dtype)
        return L.embed_tokens(params["embed"], batch["inputs"], cfg, positions)

    def _stack_forward(self, params, x, *, dicts, positions, seg_ids, caches,
                       cache_index, mesh, sparse_train, unroll=False,
                       slot_mask=None, pages=None, prefix=None, n_new=None):
        """Run the block stack; returns (x, new_caches, aux). ``pages`` is
        the paged-decode block-table info: one entry shared by every layer
        of a uniform stack, or ``{layer_name: entry-or-None}`` for
        heterogeneous stacks (recurrent layers carry ``None``). ``prefix``
        is the suffix-prefill shared-prefix KV (``{"k", "v", "len"}``
        with per-layer leaves: L-stacked arrays for uniform stacks,
        ``{layer_name: array-or-None}`` otherwise)."""
        cfg = self.cfg
        if cfg.uniform_layers and unroll:
            # Unrolled layer loop (decode): tiny graphs; static layer indices
            # keep every cache update a local in-place DUS — the scanned
            # carry otherwise copies the whole stacked cache every layer
            # (§Perf cell C).
            assert prefix is None, "prefix_kv is a prefill-only input"
            kind = cfg.block_kind(0)
            aux = jnp.float32(0.0)
            cur_caches = caches
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                x, cur_caches, aux_l = self._block(
                    lp, x, kind, dicts=dicts, positions=positions,
                    seg_ids=seg_ids, cache_l=cur_caches,
                    cache_index=cache_index, mesh=mesh,
                    sparse_train=sparse_train, layer_idx=i,
                    slot_mask=slot_mask, pages_l=pages, n_new=n_new)
                aux = aux + aux_l
            return x, cur_caches, aux
        if cfg.uniform_layers:
            kind = cfg.block_kind(0)
            idxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
            plen = prefix["len"] if prefix is not None else None

            # Caches ride the scan CARRY (in-place dynamic-update-slice per
            # layer), never the ys — ys-stacking would copy the whole KV
            # cache every layer (EXPERIMENTS §Dry-run). Per-layer prefix KV
            # rides the xs (it is read-only per layer, like the params).
            def body(carry, xs):
                if prefix is None:
                    lp, li = xs
                    prefix_l = None
                else:
                    lp, li, pk_l, pv_l = xs
                    prefix_l = {"k": pk_l, "v": pv_l, "len": plen}
                if caches is None:
                    xc, aux = carry
                    cache_arg = None
                else:
                    xc, aux, cache_arg = carry
                xc, new_cache, aux_l = self._block(
                    lp, xc, kind, dicts=dicts, positions=positions,
                    seg_ids=seg_ids, cache_l=cache_arg,
                    cache_index=cache_index, mesh=mesh,
                    sparse_train=sparse_train, layer_idx=li,
                    slot_mask=slot_mask, pages_l=pages, prefix_l=prefix_l,
                    n_new=n_new)
                if caches is None:
                    return (xc, aux + aux_l), None
                return (xc, aux + aux_l, new_cache), None

            xs = (params["layers"], idxs)
            if prefix is not None:
                xs = xs + (prefix["k"], prefix["v"])
            if cfg.remat != "none":
                policy = getattr(jax.checkpoint_policies, cfg.remat)
                body = jax.checkpoint(body, policy=policy)
            if caches is None:
                (x, aux), _ = jax.lax.scan(
                    body, (x, jnp.float32(0.0)), xs)
                return x, None, aux
            (x, aux, new_caches), _ = jax.lax.scan(
                body, (x, jnp.float32(0.0), caches), xs)
            return x, new_caches, aux

        aux = jnp.float32(0.0)
        new_caches = {} if caches is not None else None
        for i in range(cfg.n_layers):
            name = f"layer_{i:02d}"
            cache_l = caches[name] if caches is not None else None
            pages_l = pages.get(name) if pages is not None else None
            prefix_l = None
            if prefix is not None and prefix["k"].get(name) is not None:
                prefix_l = {"k": prefix["k"][name], "v": prefix["v"][name],
                            "len": prefix["len"]}
            blk = functools.partial(
                self._block, kind=cfg.block_kind(i), dicts=dicts,
                positions=positions, seg_ids=seg_ids, cache_l=cache_l,
                cache_index=cache_index, mesh=mesh, sparse_train=sparse_train,
                slot_mask=slot_mask, pages_l=pages_l, prefix_l=prefix_l,
                n_new=n_new)
            if cfg.remat != "none":
                policy = getattr(jax.checkpoint_policies, cfg.remat)
                blk = jax.checkpoint(blk, policy=policy, static_argnums=())
            x, new_cache, aux_l = blk(params["layers"][name], x)
            aux = aux + aux_l
            if caches is not None:
                new_caches[name] = new_cache
        return x, new_caches, aux

    def hidden(self, params: Dict, batch: Dict, *, mesh=None,
               sparse_train: bool = False, caches=None, cache_index=None,
               prefix_kv=None) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
        """Final-norm hidden states. Returns (h, new_caches, aux_loss).

        ``prefix_kv`` (suffix prefill, serving only): cached post-RoPE K/V
        of a shared prompt prefix — ``{"k", "v", "len"}`` with per-layer
        attention memories — that every attention layer prepends to its
        keys. ``batch`` then carries only the suffix tokens, with absolute
        ``positions`` starting at the prefix length."""
        cfg = self.cfg
        ref = batch["embeds"] if cfg.external_embeddings else batch["inputs"]
        B, Ss = ref.shape[0], ref.shape[1]
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(Ss, dtype=jnp.int32), (B, Ss))
        seg_ids = batch.get("seg_ids")
        dicts = params.get("dicts")
        x = self._embed_in(params, batch, positions)
        x = L.constrain_batch(x, mesh)
        x, new_caches, aux = self._stack_forward(
            params, x, dicts=dicts, positions=positions, seg_ids=seg_ids,
            caches=caches, cache_index=cache_index, mesh=mesh,
            sparse_train=sparse_train, prefix=prefix_kv)
        x = L.apply_norm(params["final_norm"], x, cfg.norm_eps)
        return x, new_caches, aux

    def apply(self, params: Dict, batch: Dict, *, mesh=None,
              sparse_train: bool = False, caches=None, cache_index=None,
              prefix_kv=None) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
        """Full-sequence forward. Returns (logits, new_caches, aux_loss).

        Materializes all-position logits — fine for small vocab / short
        sequences; the train loss uses chunked_xent instead. ``prefix_kv``
        selects the suffix-prefill path (see :meth:`hidden`)."""
        x, new_caches, aux = self.hidden(params, batch, mesh=mesh,
                                         sparse_train=sparse_train,
                                         caches=caches,
                                         cache_index=cache_index,
                                         prefix_kv=prefix_kv)
        logits = L.lm_logits(params["lm_head"], params["embed"], x, self.cfg)
        return logits, new_caches, aux

    def loss(self, params: Dict, batch: Dict, *, mesh=None,
             sparse_train: bool = False) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        h, _, aux = self.hidden(params, batch, mesh=mesh,
                                sparse_train=sparse_train)
        weights = batch.get("weights")
        xe = L.chunked_xent(params["lm_head"], params["embed"], h,
                            batch["labels"], cfg, weights)
        total = xe + 0.01 * aux
        metrics = {"xent": xe, "aux": aux}
        if sparse_train and cfg.factorization.enabled:
            reg = factorization_regularizer(params, cfg.factorization)
            total = total + cfg.factorization.reg_coeff * reg
            metrics["sparsity_reg"] = reg
        metrics["loss"] = total
        return total, metrics

    # ------------------------------------------------------------------
    # caches / decode
    # ------------------------------------------------------------------

    def _block_ring(self, kind: str, max_len: int, ring: bool = True) -> int:
        """Sequence capacity of one attention cache lane: the window clamps
        it to a ring buffer unless ``ring=False`` (full-length caches, used
        by the serving engine's prefill so every position stays addressable
        for the slot-lane gather)."""
        cfg = self.cfg
        window = cfg.local_window if kind == "local" else cfg.sliding_window
        if window is None or not ring:
            return max_len
        return min(window, max_len)

    def _init_block_cache(self, kind: str, batch: int, max_len: int,
                          ring: bool = True) -> Dict:
        cfg = self.cfg
        if kind in ("attn", "local"):
            shape = (batch, self._block_ring(kind, max_len, ring),
                     cfg.kv_heads, cfg.head_dim)
            if cfg.kv_quant:
                return {"k": jnp.zeros(shape, jnp.int8),
                        "v": jnp.zeros(shape, jnp.int8),
                        "k_scale": jnp.zeros(shape[:-1], jnp.float32),
                        "v_scale": jnp.zeros(shape[:-1], jnp.float32)}
            return {"k": jnp.zeros(shape, cfg.compute_dtype),
                    "v": jnp.zeros(shape, cfg.compute_dtype)}
        if kind == "ssd":
            return S.init_ssd_cache(cfg, batch)
        if kind == "rglru":
            return R.init_rglru_cache(cfg, batch)
        raise ValueError(kind)

    def init_cache(self, batch: int, max_len: int, ring: bool = True):
        """Zero decode caches. ``ring=True`` clamps windowed attention lanes
        to their ring-buffer size (decode layout); ``ring=False`` keeps every
        sequence position (the engine's prefill layout, so a slot-lane gather
        can address any row position regardless of the window)."""
        cfg = self.cfg
        if cfg.uniform_layers:
            one = self._init_block_cache(cfg.block_kind(0), batch, max_len,
                                         ring)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), one)
        return {f"layer_{i:02d}": self._init_block_cache(cfg.block_kind(i),
                                                         batch, max_len, ring)
                for i in range(cfg.n_layers)}

    def cache_lane_specs(self):
        """Per-leaf lane kinds for the slot-state table, as a pytree with the
        same structure as :meth:`init_cache` output. Leaves are strings:

        * ``"kv"`` — a per-token lane with the sequence axis right after the
          batch axis: full attention KV/scales (width ``cache_len``) or a
          ring-buffered windowed lane (width ``min(window, cache_len)``). The
          slot table gathers request segments into it in *canonical ring
          phase* (token ``t`` at position ``t % width``).
        * ``"state"`` — a fixed-shape recurrent state (RG-LRU hidden state,
          SSD state, conv taps): no sequence axis; assign copies the whole
          per-row state and advance is a no-op.
        """
        cfg = self.cfg

        def block_spec(kind: str) -> Dict:
            if kind in ("attn", "local"):
                spec = {"k": "kv", "v": "kv"}
                if cfg.kv_quant:
                    spec.update({"k_scale": "kv", "v_scale": "kv"})
                return spec
            if kind == "ssd":
                return {"state": "state", "conv": "state"}
            if kind == "rglru":
                return {"h": "state", "conv": "state"}
            raise ValueError(kind)

        if cfg.uniform_layers:
            return block_spec(cfg.block_kind(0))
        return {f"layer_{i:02d}": block_spec(cfg.block_kind(i))
                for i in range(cfg.n_layers)}

    def decode_step(self, params: Dict, batch: Dict, caches,
                    cache_index: jnp.ndarray, *, mesh=None,
                    slot_mask: Optional[jnp.ndarray] = None,
                    pages=None) -> Tuple[jnp.ndarray, Any]:
        """One-token step. batch: {"inputs": (B,1)} or {"embeds": (B,1,d)}.

        ``cache_index`` is either a scalar (lock-step decode: every row at
        the same depth) or a ``(B,)`` vector (continuous batching: row b's
        cache holds ``cache_index[b]`` tokens and the new token is written
        there). ``slot_mask`` (``(B,)`` bool) marks rows whose cache may be
        written — inactive serving slots keep their KV lanes untouched so a
        freshly admitted request never sees a stale write.

        ``pages`` selects the paged cache layout (``serve/pages.py``):
        attention cache leaves are then physical page pools and each
        attention layer's entry — ``{"bt": (B, n) int32 block table,
        "width": logical lane width, "page_size": int}``, one shared entry
        for uniform stacks or ``{layer_name: entry-or-None}`` otherwise —
        routes the token write through ``bt[b, pos // page_size]``.
        ``cache_index``/``slot_mask`` semantics are unchanged.
        """
        cfg = self.cfg
        ref = batch["embeds"] if cfg.external_embeddings else batch["inputs"]
        B = ref.shape[0]
        ci = jnp.asarray(cache_index, jnp.int32)
        positions = jnp.broadcast_to(jnp.reshape(ci, (-1, 1)), (B, 1))
        dicts = params.get("dicts")
        x = self._embed_in(params, batch, positions)
        x, new_caches, _ = self._stack_forward(
            params, x, dicts=dicts, positions=positions, seg_ids=None,
            caches=caches, cache_index=ci, mesh=mesh,
            sparse_train=False, unroll=cfg.unroll_decode,
            slot_mask=slot_mask, pages=pages)
        x = L.apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = L.lm_logits(params["lm_head"], params["embed"], x, cfg)
        return logits, new_caches

    def mixed_step(self, params: Dict, batch: Dict, caches,
                   cache_index: jnp.ndarray, n_new: jnp.ndarray, *,
                   mesh=None, slot_mask: Optional[jnp.ndarray] = None,
                   pages=None) -> Tuple[jnp.ndarray, Any]:
        """One fixed-shape mixed step: up to ``S`` tokens per row, packing
        prefill-chunk rows (``n_new[b] > 1``) alongside decode rows
        (``n_new[b] == 1``) and inert rows (``n_new[b] == 0``) in a single
        jitted forward. batch: {"inputs": (B, S)} left-aligned — row b's
        columns ``[0, n_new[b])`` are its fresh tokens at absolute positions
        ``[cache_index[b], cache_index[b] + n_new[b])``.

        Requires the paged cache layout (``pages``) and an attention-only
        stack: recurrent blocks have no variable-token mixed path (the
        serving engine gates them back to phase-serialized admission).
        Returns ``(B, V)`` logits of each row's last fresh column ``n_new[b]
        - 1`` (clamped into range; an inert row reads column 0), the
        column the caller samples row b's next token from. The head runs on
        those B columns only: all-position logits of a 512-wide chunk over a
        152k vocabulary would take gigabytes.
        ``cache_index``/``slot_mask`` semantics match :meth:`decode_step`;
        the per-row chunk K/V is written into the paged lanes through the
        block tables after attention (pre-write lane view + causal in-row
        chunk — see :func:`repro.kernels.tda.ref.mixed_attention_reference`
        for the mask contract).
        """
        cfg = self.cfg
        ref = batch["embeds"] if cfg.external_embeddings else batch["inputs"]
        B, S = ref.shape[0], ref.shape[1]
        ci = jnp.asarray(cache_index, jnp.int32)
        nn = jnp.asarray(n_new, jnp.int32)
        positions = (jnp.reshape(ci, (-1, 1))
                     + jnp.arange(S, dtype=jnp.int32)[None, :])
        dicts = params.get("dicts")
        x = self._embed_in(params, batch, positions)
        x, new_caches, _ = self._stack_forward(
            params, x, dicts=dicts, positions=positions, seg_ids=None,
            caches=caches, cache_index=ci, mesh=mesh, sparse_train=False,
            unroll=cfg.unroll_decode, slot_mask=slot_mask, pages=pages,
            n_new=nn)
        last = jnp.clip(nn - 1, 0, S - 1)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)  # (B, 1, d)
        x = L.apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = L.lm_logits(params["lm_head"], params["embed"], x, cfg)
        return logits[:, 0], new_caches

    def prefill(self, params: Dict, batch: Dict, *, mesh=None,
                max_len: int = 0) -> Tuple[jnp.ndarray, Any]:
        """Forward that also fills caches; returns (logits, caches).
        ``max_len`` sizes the cache (>= prefill length + decode budget)."""
        cfg = self.cfg
        ref = batch["embeds"] if cfg.external_embeddings else batch["inputs"]
        B, Ss = ref.shape[0], ref.shape[1]
        caches = self.init_cache(B, max(max_len, Ss))
        h, new_caches, _ = self.hidden(params, batch, mesh=mesh,
                                       caches=caches,
                                       cache_index=jnp.int32(0))
        # Serving prefill only needs the last position's logits — computing
        # all-position logits at 32k x 150k-vocab would be hundreds of GB.
        logits = L.lm_logits(params["lm_head"], params["embed"], h[:, -1:],
                             cfg)
        return logits, new_caches
