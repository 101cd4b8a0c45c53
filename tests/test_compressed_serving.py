"""Compressed weights on the decode hot path.

Pins the tentpole contract: a model whose linears are served from
nibble-packed W_S codes + delta/quantized W_D streams must (a) compute the
same function as a forward through the explicitly-decompressed dense
factors (exact up to float reduction order — decompression is
deterministic), (b) stay within quantization tolerance of the original
factorized model, and (c) report strictly fewer estimated HBM bytes per
decoded token than dense-factorized serving of the same workload.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.factorized import (FactorizationConfig,
                                   decompress_model_params, project_wd_leaves)
from repro.models.transformer import Model
from repro.serve import Engine, Request

FCFG = FactorizationConfig(enabled=True, min_dim=32, rank=32, nnz=8)


@pytest.fixture(scope="module", params=["qwen2.5-32b", "dbrx-132b"])
def compressed_model(request):
    cfg = get_config(request.param, "smoke", dtype="float32",
                     factorization=FCFG)
    m = Model(cfg)
    # emulate end-of-training: W_D leaves projected to their sparse support
    params = project_wd_leaves(m.init(jax.random.key(0)), FCFG)
    mc, cparams, stats = m.compress_params(params)
    return m, params, mc, cparams, stats


def test_compressed_forward_equals_decompressed_dense(compressed_model):
    """Tight: the streamed forward is the SAME function as a dense forward
    through explicitly-decompressed factors — only reduction order may
    differ, so tolerance is float-noise, not quantization-noise."""
    m, params, mc, cparams, _ = compressed_model
    recon = decompress_model_params(cparams, params)
    shapes = jax.eval_shape(lambda: params)
    assert jax.tree.structure(decompress_model_params(cparams, shapes)) \
        == jax.tree.structure(params)
    toks = np.random.default_rng(7).integers(
        0, m.cfg.vocab_size, size=12).astype(np.int32)
    batch = {"inputs": jnp.asarray(toks)[None]}
    logits_dense = np.asarray(m.apply(recon, batch)[0])
    logits_comp = np.asarray(mc.apply(cparams, batch)[0])
    np.testing.assert_allclose(logits_comp, logits_dense,
                               rtol=1e-4, atol=1e-4)


def test_compressed_forward_close_to_factorized(compressed_model):
    """Loose: vs the ORIGINAL factorized model the only divergence is 4b/6b
    quantization noise — bounded, and nonzero (compression did happen)."""
    m, params, mc, cparams, stats = compressed_model
    toks = np.random.default_rng(8).integers(
        0, m.cfg.vocab_size, size=12).astype(np.int32)
    batch = {"inputs": jnp.asarray(toks)[None]}
    ref = np.asarray(m.apply(params, batch)[0])
    got = np.asarray(mc.apply(cparams, batch)[0])
    rel = np.abs(got - ref).mean() / (np.abs(ref).mean() + 1e-9)
    assert 0.0 < rel < 0.5  # smoke dims are tiny; real widths sit far lower
    assert stats["weight_compression_ratio"] > 1.5
    assert stats["weight_stream_bits"] < stats["weight_stream_bits_dense"]


def _workload(cfg, n=8, seed=11):
    r = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=r.integers(0, cfg.vocab_size,
                                      size=int(r.integers(4, 12))
                                      ).astype(np.int32),
                    max_new_tokens=int(r.integers(2, 8)))
            for i in range(n)]


@pytest.fixture(scope="module")
def qwen_compressed():
    cfg = get_config("qwen2.5-32b", "smoke", dtype="float32",
                     factorization=FCFG)
    m = Model(cfg)
    params = project_wd_leaves(m.init(jax.random.key(0)), FCFG)
    mc, cparams, stats = m.compress_params(params)
    return m, params, mc, cparams, stats


def _run_engine(model, params, cfg, wsb):
    eng = Engine(model, params, max_len=32, max_new_tokens=8, num_slots=4,
                 decode_block_k=32, paged=True, page_size=8,
                 prefix_share=False, weight_stream_bits=wsb)
    reqs = _workload(cfg)
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs, eng.decode_stats


def test_engine_compressed_token_equal_to_reforward(qwen_compressed):
    """Continuous-batching greedy decode over compressed streams matches a
    single-request full re-forward argmax with the same compressed params."""
    _, _, mc, cparams, stats = qwen_compressed
    reqs, _ = _run_engine(mc, cparams, mc.cfg, stats["weight_stream_bits"])
    r = max(reqs, key=lambda q: q.max_new_tokens)
    seq = list(np.asarray(r.prompt))
    expect = []
    for _ in range(r.max_new_tokens):
        logits = mc.apply(cparams, {"inputs": jnp.asarray(seq)[None]})[0]
        t = int(jnp.argmax(logits[0, -1]))
        expect.append(t)
        seq.append(t)
    assert list(r.output) == expect


def test_engine_bytes_per_token_compressed_below_dense(qwen_compressed):
    """The observability contract gated in tools/check_bench.py: identical
    workload, equal decoded tokens, strictly fewer estimated bytes moved."""
    m, params, mc, cparams, stats = qwen_compressed
    _, ds_dense = _run_engine(m, params, m.cfg,
                              stats["weight_stream_bits_dense"])
    _, ds_comp = _run_engine(mc, cparams, mc.cfg,
                             stats["weight_stream_bits"])
    for ds in (ds_dense, ds_comp):
        for k in ("weight_format", "weight_bytes_per_step",
                  "weight_bytes_per_token", "kv_bytes_per_token",
                  "bytes_per_token"):
            assert k in ds, k
    assert ds_dense["weight_format"] == "dense"
    assert ds_comp["weight_format"] == "compressed"
    assert ds_comp["decoded_tokens"] == ds_dense["decoded_tokens"] > 0
    # same model geometry + schedule -> identical KV traffic; the weight
    # stream is the whole difference
    assert ds_comp["kv_bytes_per_token"] == pytest.approx(
        ds_dense["kv_bytes_per_token"])
    assert 0 < ds_comp["weight_bytes_per_token"] \
        < ds_dense["weight_bytes_per_token"]
    assert 0 < ds_comp["bytes_per_token"] < ds_dense["bytes_per_token"]
    ratio = ds_dense["weight_bytes_per_token"] / \
        ds_comp["weight_bytes_per_token"]
    assert ratio == pytest.approx(stats["weight_compression_ratio"])
