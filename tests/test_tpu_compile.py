"""Compile the serving path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles one kernel at the widths the
chip smoke serves (qwen1.5-4b: 20 heads x 128, pages of 128 tokens, mixed
chunks of 512 columns, factorized rank 1664 with 208 nonzeros per column)
for a chip that is described, not attached, and checks that the Mosaic
kernel is in the program (``tpu_custom_call``). This catches what interpret
mode cannot: block shapes the TPU tiling refuses, more VMEM than a kernel
may use, primitives Mosaic cannot lower.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dmm.ops import lut_matmul
from repro.kernels.smm.ops import compressed_matmul
from repro.kernels.tda.page_write import paged_kv_write
from repro.kernels.tda.tda import (tda_decode_attention, tda_mixed_attention,
                                   tda_paged_decode_attention)

BF16, I32, U8, F32 = jnp.bfloat16, jnp.int32, jnp.uint8, jnp.float32
B, PAGE, D = 8, 128, 128
LAYERS, PAGES, PER_SLOT = 2, 64, 8  # stacked page pool, pages per lane


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("hq,hkv", [(20, 20), (48, 4)])
def test_paged_decode_compiles(one_chip, hq, hkv):
    pool = ((LAYERS, PAGES, hkv, PAGE, D), BF16)
    text = _compiled_text(
        lambda q, k, v, l, bd, bt: tda_paged_decode_attention(
            q, k, v, l, bd, bt, interpret=False), one_chip,
        ((B, hq, D), BF16), pool, pool, ((), I32), ((B, 2), I32),
        ((B, PER_SLOT), I32))
    assert "tpu_custom_call" in text


def test_mixed_compiles_at_chunk_512(one_chip):
    S, H = 512, 20
    pool = ((LAYERS, PAGES, H, PAGE, D), BF16)
    text = _compiled_text(
        lambda q, k, v, kr, vr, l, bd, bt: tda_mixed_attention(
            q, k, v, kr, vr, l, bd, bt, ring=PER_SLOT * PAGE,
            interpret=False),
        one_chip, ((B, S, H, D), BF16), pool, pool, ((B, H, S, D), BF16),
        ((B, H, S, D), BF16), ((), I32), ((B, 2), I32),
        ((B, PER_SLOT), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("S,quant", [(1, False), (1, True), (512, False)])
def test_paged_kv_write_compiles(one_chip, S, quant):
    """The in-place pool write: a decode step's tokens (bf16, or int8
    codes with their scales) and a mixed step's 512-column chunks."""
    H = 20
    dt = jnp.int8 if quant else BF16
    leaves = [((LAYERS, PAGES, H, PAGE, D), dt)] * 2
    news = [((B, S, H, D), dt)] * 2
    if quant:
        leaves += [((LAYERS, PAGES, H, PAGE), F32)] * 2
        news += [((B, S, H), F32)] * 2
    n = len(leaves)

    def write(*a):
        return paged_kv_write(a[:n], a[n:2 * n], *a[2 * n:],
                              ring=PER_SLOT * PAGE, interpret=False)

    text = _compiled_text(write, one_chip, *leaves, *news, ((), I32),
                          ((B,), I32), ((B,), I32), ((B, PER_SLOT), I32))
    assert "tpu_custom_call" in text


def test_contiguous_decode_compiles(one_chip):
    S, H = PER_SLOT * PAGE, 20
    text = _compiled_text(
        lambda q, k, v, bd: tda_decode_attention(q, k, v, bd, block_k=PAGE,
                                                 interpret=False),
        one_chip, ((B, H, D), BF16), ((B, S, H, D), BF16),
        ((B, S, H, D), BF16), ((B, 2), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [8, 4096])  # a decode step, a mixed step
def test_compressed_linear_compiles(one_chip, m):
    d_in, r, nnz, d_out = 2560, 1664, 208, 6912

    def linear(x, codes, lut, first, deltas, vq, scale, offset, bits):
        y = lut_matmul(x, codes, lut, interpret=False)
        return compressed_matmul(y, first, deltas, vq, scale, offset,
                                 value_bits=bits, interpret=False)

    text = _compiled_text(
        linear, one_chip, ((m, d_in), BF16), ((d_in // 2, r), U8),
        ((16,), F32), ((d_out,), I32), ((nnz - 1, d_out), U8),
        ((nnz, d_out), U8), ((), F32), ((), F32), ((), I32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_paged_step_moves_no_pool_layer(one_chip, monkeypatch, step):
    """The whole decode step, and the mixed step at S = 512, at qwen1.5-4b
    widths (two layers, a 16-page pool): no XLA operation (copy, fusion,
    slice, update, scatter) yields an array of a layer's pool or of the
    stacked pool, in any layout — the pool is read and written in place
    by kernels that address it by (layer, page) — and the write's Mosaic
    kernel carries the ``kv_write`` name scope, so a trace charges it to
    the KV write."""
    import re

    from repro.kernels import common
    from repro.launch.serve import build_model
    from repro.models.layers import KV_WRITE_SCOPE

    # The program sees the CPU backend here; compile its kernels for the
    # described chip, not the interpreter.
    monkeypatch.setattr(common, "pallas_interpret_default", lambda: False)
    L, P, H, S = 2, 16, 20, 512
    model = build_model("qwen1.5-4b", "full", n_layers=L, decode_attn="tda")
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.key(0)))
    pool = jax.ShapeDtypeStruct((L, P, H, PAGE, D), BF16, sharding=one_chip)
    pages = {"width": PER_SLOT * PAGE, "page_size": PAGE}

    def decode_fn(params, tokens, caches, lengths, n_new, active, bt):
        logits, new = model.decode_step(
            params, {"inputs": tokens}, caches, lengths, slot_mask=active,
            pages=dict(pages, bt=bt))
        return jnp.argmax(logits[:, 0, :], -1), new

    def mixed_fn(params, tokens, caches, lengths, n_new, active, bt):
        logits, new = model.mixed_step(
            params, {"inputs": tokens}, caches, lengths, n_new,
            slot_mask=active, pages=dict(pages, bt=bt))
        return jnp.argmax(logits, -1), new

    fn, width = (decode_fn, 1) if step == "decode" else (mixed_fn, S)
    shapes = [((B, width), I32), ((B,), I32), ((B,), I32), ((B,), jnp.bool_),
              ((B, PER_SLOT), I32)]
    t, ln, nn, act, bt = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                          for s, dt in shapes]
    text = jax.jit(fn, donate_argnums=(2,)).lower(
        params, t, {"k": pool, "v": pool}, ln, nn, act,
        bt).compile().as_text()
    pool_dims = (sorted((L, P, H, PAGE, D)), sorted((P, H, PAGE, D)))
    op = re.compile(r"=\s*(.*?)\s(copy|fusion|dynamic-slice|"
                    r"dynamic-update-slice|scatter)\(")
    moves = []
    for line in text.splitlines():
        m = op.search(line)
        if m and any(sorted(int(d) for d in dims.split(",")) in pool_dims
                     for dims in re.findall(r"\w+\[([\d,]+)\]",
                                            m.group(1))):
            moves.append(line[:200])
    assert not moves, moves
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert any(f"/{KV_WRITE_SCOPE}/" in line for line in kernels)
