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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.kernels.dmm.ops import lut_matmul
from repro.kernels.smm.ops import compressed_matmul
from repro.kernels.tda.page_write import paged_kv_write
from repro.kernels.tda.tda import (tda_decode_attention, tda_mixed_attention,
                                   tda_paged_decode_attention)

BF16, I32, U8, F32 = jnp.bfloat16, jnp.int32, jnp.uint8, jnp.float32
B, PAGE, D = 8, 128, 128
LAYERS, PAGES, PER_SLOT = 2, 64, 8  # stacked page pool, pages per lane


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The described host's four chips as the serving mesh, 1 x 4, with
    ``launch/mesh.make_local_mesh``'s axes."""
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


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


def _step_program(model, step, pool, sharding, mesh=None, donate=True):
    """The compiled text of a paged decode step, or a mixed step at
    S = 512, over ``pool`` (the stacked k and v pools' shape), with the
    model's shapes as parameters: placed by ``sharding`` (one chip), or
    on ``mesh`` as the engine places them (``launch/sharding``)."""
    from repro.launch import sharding as shd

    shapes = jax.eval_shape(model.init, jax.random.key(0))
    cache = {"k": jax.ShapeDtypeStruct(pool, BF16),
             "v": jax.ShapeDtypeStruct(pool, BF16)}
    if mesh is None:
        place = jax.tree.map(lambda _: sharding, shapes)
        cache_place = jax.tree.map(lambda _: sharding, cache)
    else:
        sharding = NamedSharding(mesh, PartitionSpec())
        place = shd.named(shd.param_specs(shapes, mesh), mesh)
        cache_place = shd.named(shd.slot_cache_specs(cache, mesh, paged=True),
                                mesh)

    def put(tree, where):
        return jax.tree.map(
            lambda a, w: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=w),
            tree, where)

    pages = {"width": PER_SLOT * PAGE, "page_size": PAGE}

    def decode_fn(params, tokens, caches, lengths, n_new, active, bt):
        logits, new = model.decode_step(
            params, {"inputs": tokens}, caches, lengths, slot_mask=active,
            pages=dict(pages, bt=bt), mesh=mesh)
        return jnp.argmax(logits[:, 0, :], -1), new

    def mixed_fn(params, tokens, caches, lengths, n_new, active, bt):
        logits, new = model.mixed_step(
            params, {"inputs": tokens}, caches, lengths, n_new,
            slot_mask=active, pages=dict(pages, bt=bt), mesh=mesh)
        return jnp.argmax(logits, -1), new

    fn, width = (decode_fn, 1) if step == "decode" else (mixed_fn, 512)
    shapes_in = [((B, width), I32), ((B,), I32), ((B,), I32),
                 ((B,), jnp.bool_), ((B, PER_SLOT), I32)]
    t, ln, nn, act, bt = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
                          for s, dt in shapes_in]
    return jax.jit(fn, donate_argnums=(2,) if donate else ()).lower(
        put(shapes, place), t, put(cache, cache_place), ln, nn, act,
        bt).compile().as_text()


def _pool_moves(text, *pool_shapes):
    """Lines of XLA operations (copy, fusion, slice, update, scatter) that
    yield an array of one of ``pool_shapes``, in any layout."""
    dims = [sorted(d) for d in pool_shapes]
    op = re.compile(r"=\s*(.*?)\s(copy|fusion|dynamic-slice|"
                    r"dynamic-update-slice|scatter|copy-start)\(")
    moves = []
    for line in text.splitlines():
        m = op.search(line)
        if m and any(sorted(int(d) for d in found.split(",")) in dims
                     for found in re.findall(r"\w+\[([\d,]+)\]",
                                             m.group(1))):
            moves.append(line[:200])
    return moves


def _kernels(text):
    """The ``op_name`` of each Mosaic kernel in a compiled program."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_paged_step_moves_no_pool_layer(one_chip, monkeypatch, step):
    """The whole decode step, and the mixed step at S = 512, at qwen1.5-4b
    widths (two layers, a 16-page pool): no XLA operation (copy, fusion,
    slice, update, scatter) yields an array of a layer's pool or of the
    stacked pool, in any layout — the pool is read and written in place
    by kernels that address it by (layer, page) — and the write's Mosaic
    kernel carries the ``kv_write`` name scope, so a trace charges it to
    the KV write. No op relayouts the q or k weights for RoPE, and a
    one-chip program has no ``tp_reduce`` scope."""
    from repro.kernels import common
    from repro.launch.serve import build_model
    from repro.models.layers import KV_WRITE_SCOPE, TP_REDUCE_SCOPE

    # The program sees the CPU backend here; compile its kernels for the
    # described chip, not the interpreter.
    monkeypatch.setattr(common, "pallas_interpret_default", lambda: False)
    L, P, H = 2, 16, 20
    model = build_model("qwen1.5-4b", "full", n_layers=L, decode_attn="tda")
    text = _step_program(model, step, (L, P, H, PAGE, D), one_chip)
    moves = _pool_moves(text, (L, P, H, PAGE, D), (P, H, PAGE, D))
    assert not moves, moves
    # RoPE splits pairs on the activations: the q and k weights are not
    # relayouted into (heads, D / 2, 2, d_model) pairs on every call.
    relayouts = _pool_moves(text, (H, D // 2, 2, model.cfg.d_model))
    assert not relayouts, relayouts
    assert any(f"/{KV_WRITE_SCOPE}/" in name for name in _kernels(text))
    assert TP_REDUCE_SCOPE not in text and "shard_map" not in text


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_tp4_step_runs_the_kernels_per_rank(four_chips, monkeypatch, step):
    """starcoder2-15b at its published widths (two of its 40 layers) on
    the described 2x2 host, sharded as the engine shards it (4-way over
    its 4 KV heads): the step's kernels run per rank inside the
    ``shard_map`` (decode: the page write and the paged decode kernel;
    mixed: the mixed kernel and the page write), no XLA operation yields
    a rank's pool or a layer of it, and the attention adds no collective.
    The program holds five: in the layer loop the all-reduces of the two
    row-parallel projections (attention output, feed-forward down), each
    under ``tp_reduce``; outside it one all-reduce that assembles the
    embedding rows from the vocabulary-sharded table and two all-gathers
    of the vocabulary-sharded head's per-rank argmax."""
    from repro.kernels import common
    from repro.launch.serve import build_model
    from repro.models.layers import (ATTN_SCOPE, KV_WRITE_SCOPE,
                                     TP_REDUCE_SCOPE)

    monkeypatch.setattr(common, "pallas_interpret_default", lambda: False)
    L, P, HKV = 2, 320, 4  # 21 MB a rank's pool: too large to prefetch
    model = build_model("starcoder2-15b", "full", n_layers=L,
                        decode_attn="tda")
    text = _step_program(model, step, (L, P, HKV, PAGE, D), None,
                         mesh=four_chips)
    rank = HKV // 4
    moves = _pool_moves(text, (L, P, rank, PAGE, D), (P, rank, PAGE, D),
                        (L, P, PAGE, D), (P, PAGE, D))
    assert not moves, moves
    attn = {"decode": "tda_paged_decode_attention",
            "mixed": "tda_mixed_attention"}[step]
    kernels = _kernels(text)
    assert len(kernels) == 2, kernels
    for scope, kernel in ((KV_WRITE_SCOPE, "paged_kv_write"),
                          (ATTN_SCOPE, attn)):
        assert any(n.endswith(f"/shard_map/{scope}/jit({kernel})/pallas_call")
                   for n in kernels), kernels
    coll = re.compile(r"=\s*\S+\s(all-reduce|all-gather|reduce-scatter|"
                      r"collective-permute|all-to-all)(-start)?\(")
    found = [(m.group(1), re.search(r'op_name="([^"]*)"', line).group(1))
             for line in text.splitlines() if (m := coll.search(line))]
    in_loop = [c for c in found if "/while/body/" in c[1]]
    assert [kind for kind, _ in in_loop] == ["all-reduce"] * 2, found
    assert all(f"/{TP_REDUCE_SCOPE}/" in name for _, name in in_loop), found
    assert sorted(kind for kind, _ in found) == (
        ["all-gather"] * 2 + ["all-reduce"] * 3), found
