"""TDA fused slot-decode attention vs its jnp oracles.

Equivalence sweeps cover GQA ratios, per-slot depths, masked (inactive)
slots, windowed caches, and int8-quantized KV; the property tests pin down
that predication (block size, cache padding) changes the *work*, never the
result. The dispatch tests exercise the serving wiring:
``layers.decode_attention(impl="tda")`` and a continuous Engine decoding
through the kernel must match the dense path token-for-token.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels.afu.ref import exp_lut_table
from repro.kernels.tda.ops import block_stats, fused_decode_attention
from repro.kernels.tda.ref import decode_attention_reference
from repro.models import layers as L

RNG = np.random.default_rng(0)


def _mk(B, S, Hq, Hkv, D, quant=False):
    q = jnp.asarray(RNG.normal(size=(B, Hq, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
    if not quant:
        return q, k, v, None, None
    kq, ks = L.kv_quantize(k)  # the real serving cache layout
    vq, vs = L.kv_quantize(v)
    return q, kq, vq, ks, vs


# ---- equivalence sweeps ---------------------------------------------------


@pytest.mark.parametrize("B,S,Hq,Hkv,D,bk", [
    (2, 32, 4, 4, 16, 16),    # MHA
    (4, 48, 8, 2, 16, 16),    # GQA 4:1
    (3, 40, 6, 1, 8, 16),     # MQA, padding path (40 % 16 != 0)
    (8, 33, 4, 2, 32, 8),     # odd cache width
    (2, 16, 4, 2, 16, 64),    # block larger than cache -> single block
])
@pytest.mark.parametrize("quant", [False, True])
def test_tda_matches_ref(B, S, Hq, Hkv, D, bk, quant):
    q, k, v, ks, vs = _mk(B, S, Hq, Hkv, D, quant)
    lengths = jnp.asarray(RNG.integers(1, S + 1, size=B), jnp.int32)
    out = fused_decode_attention(q, k, v, lengths, k_scale=ks, v_scale=vs,
                                 block_k=bk)
    ref = decode_attention_reference(q, k, v, lengths, k_scale=ks,
                                     v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_tda_scalar_length_and_4d_query():
    q, k, v, _, _ = _mk(3, 32, 4, 2, 16)
    out = fused_decode_attention(q[:, None], k, v, jnp.int32(20), block_k=8)
    ref = decode_attention_reference(q, k, v, jnp.int32(20))
    assert out.shape == (3, 1, 4, 16)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_tda_masked_slots_output_zero():
    """Inactive lanes (length 0) must come back all-zero, not softmax(0)."""
    q, k, v, _, _ = _mk(4, 32, 4, 2, 16)
    lengths = jnp.asarray([5, 0, 32, 0], jnp.int32)
    out = fused_decode_attention(q, k, v, lengths, block_k=16)
    ref = decode_attention_reference(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(out)[[1, 3]] == 0.0)
    assert np.all(np.abs(np.asarray(out)[[0, 2]]).max((-1, -2)) > 0)


@pytest.mark.parametrize("window", [4, 16, 100])
def test_tda_windowed(window):
    q, k, v, _, _ = _mk(4, 48, 4, 2, 16)
    lengths = jnp.asarray([3, 17, 48, 30], jnp.int32)
    out = fused_decode_attention(q, k, v, lengths, window=window, block_k=16)
    ref = decode_attention_reference(q, k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_tda_lut_exp_close_to_exact():
    """AFU LUT-exp option: within the 64-entry interpolation bound."""
    q, k, v, _, _ = _mk(4, 32, 4, 2, 16)
    lengths = jnp.asarray([5, 17, 32, 9], jnp.int32)
    exact = fused_decode_attention(q, k, v, lengths, block_k=16)
    lut = fused_decode_attention(q, k, v, lengths, block_k=16,
                                 lut_table=exp_lut_table())
    assert float(jnp.abs(lut - exact).max()) < 2e-2
    assert bool(jnp.all(jnp.isfinite(lut)))


# ---- paged lane pool: block-table scalar prefetch --------------------------


LAYERS = 3  # layers of a stacked test pool


def _mk_paged(B, P, ps, Hkv, D, lengths, rng, quant=False):
    """Layer-stacked head-major page pools ``(LAYERS, P, Hkv, ps, D)`` +
    prefix-allocated block tables over a shuffled free list (fragmented
    physical order on purpose)."""
    n = max(-(-int(max(lengths)) // ps), 1)
    kp = jnp.asarray(rng.normal(size=(LAYERS, P, Hkv, ps, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(LAYERS, P, Hkv, ps, D)), jnp.float32)
    free = rng.permutation(P).tolist()
    bt = np.full((B, n), P, np.int32)  # FREE sentinel == P
    for b in range(B):
        for i in range(-(-int(lengths[b]) // ps)):
            bt[b, i] = free.pop()
    if not quant:
        return kp, vp, None, None, jnp.asarray(bt)
    kq, ks = L.kv_quantize(kp)
    vq, vs = L.kv_quantize(vp)
    return kq, vq, ks, vs, jnp.asarray(bt)


def _layer(x, l):
    return None if x is None else x[l]


@pytest.mark.parametrize("quant", [False, True])
def test_tda_paged_matches_gathered_reference(quant):
    """The paged kernel over the stacked pool at layer ``l`` equals the
    gathered reference over that layer's pool, for every ``l``."""
    rng = np.random.default_rng(3)
    B, P, ps, Hq, Hkv, D = 4, 14, 8, 8, 2, 16
    lengths = np.asarray([3, 17, 40, 0], np.int32)
    k, v, ks, vs, bt = _mk_paged(B, P, ps, Hkv, D, lengths, rng, quant)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    lens = jnp.asarray(lengths)
    for l in range(LAYERS):
        out = fused_decode_attention(q, k, v, lens, k_scale=ks, v_scale=vs,
                                     block_table=bt, layer=jnp.int32(l))
        ref = fused_decode_attention(
            q, k[l], v[l], lens, k_scale=_layer(ks, l),
            v_scale=_layer(vs, l), block_table=bt, use_kernel=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        assert np.all(np.asarray(out)[3] == 0.0)  # empty lane -> zeros


def test_tda_paged_equals_contiguous_layout():
    """Scattering the same lanes across shuffled physical pages must not
    change a single output value vs the contiguous kernel."""
    from repro.kernels.tda.ops import gather_paged_lanes
    rng = np.random.default_rng(4)
    B, P, ps, Hq, Hkv, D = 3, 12, 8, 4, 2, 16
    lengths = np.asarray([5, 23, 32], np.int32)
    kp, vp, _, _, bt = _mk_paged(B, P, ps, Hkv, D, lengths, rng)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    lens = jnp.asarray(lengths)
    paged = fused_decode_attention(q, kp, vp, lens, block_table=bt,
                                   layer=jnp.int32(1))
    # contiguous layout = the gathered lane views, through the dense kernel
    kd = gather_paged_lanes(kp, bt, jnp.int32(1))
    vd = gather_paged_lanes(vp, bt, jnp.int32(1))
    contiguous = fused_decode_attention(q, kd, vd, lens, block_k=ps)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(contiguous),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quant,ring,window,block_q", [
    (False, 32, None, None),  # one query tile covers the chunk
    (False, 32, None, 4),     # tiled queries + in-row key tiles, padded tail
    (True, 32, None, 4),      # int8 pool codes dequantized in VMEM
    (False, 12, 12, 8),       # wrapped ring lanes under a window
    (False, 32, None, 2),     # five query tiles: dead tiles repeat blocks
])
def test_tda_mixed_matches_reference(quant, ring, window, block_q):
    """The mixed kernel over the stacked pool at layer ``l`` equals the
    gathered jnp reference over that layer on every fresh column (columns
    >= n_new are garbage by contract), for every ``l`` and whatever the
    query tile — chunk rows, a decode row, an inert row and an empty
    lane."""
    from repro.kernels.tda.ops import fused_mixed_attention
    from repro.kernels.tda.tda import tda_mixed_attention
    rng = np.random.default_rng(5)
    B, P, ps, Hq, Hkv, D, S = 4, 20, 8, 4, 2, 16, 10
    n = -(-ring // ps)
    ci = np.asarray([0, 5, 30, 13], np.int32)
    nn = np.asarray([10, 1, 7, 0], np.int32)
    k, v, ks, vs, _ = _mk_paged(1, P, ps, Hkv, D, [0], rng, quant)
    bt = jnp.asarray(rng.permutation(P)[:B * n].reshape(B, n), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    vr = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    bounds = jnp.asarray(np.stack([ci, nn], axis=1))
    for l in range(LAYERS):
        ref = fused_mixed_attention(
            q, k[l], v[l], kr, vr, jnp.asarray(ci), jnp.asarray(nn),
            block_table=bt, ring=ring, window=window, k_scale=_layer(ks, l),
            v_scale=_layer(vs, l), use_kernel=False)
        out = tda_mixed_attention(
            q, k, v, jnp.swapaxes(kr, 1, 2), jnp.swapaxes(vr, 1, 2),
            jnp.int32(l), bounds, bt, ks, vs, ring=ring, window=window,
            block_q=block_q, interpret=True)
        assert out.shape == ref.shape
        for b in range(B):
            np.testing.assert_allclose(np.asarray(out)[b, :nn[b]],
                                       np.asarray(ref)[b, :nn[b]],
                                       rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("S,ring,ps,ci,nn,quant", [
    # decode rows: an inactive row, a write into the last offset of a page
    (1, 32, 8, [0, 5, 31, 7], [1, 1, 1, 0], False),
    (1, 32, 8, [0, 5, 31, 7], [1, 1, 1, 0], True),   # int8 codes + scales
    (1, 12, 8, [3, 12, 25, 0], [1, 1, 1, 1], False),  # ring write pointer
    (1, 32, 8, [9, 5, 6, 7], [1, 1, 1, 1], True),  # rows 1-3 share a page
    # chunks crossing page boundaries, a decode row and an n_new == 0 row
    (10, 32, 8, [0, 5, 20, 13], [10, 1, 7, 0], False),
    (10, 12, 8, [0, 5, 20, 13], [10, 1, 7, 9], False),  # ring wraps
    (24, 12, 8, [3, 5, 20, 0], [24, 13, 12, 0], False),  # chunk > ring
    (10, 6, 4, [3, 5, 20, 1], [10, 3, 4, 2], False),  # ring not in tiles
])
def test_paged_kv_write_matches_scatter(S, ring, ps, ci, nn, quant):
    """The in-place write kernel leaves the stacked pool exactly as the
    XLA ``mode="drop"`` scatter does: rows with ``n_new == 0`` and
    sentinel pages write nothing, only the last ``ring`` columns of a
    wrapping chunk write, rows meeting in one tile all land, and every
    other layer stays untouched."""
    from repro.kernels.tda.page_write import (paged_kv_write,
                                              paged_kv_write_reference)
    rng = np.random.default_rng(6)
    B, P, Hkv, D, layer = 4, 24, 2, 16, 1
    n = -(-ring // ps)
    bt = rng.permutation(P)[:B * n].reshape(B, n).astype(np.int32)
    bt[0, -1] = P  # an unallocated (FREE) page
    if ci[1:] == [5, 6, 7]:  # three rows write one page, one tile of it
        bt[2:] = bt[1]
    pools = [jnp.asarray(rng.normal(size=(LAYERS, P, Hkv, ps, D)),
                         jnp.float32) for _ in range(2)]
    news = [jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
            for _ in range(2)]
    if quant:
        (kq, ks), (vq, vs) = (L.kv_quantize(x) for x in pools)
        (nkq, nks), (nvq, nvs) = (L.kv_quantize(x) for x in news)
        pools, news = [kq, vq, ks, vs], [nkq, nvq, nks, nvs]
    args = (jnp.int32(layer), jnp.asarray(ci, jnp.int32),
            jnp.asarray(nn, jnp.int32), jnp.asarray(bt))
    want = paged_kv_write_reference(pools, news, *args, ring=ring)
    got = paged_kv_write(pools, news, *args, ring=ring, interpret=True)
    for w, g, p in zip(want, got, pools):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        keep = np.arange(LAYERS) != layer
        np.testing.assert_array_equal(np.asarray(g)[keep],
                                      np.asarray(p)[keep])


def _fetches(index_map, steps, *prefetch):
    """Block copies a Pallas pipeline issues over ``steps``: one whenever
    the block index differs from the previous step's."""
    n, prev = 0, None
    for idx in steps:
        blk = tuple(int(x) for x in index_map(*idx, *prefetch))
        n += blk != prev
        prev = blk
    return n


@pytest.mark.parametrize("lo,hi", [(0, 0), (0, 1), (0, 13), (5, 31),
                                   (17, 24), (0, 32)])
def test_decode_fetches_follow_occupancy(lo, hi):
    """A decode grid row fetches each block that meets ``[lo, hi)`` once
    and nothing for the blocks it skips (an empty span pins one block)."""
    from repro.kernels.tda.tda import _live_block
    bk, nk = 8, 4
    blocks = [int(_live_block(kb, lo, hi, bk, nk)) for kb in range(nk)]
    live = [kb for kb in range(nk) if kb * bk < hi and kb * bk + bk > lo]
    assert all(blocks[kb] == kb for kb in live)
    assert len(set(blocks)) == max(len(live), 1)
    assert blocks == sorted(blocks)


def test_mixed_fetches_follow_occupancy():
    """Mixed-step DMA: dead query tiles, cache steps past the resident span
    and in-row key tiles past the diagonal fetch nothing. A decode row
    reads its resident pages once; each live tile of a chunk row reads the
    resident pages once more and its causal key tiles."""
    from repro.kernels.tda.tda import _mixed_maps
    nk, ps, ring, tq, nq, P = 4, 8, 32, 4, 3, 20
    maps = _mixed_maps(nk=nk, nq=nq, tq=tq, ps=ps, ring=ring, P=P)
    q_map, pool_map, _, row_map, out_map = maps
    ci = np.asarray([0, 13, 30, 20, 9], np.int32)
    nn = np.asarray([12, 1, 6, 0, 4], np.int32)
    bounds = np.stack([ci, nn], axis=1)
    bt = np.arange(5 * nk, dtype=np.int32).reshape(5, nk)
    layer = np.asarray([2], np.int32)
    for b in range(len(ci)):
        steps = [(b, qi, kb) for qi in range(nq) for kb in range(nk + nq)]
        n_live = -(-nn[b] // tq)
        n_res = max(-(-min(ci[b], ring) // ps), 1)
        pool = _fetches(pool_map, steps, layer, bounds, bt)
        rows = _fetches(row_map, steps, layer, bounds, bt)
        # an inert row (no live tile) pins its last resident page
        assert pool == (1 if n_live == 0 or n_res == 1
                        else n_live * n_res), b
        # tile 0 reads key tile 0; tile qi >= 1 re-reads 0 (unless the
        # previous tile ended on it) and then 1..qi
        assert rows == max(1, sum(qi + 1 for qi in range(n_live))
                           - (n_live >= 2)), b
        assert _fetches(q_map, steps, layer, bounds, bt) == max(n_live, 1), b
        assert _fetches(out_map, steps, layer, bounds, bt) == nq, b


# ---- property: predication changes work, never results --------------------


@given(st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_tda_block_size_invariance(seed):
    rng = np.random.default_rng(seed)
    B, S, Hq, Hkv, D = 3, 40, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    lengths = jnp.asarray(rng.integers(0, S + 1, size=B), jnp.int32)
    outs = [fused_decode_attention(q, k, v, lengths, block_k=bk)
            for bk in (5, 8, 16, 40, 128)]
    for o in outs[1:]:  # different grids, different visited sets — same math
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=1e-5, atol=1e-5)


@given(st.integers(0, 1000))
@settings(max_examples=5, deadline=None)
def test_tda_cache_padding_invariance(seed):
    """Growing the cache (dead tail past every length) adds skipped blocks
    but cannot change any output value."""
    rng = np.random.default_rng(seed)
    B, S, Hq, Hkv, D = 2, 24, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    k = rng.normal(size=(B, S, Hkv, D)).astype(np.float32)
    v = rng.normal(size=(B, S, Hkv, D)).astype(np.float32)
    lengths = jnp.asarray(rng.integers(1, S + 1, size=B), jnp.int32)
    pad = ((0, 0), (0, 40), (0, 0), (0, 0))
    out = fused_decode_attention(q, jnp.asarray(k), jnp.asarray(v), lengths,
                                 block_k=8)
    big = fused_decode_attention(q, jnp.asarray(np.pad(k, pad)),
                                 jnp.asarray(np.pad(v, pad)), lengths,
                                 block_k=8)
    np.testing.assert_allclose(np.asarray(big), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    s1 = block_stats(np.asarray(lengths), S, 8)
    s2 = block_stats(np.asarray(lengths), S + 40, 8)
    assert s1["visited"] == s2["visited"]  # dead tail is never visited
    assert s2["dense"] > s1["dense"]


def test_block_stats_accounting():
    assert block_stats([8], 64, 8) == {"visited": 1, "dense": 8,
                                       "ratio": 1 / 8}
    assert block_stats([64, 64], 64, 8)["ratio"] == 1.0
    assert block_stats([0, 0], 64, 8)["visited"] == 0
    w = block_stats([64], 64, 8, window=8)
    assert w["visited"] == 1  # only the last block falls in the window
    assert block_stats(17, 64, 8, batch=4)["visited"] == 4 * 3


# ---- serving wiring -------------------------------------------------------


def test_layers_dispatch_matches_dense():
    """decode_attention(impl='tda') == impl='dense' on fp and int8 caches."""
    B, S, Hq, Hkv, D = 4, 32, 4, 2, 16
    q4 = jnp.asarray(RNG.normal(size=(B, 1, Hq, D)), jnp.float32)
    _, k, v, ks, vs = _mk(B, S, Hq, Hkv, D, quant=True)
    idx = jnp.asarray([1, 7, 32, 15], jnp.int32)
    dense = L.decode_attention(q4, k, v, idx, k_scale=ks, v_scale=vs,
                               impl="dense")
    fused = L.decode_attention(q4, k, v, idx, k_scale=ks, v_scale=vs,
                               impl="tda", block_k=16)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    _, kf, vf, _, _ = _mk(B, S, Hq, Hkv, D)
    dense = L.decode_attention(q4, kf, vf, idx, window=8)
    fused = L.decode_attention(q4, kf, vf, idx, window=8, impl="tda",
                               block_k=16)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv_quant", [False, True])
def test_engine_tda_decode_matches_dense(kv_quant):
    """Continuous engine decoding through the fused kernel emits the same
    tokens as the dense path — mixed lengths, mid-decode admissions."""
    from repro.configs import get_config
    from repro.models.transformer import Model
    from repro.serve import Engine, Request

    cfg = get_config("qwen2.5-32b", "smoke", kv_quant=kv_quant)
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (3, 11, 7)]

    def run(mode):
        eng = Engine(m, params, max_len=16, max_new_tokens=4, num_slots=2,
                     decode_attn=mode, decode_block_k=16)
        assert eng.decode_attn == mode
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p))
        outs = {r.rid: r.output for r in eng.run()}
        return outs, eng.decode_stats

    dense_out, dense_stats = run("dense")
    tda_out, tda_stats = run("tda")
    assert tda_out == dense_out
    assert all(len(o) == 4 for o in tda_out.values())
    # predicated work strictly below the dense sweep on this workload
    assert 0 < tda_stats["kv_block_ratio"] < 0.7
    assert tda_stats["kv_blocks_visited"] == dense_stats["kv_blocks_visited"]


def test_engine_auto_resolves_by_backend():
    from repro.configs import get_config
    from repro.kernels.common import resolve_decode_attn
    from repro.models.transformer import Model
    from repro.serve import Engine

    cfg = get_config("qwen2.5-32b", "smoke")
    eng = Engine(Model(cfg), params=None, max_len=16, num_slots=2)
    assert eng.decode_attn == resolve_decode_attn("auto")
    if jax.default_backend() == "cpu":
        assert eng.decode_attn == "dense"  # interpret Pallas never on hot path
