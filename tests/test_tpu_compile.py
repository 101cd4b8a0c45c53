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


def _placed(eng, args, place):
    """A step's arguments (``Engine._step_args``) as shapes placed on
    ``place``: a described chip's ``Sharding`` takes every input; a
    ``Mesh`` takes the parameters (the model's shapes) and the page pool
    sharded as a serving mesh places them (``launch/sharding``) and the
    rest replicated. The static last argument stays."""
    from repro.launch import sharding as shd

    def put(tree, where):
        return jax.tree.map(
            lambda a, w: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=w),
            tree, where)

    params = jax.eval_shape(eng.model.init, jax.random.key(0))
    caches = args[2]
    if isinstance(place, Mesh):
        rest = NamedSharding(place, PartitionSpec())
        at_params = shd.named(shd.param_specs(params, place), place)
        at_caches = shd.named(shd.slot_cache_specs(caches, place, paged=True),
                              place)
    else:
        rest = place
        at_params = jax.tree.map(lambda _: place, params)
        at_caches = jax.tree.map(lambda _: place, caches)

    def replicated(a):
        return put(a, jax.tree.map(lambda _: rest, a))

    return [put(params, at_params), replicated(args[1]),
            put(caches, at_caches), *map(replicated, args[3:-1]), args[-1]]


def _engine_programs(arch, place, rows=None, **engine):
    """The engine's own decode step and chunk step at S = 512, pool
    donated, compiled for ``place`` — one described chip, or the described
    host's mesh — with ``arch`` at its published widths (two layers), the
    TDA kernels compiled by Mosaic and the engine sized by ``engine``.
    Returns the engine and the programs' text: ``decode`` and the chunk
    step at each of ``rows`` (``{key: row count}``; by default ``mixed``
    at one row and ``mixed_every_row`` at its most)."""
    from repro.kernels import common
    from repro.launch.serve import build_model
    from repro.serve import Engine, EngineConfig

    model = build_model(arch, "full", n_layers=2, decode_attn="tda")
    eng = Engine(model, None, config=EngineConfig(
        max_len=512, max_new_tokens=512, page_size=PAGE, decode_attn="tda",
        **engine))
    decode, mixed = eng._step_programs(
        place if isinstance(place, Mesh) else None, donate=True)
    n, S = eng.num_slots, eng._chunk_width
    x = eng._slot_inputs()
    steps = {"decode": (decode, eng._step_args(x, np.zeros((n, 1), I32)))}
    rows = rows or {"mixed": 1, "mixed_every_row": eng._chunk_rows}
    for key, r in rows.items():
        steps[key] = (mixed, eng._step_args(
            x, np.zeros((r, S), I32), n_new=np.zeros(n, I32),
            rows=np.zeros(r, I32)))
    # The program sees the CPU backend here; compile its kernels for the
    # described chip, not the interpreter.
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "pallas_interpret_default", lambda: False)
        return eng, {k: program.lower(*_placed(eng, args, place)).compile()
                     .as_text() for k, (program, args) in steps.items()}


@pytest.fixture(scope="module")
def qwen_steps(one_chip):
    """qwen1.5-4b on one chip: 8 slots, a 16-page pool."""
    return _engine_programs("qwen1.5-4b", one_chip, num_slots=B,
                            max_prompt_len=512, page_cap=16)


@pytest.fixture(scope="module")
def starcoder2_steps(four_chips):
    """starcoder2-15b on the 2x2 host: 16 slots of 4096-token lanes (its
    window), a 512-page pool, 32 MB a rank."""
    return _engine_programs("starcoder2-15b", four_chips, num_slots=16,
                            max_prompt_len=3584)


def _arrays_of(text, shape):
    """Lines of ``text`` holding an array of ``shape``, in any layout."""
    dims = sorted(shape)
    return [line[:200] for line in text.splitlines()
            if any(sorted(int(d) for d in found.split(",")) == dims
                   for found in re.findall(r"\w+\[([\d,]+)\]", line))]


@pytest.mark.parametrize("engine", ["qwen_steps", "starcoder2_steps"])
def test_chunk_step_runs_only_its_rows(request, engine):
    """The chunk step is compiled at (rows, S), one row up to every slot:
    no operand of the decode step or of the one-row chunk step has the
    shape (num_slots, S, d_model) that a step paying S columns for every
    slot would carry."""
    eng, programs = request.getfixturevalue(engine)
    assert eng._chunk_rows == eng.num_slots
    d = eng.model.cfg.d_model
    assert _arrays_of(programs["mixed"], (1, 512, d))
    assert _arrays_of(programs["mixed_every_row"], (eng.num_slots, 512, d))
    for step in ("decode", "mixed"):
        assert not _arrays_of(programs[step], (eng.num_slots, 512, d))


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


def test_tp4_one_row_chunk_step_moves_a_small_pool(four_chips):
    """What the 512-page pool of ``starcoder2_steps`` stays clear of: at
    320 pages (two layers, 21 MB a rank) XLA moves a rank's whole stacked
    pool into another memory space and back (``copy-start`` to and from
    ``S(1)``) at every one-row chunk step, and not at two rows. The cell's
    pool (40 layers, 288 pages, 377 MB a rank) is not moved at any row
    count (PERF.md, the compiled cell programs)."""
    P = 320
    eng, programs = _engine_programs(
        "starcoder2-15b", four_chips, rows={"mixed": 1, "mixed_two_rows": 2},
        num_slots=16, max_prompt_len=3584, page_cap=P)
    pool = (2, P, 1, PAGE, D)
    assert eng.slots.caches["k"].shape == (2, P, 4, PAGE, D)
    one_row = _pool_moves(programs["mixed"], pool)
    assert one_row and all("copy-start(" in m and "S(1)}" in m
                           for m in one_row), one_row
    assert not _pool_moves(programs["mixed_two_rows"], pool)


def _kernels(text):
    """The ``op_name`` of each Mosaic kernel in a compiled program."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("step", ["decode", "mixed", "mixed_every_row"])
def test_paged_step_moves_no_pool_layer(qwen_steps, step):
    """The engine's decode step, and its chunk step at S = 512 (one row,
    and every slot's), at qwen1.5-4b widths (two layers, a 16-page
    pool): no XLA operation (copy, fusion, slice, update, scatter) yields
    an array of a layer's pool or of the stacked pool, in any layout —
    the pool is read and written in place by kernels that address it by
    (layer, page) — and the write's Mosaic kernel carries the
    ``kv_write`` name scope, so a trace charges it to the KV write. No op
    relayouts the q or k weights for RoPE, and a one-chip program has no
    ``tp_reduce`` scope."""
    from repro.models.layers import KV_WRITE_SCOPE, TP_REDUCE_SCOPE

    eng, programs = qwen_steps
    text = programs[step]
    L, P, H = 2, 16, 20
    assert eng.slots.caches["k"].shape == (L, P, H, PAGE, D)
    moves = _pool_moves(text, (L, P, H, PAGE, D), (P, H, PAGE, D))
    assert not moves, moves
    # RoPE splits pairs on the activations: the q and k weights are not
    # relayouted into (heads, D / 2, 2, d_model) pairs on every call.
    relayouts = _pool_moves(text, (H, D // 2, 2, eng.model.cfg.d_model))
    assert not relayouts, relayouts
    assert any(f"/{KV_WRITE_SCOPE}/" in name for name in _kernels(text))
    assert TP_REDUCE_SCOPE not in text and "shard_map" not in text


@pytest.mark.parametrize("step", ["decode", "mixed", "mixed_every_row"])
def test_tp4_step_runs_the_kernels_per_rank(starcoder2_steps, step):
    """starcoder2-15b at its published widths (two of its 40 layers) on
    the described 2x2 host, sharded as the engine shards it (4-way over
    its 4 KV heads): the step's kernels run per rank inside the
    ``shard_map`` (decode: the page write and the paged decode kernel;
    mixed: the mixed kernel and the page write), no XLA operation yields
    a rank's pool or a layer of it, and the attention adds no collective.
    The program holds six: in the layer loop the all-reduces of the two
    row-parallel projections (attention output, feed-forward down), each
    under ``tp_reduce``; outside it one all-reduce that assembles the
    embedding rows from the vocabulary-sharded table, two that collect
    the vocabulary-sharded head's per-rank argmax (value and index:
    all-gathers, or all-reduces where a rank holds one row), and the
    all-reduce of the in-graph finiteness guard over the sharded
    logits."""
    from repro.models.layers import (ATTN_SCOPE, KV_WRITE_SCOPE,
                                     TP_REDUCE_SCOPE)

    eng, programs = starcoder2_steps
    text = programs[step]
    L, P, HKV = 2, 512, 4
    assert eng.slots.caches["k"].shape == (L, P, HKV, PAGE, D)
    rank = HKV // 4
    moves = _pool_moves(text, (L, P, rank, PAGE, D), (P, rank, PAGE, D),
                        (L, P, PAGE, D), (P, PAGE, D))
    assert not moves, moves
    attn = "tda_paged_decode_attention" if step == "decode" \
        else "tda_mixed_attention"
    kernels = _kernels(text)
    assert len(kernels) == 2, kernels
    for scope, kernel in ((KV_WRITE_SCOPE, "paged_kv_write"),
                          (ATTN_SCOPE, attn)):
        assert any(n.endswith(f"/shard_map/{scope}/jit({kernel})/pallas_call")
                   for n in kernels), kernels
    coll = re.compile(r"=\s*\S+\s(all-reduce|all-gather|reduce-scatter|"
                      r"collective-permute|all-to-all)(-start)?\(")
    found = [(m.group(1), (re.search(r'op_name="([^"]*)"', line)
                           or [None, ""])[1])
             for line in text.splitlines() if (m := coll.search(line))]
    in_loop = [c for c in found if "/while/body/" in c[1]]
    assert [kind for kind, _ in in_loop] == ["all-reduce"] * 2, found
    assert all(f"/{TP_REDUCE_SCOPE}/" in name for _, name in in_loop), found
    assert len(found) == 6, found
    outside = [name for _, name in found if "/while/" not in name]
    assert sum(name.endswith("(_take)/gather") for name in outside) == 1
    assert sum(name.endswith("/reduce_and") for name in outside) == 1
    argmax = [kind for kind, name in found
              if "/while/" not in name and not name.endswith(
                  ("(_take)/gather", "/reduce_and"))]
    assert argmax in (["all-gather"] * 2, ["all-reduce"] * 2), found
