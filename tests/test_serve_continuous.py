"""Continuous-batching engine: slot KV cache, scheduler, and exact
equivalence of packed-prefill + slot-based decode vs unpacked decoding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.packing import chunk_prompt
from repro.models.transformer import Model
from repro.serve import (
    DynamicBatcher,
    Engine,
    Request,
    Scheduler,
    SlotKVCache,
)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("qwen2.5-32b", "smoke")
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    return cfg, m, params


def _reference_greedy(model, params, prompt, n_tokens):
    """Single-request unpacked greedy decode by full re-forward."""
    seq = list(prompt)
    out = []
    for _ in range(n_tokens):
        logits, _, _ = model.apply(params, {"inputs": jnp.asarray(seq)[None]})
        t = int(jnp.argmax(logits[0, -1]))
        out.append(t)
        seq.append(t)
    return out


# ---------------------------------------------------------------------------
# chunking / long-prompt submit (regression: used to raise ValueError)
# ---------------------------------------------------------------------------


def test_chunk_prompt_roundtrip():
    rng = np.random.default_rng(0)
    for n in (1, 15, 16, 17, 40, 64):
        prompt = rng.integers(0, 100, size=n).astype(np.int32)
        chunks = chunk_prompt(prompt, 16)
        assert all(len(c) <= 16 for c in chunks)
        assert all(len(c) == 16 for c in chunks[:-1])
        np.testing.assert_array_equal(np.concatenate(chunks), prompt)
    with pytest.raises(ValueError):
        chunk_prompt(np.zeros(0, np.int32), 16)


def test_dynamic_batcher_accepts_long_prompts():
    """Regression: submit used to raise for prompts > max_len."""
    b = DynamicBatcher(max_len=16)
    long_prompt = np.arange(40, dtype=np.int32)
    b.submit(Request(rid=0, prompt=long_prompt))  # must not raise
    batch = b.next_batch()
    assert batch["packed"] is None
    assert len(batch["chunks"]) == 3
    np.testing.assert_array_equal(np.concatenate(batch["chunks"]), long_prompt)
    assert b.next_batch() is None


def test_engine_rejects_only_beyond_cache_capacity():
    cfg = get_config("qwen2.5-32b", "smoke")
    eng = Engine(Model(cfg), params=None, max_len=16, num_slots=2)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=np.arange(
            eng.max_prompt_len + 1, dtype=np.int32)))


def test_long_prompt_decodes_exactly(smoke_model):
    cfg, m, params = smoke_model
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=25).astype(np.int32)
    eng = Engine(m, params, max_len=16, max_new_tokens=4, num_slots=2)
    eng.submit(Request(rid=0, prompt=prompt))
    out = eng.run()[0].output
    assert out == _reference_greedy(m, params, prompt, 4)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


def test_scheduler_admits_at_most_free_slots():
    s = Scheduler(max_len=16)
    for rid in range(10):
        s.submit(Request(rid=rid, prompt=np.arange(1 + rid % 5,
                                                   dtype=np.int32) + 1))
    groups = s.next_admissions(3)
    assert sum(len(g.requests) for g in groups) == 3
    assert s.pending() == 7
    assert s.next_admissions(0) == []
    assert s.pending() == 7


def test_scheduler_mixes_packed_and_solo_groups():
    s = Scheduler(max_len=16)
    s.submit(Request(rid=0, prompt=np.ones(4, np.int32)))
    s.submit(Request(rid=1, prompt=np.ones(40, np.int32)))  # long -> solo
    s.submit(Request(rid=2, prompt=np.ones(6, np.int32)))
    groups = s.next_admissions(3)
    solos = [g for g in groups if g.packed is None]
    packed = [g for g in groups if g.packed is not None]
    assert len(solos) == 1 and solos[0].requests[0].rid == 1
    assert len(packed) == 1 and {r.rid for r in packed[0].requests} == {0, 2}
    assert 0 < solos[0].utilization <= 1
    assert 0 < packed[0].utilization <= 1


# ---------------------------------------------------------------------------
# slot KV cache
# ---------------------------------------------------------------------------


def test_slot_kv_cache_guards(smoke_model):
    cfg, m, _ = smoke_model
    sl = SlotKVCache(m, num_slots=2, cache_len=8)
    assert list(sl.free_slots()) == [0, 1]
    src = m.init_cache(1, 8)
    sl.assign(0, "req", src, row=0, start=0, length=3)
    assert list(sl.free_slots()) == [1]
    with pytest.raises(ValueError):
        sl.assign(0, "req2", src, row=0, start=0, length=1)
    with pytest.raises(ValueError):
        sl.assign(1, "req3", src, row=0, start=0, length=9)
    sl.release(0)
    assert list(sl.free_slots()) == [0, 1]


def test_slot_table_accepts_every_config():
    """The slot-state table must hold lanes for every configs/ model —
    recurrent state caches and short-window ring caches included (both
    used to raise NotImplementedError and force lock-step decode)."""
    from repro.configs import list_archs
    for arch in list_archs():
        cfg = get_config(arch, "smoke")
        sl = SlotKVCache(Model(cfg), num_slots=2, cache_len=48)
        kinds = {cfg.block_kind(i) for i in range(cfg.n_layers)}
        specs = set(jax.tree.leaves(sl.specs))
        if kinds & {"ssd", "rglru"}:
            assert "state" in specs
        if kinds & {"attn", "local"}:
            assert "kv" in specs


def test_recurrent_lane_release_reassign_no_stale_state():
    """Property: release -> reassign of a recurrent lane leaves no trace of
    the previous occupant — the lane's state leaves equal a fresh solo
    prefill of the new request."""
    cfg = get_config("mamba2-370m", "smoke", dtype="float32")
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    rng = np.random.default_rng(7)

    def prefill_state(prompt):
        L = len(prompt)
        batch = {"inputs": jnp.asarray(prompt)[None],
                 "positions": jnp.asarray(np.arange(L, dtype=np.int32))[None],
                 "seg_ids": jnp.asarray(np.ones((1, L), np.int32))}
        caches = m.init_cache(1, L, ring=False)
        _, new_caches, _ = m.apply(params, batch, caches=caches,
                                   cache_index=jnp.int32(0))
        return new_caches

    pa = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    pb = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    sl = SlotKVCache(m, num_slots=2, cache_len=16)
    sl.assign(0, "A", prefill_state(pa), row=0, start=0, length=8)
    sl.release(0)
    sl.assign(0, "B", prefill_state(pb), row=0, start=0, length=8)
    want = prefill_state(pb)
    for leaf, ref in zip(jax.tree.leaves(sl.caches), jax.tree.leaves(want)):
        lane = np.asarray(leaf[:, 0])   # (L, ...) lane 0, stacked layers
        np.testing.assert_array_equal(lane, np.asarray(ref[:, 0]))


# ---------------------------------------------------------------------------
# equivalence: packed prefill + slot decode == unpacked single-request decode
# ---------------------------------------------------------------------------


def test_continuous_engine_matches_unpacked_decode(smoke_model):
    """Greedy outputs from packed prefill + continuous slot decode must
    exactly match single-request unpacked decoding — mixed lengths, more
    requests than slots (forcing mid-decode admissions), varied budgets."""
    cfg, m, params = smoke_model
    rng = np.random.default_rng(1)
    lengths = [3, 11, 25, 7, 16, 5]  # includes one > max_len (chunked solo)
    budgets = [4, 2, 5, 3, 4, 6]
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lengths]
    eng = Engine(m, params, max_len=16, max_new_tokens=8, num_slots=2)
    for rid, (p, b) in enumerate(zip(prompts, budgets)):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=b))
    done = eng.run()
    assert sorted(r.rid for r in done) == list(range(len(prompts)))
    # 6 requests through 2 slots: admissions necessarily happen mid-decode
    assert len(eng.stats) > 1
    by_rid = {r.rid: r for r in done}
    for rid, (p, b) in enumerate(zip(prompts, budgets)):
        assert by_rid[rid].output == _reference_greedy(m, params, p, b), \
            f"request {rid} diverged from unpacked decode"
    ds = eng.decode_stats
    assert ds["decoded_tokens"] == sum(b - 1 for b in budgets)
    assert 0 < ds["slot_utilization"] <= 1


def test_engine_zero_budget_emits_nothing(smoke_model):
    cfg, m, params = smoke_model
    eng = Engine(m, params, max_len=16, max_new_tokens=8, num_slots=2)
    eng.submit(Request(rid=0, prompt=np.asarray([1, 2, 3], np.int32),
                       max_new_tokens=0))
    done = eng.run()
    assert len(done) == 1 and done[0].output == []


def _reference_lockstep(model, params, prompt, n_tokens):
    """Single-request lock-step decode (seed-style): exact-prompt prefill
    into a ring-clamped cache, then scalar-index decode steps. The slot
    engine's per-request tokens must match this reference exactly."""
    L = len(prompt)
    batch = {"inputs": jnp.asarray(prompt)[None],
             "positions": jnp.asarray(np.arange(L, dtype=np.int32))[None],
             "seg_ids": jnp.asarray(np.ones((1, L), np.int32))}
    logits, caches = model.prefill(params, batch, max_len=L + n_tokens)
    out = [int(np.argmax(np.asarray(logits)[0, -1]))]
    idx = jnp.int32(L)
    for _ in range(n_tokens - 1):
        cur = jnp.asarray([[out[-1]]], jnp.int32)
        logits, caches = model.decode_step(params, {"inputs": cur}, caches,
                                           idx)
        out.append(int(np.argmax(np.asarray(logits)[0, 0])))
        idx = idx + 1
    return out


def _check_engine_matches_references(arch, lengths, budgets, *,
                                     full_reforward=True, **engine_kw):
    """Slot-engine tokens == lock-step reference (== full re-forward) for
    every request. float32 compute: the references run different XLA graphs
    than the engine, and bf16 jit-vs-eager noise can flip near-tied argmax."""
    cfg = get_config(arch, "smoke", dtype="float32")
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lengths]
    eng = Engine(m, params, max_len=16, max_new_tokens=8, num_slots=2,
                 **engine_kw)
    for rid, (p, b) in enumerate(zip(prompts, budgets)):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=b))
    done = eng.run()
    assert sorted(r.rid for r in done) == list(range(len(prompts)))
    by_rid = {r.rid: r for r in done}
    for rid, (p, b) in enumerate(zip(prompts, budgets)):
        assert by_rid[rid].output == _reference_lockstep(m, params, p, b), \
            f"{arch} request {rid} diverged from lock-step decode"
        if full_reforward:
            assert by_rid[rid].output == _reference_greedy(m, params, p, b), \
                f"{arch} request {rid} diverged from full re-forward"
    return eng


def test_slot_engine_matches_lockstep_on_recurrent():
    """RG-LRU-free SSM stack (mamba2): recurrent state lanes through the
    slot engine must equal lock-step decode (this path used to raise and
    fall back). Prompts+budgets stay < the SSD chunk so the re-forward
    reference's scan widths are valid."""
    eng = _check_engine_matches_references(
        "mamba2-370m", [3, 7, 5, 8, 4], [4, 3, 2, 4, 3])
    assert eng.slots is not None and eng._recurrent
    assert eng.decode_stats["slot_utilization"] > 0.5
    # pure-recurrent stacks have no kv blocks to predicate
    assert eng.decode_stats["kv_blocks_dense"] == 0


def test_slot_engine_matches_lockstep_on_hybrid_rglru():
    """recurrentgemma-style hybrid (rglru + short-window local attention):
    recurrent lanes AND ring lanes in one stack."""
    eng = _check_engine_matches_references(
        "recurrentgemma-2b", [3, 11, 7, 5, 9], [4, 2, 5, 3, 4])
    assert eng._recurrent
    assert eng.decode_stats["kv_blocks_dense"] > 0


def test_slot_engine_matches_lockstep_on_short_window():
    """Sliding window (32) shorter than the cache lanes: ring-buffered KV
    lanes (canonical ring phase), including a 40-token prompt that wraps
    the ring at assign time and keeps wrapping through decode."""
    eng = _check_engine_matches_references(
        "starcoder2-15b", [3, 11, 25, 7, 40], [4, 2, 5, 3, 6],
        max_prompt_len=48)
    assert not eng._recurrent
    assert eng.decode_stats["kv_blocks_dense"] > 0


def test_engine_honors_per_request_budgets_and_eos(smoke_model):
    cfg, m, params = smoke_model
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (4, 9, 13)]
    eng = Engine(m, params, max_len=16, max_new_tokens=8, num_slots=4)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=rid + 1))
    done = eng.run()
    by_rid = {r.rid: r for r in done}
    for rid in range(3):
        assert len(by_rid[rid].output) == rid + 1

    # eos stops a request early, frees its slot for the next one
    ref = _reference_greedy(m, params, prompts[1], 8)
    eos = ref[2]
    eng = Engine(m, params, max_len=16, max_new_tokens=8, num_slots=1,
                 eos_id=eos)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p))
    done = eng.run()
    assert len(done) == 3
    by_rid = {r.rid: r for r in done}
    # stopped at the FIRST eos occurrence (greedy often repeats tokens)
    assert by_rid[1].output == ref[:ref.index(eos) + 1]
    assert all(len(r.output) <= 8 for r in done)


def test_launcher_requests_are_seeded_and_share_a_prefix():
    """``make_requests``: one request per length, same seed -> same
    prompts, and the last prompt opens with the first one's prefix."""
    from repro.launch.serve import make_requests
    a = make_requests([40, 9, 33], 256, max_new=[3, 4, 5], seed=3,
                      shared_prefix=16)
    b = make_requests([40, 9, 33], 256, max_new=[3, 4, 5], seed=3,
                      shared_prefix=16)
    assert [len(r.prompt) for r in a] == [40, 9, 33]
    assert [r.max_new_tokens for r in a] == [3, 4, 5]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert np.array_equal(a[-1].prompt[:16], a[0].prompt[:16])
    assert not np.array_equal(a[-1].prompt[16:24], a[0].prompt[16:24])


def test_compiled_steps_returns_both_programs(smoke_model):
    """``compiled_steps`` compiles the decode and the mixed step from the
    same argument builder the serving loop dispatches with, after a run
    that used both."""
    from repro.serve import EngineConfig
    cfg, m, params = smoke_model
    eng = Engine(m, params, config=EngineConfig(max_len=16, num_slots=2,
                                                max_new_tokens=3))
    assert eng.mixed
    rng = np.random.default_rng(0)
    for rid, n in enumerate((20, 5)):
        eng.submit(Request(rid=rid, prompt=rng.integers(
            0, cfg.vocab_size, size=n).astype(np.int32), max_new_tokens=3))
    assert all(r.status == "ok" for r in eng.run())
    text = eng.compiled_steps()
    assert set(text) == {"decode", "mixed"}
    assert all("HloModule" in t for t in text.values())
