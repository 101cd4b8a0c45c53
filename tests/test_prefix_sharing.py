"""Page-level prefix sharing + copy-on-write: the load-bearing claims.

* **Refcount conservation**: under random admit / grow / preempt-release /
  free schedules with sharing, no page is freed while a block table still
  references it, refcounts always equal the reference counts, and every
  page is accounted for (free + retained + mapped == capacity) when the
  dust settles.
* **CoW never mutates a shared page**: after every ``make_writable`` /
  ``make_range_writable``, the write-target page has refcount 1 and is
  out of the prefix index — a page some other slot maps (or the cache
  still advertises) is copied first, never written.
* **Shared prefill == cold prefill**: a request served through mapped
  shared pages + a suffix prefill emits exactly the tokens of a cold
  paged run and of the contiguous layout — full-attention lanes, ring
  lanes wrapping past their window (decode-time CoW with both sharers
  alive), exact-duplicate prompts (assign-time CoW of the partial tail
  page), greedy and sampled.
* **Hit-aware admission**: a request that only fits the page budget
  because of its expected prefix hits is admitted (the reservation
  discounts shared pages).
* **Sharing saves memory**: on a shared-system-prompt workload the pool
  holds strictly fewer pages in use than the same run without sharing.
* Recurrent/hybrid stacks degrade cleanly: sharing is gated off (state
  lanes are neither paged nor content-addressable).
"""
import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import get_config
from repro.models.transformer import Model
from repro.serve import Engine, EngineConfig, PagePool, Request


# ---------------------------------------------------------------------------
# pool-level properties
# ---------------------------------------------------------------------------


def test_probe_publish_roundtrip():
    pool = PagePool([64], num_slots=4, page_size=8)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 1000, size=20).astype(np.int32)
    assert pool.probe_prefix(toks) is None  # cold cache
    pool.alloc_prefix(0, 21)
    pool.publish_prefix(0, toks)
    # Only full pages publish: a 20-token lane advertises tokens [0, 16).
    hit = pool.probe_prefix(toks)
    assert hit.n_shared == 16 and len(hit.pages[64]) == 2
    longer = np.concatenate([toks, toks[:5]])
    hit = pool.probe_prefix(longer)  # common prefix: the full pages
    assert hit.n_shared == 16 and len(hit.pages[64]) == 2
    # A page-aligned exact duplicate shares all but its last token (the
    # recomputed token CoWs the tail page at assign time).
    hit = pool.probe_prefix(toks[:16])
    assert hit.n_shared == 15 and len(hit.pages[64]) == 2
    other = toks.copy()
    other[3] += 1  # first page differs -> chain dead from page 0
    assert pool.probe_prefix(other) is None
    short = toks[:7]  # no full page inside len-1
    assert pool.probe_prefix(short) is None


def test_release_retains_published_pages_and_eviction_frees_them():
    pool = PagePool([64], num_slots=2, page_size=8)
    toks = np.arange(20, dtype=np.int32)
    pool.alloc_prefix(0, 21)
    pool.publish_prefix(0, toks)
    pool.release(0)
    c = pool.classes[64]
    assert pool.pages_in_use() == 0
    assert len(c.retained) == 2  # the two published full pages survive
    assert pool.probe_prefix(toks).n_shared == 16
    # eviction (allocation pressure) drains retained LRU-first
    for s in range(2):
        pool.alloc_prefix(s, 64)
    assert not c.retained and pool.probe_prefix(toks) is None
    pool.check_invariants()


def _write_target_is_private(pool, slot, length):
    """Post-condition of every make-writable: the page the write will land
    in is exclusively owned and not advertised by the prefix index."""
    for c in pool.classes.values():
        lp = (length % c.width) // pool.page_size
        pg = int(c.table[slot, lp])
        assert pg != c.FREE
        assert c.refcount[pg] == 1, "write target still shared"
        assert pg not in c.published, "write target still published"


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_sharing_invariants_under_random_schedule(seed):
    """Random admit(+probe/map/publish) / grow / release schedules keep
    refcounts == table references, never free a referenced page, never
    hand out a shared or published page as a write target, and conserve
    every page."""
    rng = np.random.default_rng(seed)
    pool = PagePool([64, 32], num_slots=5, page_size=8,
                    pool_frac=float(rng.uniform(0.5, 1.0)))
    prefixes = [rng.integers(0, 100, size=16).astype(np.int32)
                for _ in range(2)]
    held = {}  # slot -> current length
    for _ in range(80):
        op = rng.integers(0, 3)
        if op == 0:  # admit, engine-style
            free = [s for s in range(5) if s not in held]
            if not free:
                continue
            s = int(rng.choice(free))
            prompt = np.concatenate(
                [prefixes[rng.integers(0, 2)],
                 rng.integers(0, 100, size=rng.integers(1, 14))]
            ).astype(np.int32)
            L = len(prompt)
            hit = pool.probe_prefix(prompt)
            off = hit.n_shared if hit else 0
            shared = -(-off // pool.page_size)
            # The engine's page budget (Engine._page_reserve): new pages,
            # plus shared pages the writes copy, plus retained pages the
            # mapping takes out of the evictable set.
            ok = True
            for w, c in pool.classes.items():
                need = -(-min(L + 1, w) // pool.page_size)
                if hit:
                    cow = len({(p % w) // pool.page_size
                               for p in range(off, L + 1)}
                              & set(range(shared)))
                    r0 = sum(c.refcount[pg] == 0 for pg in hit.pages[w])
                    need = max(0, need - shared) + cow + r0
                ok &= need <= c.available()
            if not ok:
                continue
            if hit:
                pool.map_shared(s, hit)
            pool.alloc_prefix(s, L + 1)
            if off:
                copies = pool.make_range_writable(s, off, L + 1)
                for w, src, dst in copies:
                    assert pool.classes[w].refcount[src] >= 1
                for p in range(off, L + 1):
                    _write_target_is_private(pool, s, p)
            pool.publish_prefix(s, prompt)
            held[s] = L
        elif op == 1 and held:  # grow one decode step
            s = int(rng.choice(list(held)))
            ok, copies = pool.make_writable(s, held[s])
            if ok:
                _write_target_is_private(pool, s, held[s])
                held[s] += 1
        elif op == 2 and held:  # release (finish or preempt)
            s = int(rng.choice(list(held)))
            pool.release(s)
            del held[s]
        pool.check_invariants()
    for s in list(held):
        pool.release(s)
    pool.check_invariants()
    assert pool.pages_in_use() == 0
    for c in pool.classes.values():
        assert len(c.free) + len(c.retained) == c.num_pages


# ---------------------------------------------------------------------------
# engine-level: shared prefill == cold prefill == contiguous
# ---------------------------------------------------------------------------


def _model(arch):
    cfg = get_config(arch, "smoke", dtype="float32")
    m = Model(cfg)
    return cfg, m, m.init(jax.random.key(0))


def test_shared_prefill_matches_cold_and_contiguous():
    cfg, m, params = _model("qwen1.5-4b")
    rng = np.random.default_rng(0)
    pre = rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)
    A = np.concatenate([pre, rng.integers(0, cfg.vocab_size, size=6)
                        ]).astype(np.int32)
    B = np.concatenate([pre, rng.integers(0, cfg.vocab_size, size=9)
                        ]).astype(np.int32)
    kw = dict(max_len=16, max_new_tokens=6, num_slots=2, max_prompt_len=40)

    eng = Engine(m, params, paged=True, page_size=8, **kw)
    eng.submit(Request(rid=0, prompt=A, max_new_tokens=5))
    outA = {r.rid: r.output for r in eng.run()}
    eng.submit(Request(rid=1, prompt=B, max_new_tokens=5))
    outB = {r.rid: r.output for r in eng.run()}
    st = eng.decode_stats
    assert st["prefix_hit_ratio"] > 0 and st["pages_shared"] > 0
    eng.slots.pool.check_invariants()

    for rid, prompt, got in ((0, A, outA[0]), (1, B, outB[1])):
        for pkw in (dict(paged=True, page_size=8, prefix_share=False),
                    dict(paged=False)):
            ref = Engine(m, params, **kw, **pkw)
            ref.submit(Request(rid=rid, prompt=prompt, max_new_tokens=5))
            assert {r.rid: r.output for r in ref.run()}[rid] == got, \
                f"sharing changed tokens for rid {rid} vs {pkw}"


@pytest.mark.parametrize("sample_kw", [
    {},  # greedy
    dict(temperature=0.8, top_k=12, seed=7),  # sampled
])
def test_ring_cow_past_window_matches_contiguous(sample_kw):
    """starcoder2's ring lanes (window 32): two live requests share a
    16-token prefix; decode pushes both past the window, so their write
    pointers wrap into the shared pages — decode-time CoW with both
    sharers alive. The pool is sized so the second request is admitted
    *only* because the reservation discounts its expected hits."""
    cfg, m, params = _model("starcoder2-15b")
    rng = np.random.default_rng(0)
    pre = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    A = np.concatenate([pre, rng.integers(0, cfg.vocab_size, size=8)
                        ]).astype(np.int32)
    B = np.concatenate([pre, rng.integers(0, cfg.vocab_size, size=6)
                        ]).astype(np.int32)
    kw = dict(max_len=32, max_new_tokens=12, num_slots=2, **sample_kw)

    def run(**pkw):
        eng = Engine(m, params, **kw, **pkw)
        eng.submit(Request(rid=0, prompt=A, max_new_tokens=12))
        eng.submit(Request(rid=1, prompt=B, max_new_tokens=12))
        out = {r.rid: r.output for r in eng.run()}
        if eng.paged:
            eng.slots.pool.check_invariants()
        return out, eng.decode_stats

    ref, _ = run(paged=False)
    out, st = run(paged=True, page_size=8, pool_frac=0.75)
    assert st["prefix_hit_ratio"] > 0, "ring prefix never shared"
    assert out == ref, "ring CoW changed the token stream"


def test_exact_duplicate_prompt_cows_partial_tail():
    """An exact-duplicate prompt shares everything but its last token;
    the suffix prefill's single recomputed token lands inside a shared
    page, forcing assign-time CoW — and the original's published pages
    must come through byte-identical (a later continuation still hits)."""
    cfg, m, params = _model("qwen1.5-4b")
    rng = np.random.default_rng(1)
    A = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    C = np.concatenate([A, rng.integers(0, cfg.vocab_size, size=5)
                        ]).astype(np.int32)
    kw = dict(max_len=16, max_new_tokens=6, num_slots=2, max_prompt_len=32)

    eng = Engine(m, params, paged=True, page_size=8, **kw)
    outs = {}
    for rid, p in ((0, A), (1, A.copy()), (2, C)):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=5))
        outs.update({r.rid: r.output for r in eng.run()})
        if rid == 1:
            assert eng.decode_stats["prefix_hit_ratio"] > 0.9
    assert outs[0] == outs[1], "duplicate prompt diverged"
    assert eng.decode_stats["prefix_hit_ratio"] > 0.5, \
        "CoW corrupted the published pages (later probe missed)"
    eng.slots.pool.check_invariants()
    ref = Engine(m, params, paged=False, **kw)
    ref.submit(Request(rid=2, prompt=C, max_new_tokens=5))
    assert {r.rid: r.output for r in ref.run()}[2] == outs[2]


def test_shared_system_prompt_lowers_kv_memory_ratio():
    """Twelve requests behind one 48-token system prompt: with sharing on,
    later admissions map the published prefix pages instead of writing
    their own, so the mean fraction of the pool in use (kv_memory_ratio)
    ends strictly below the same run with sharing off."""
    cfg, m, params = _model("qwen2.5-32b")
    rng = np.random.default_rng(4)
    prefix = rng.integers(0, cfg.vocab_size, size=48)
    tails = [int(rng.integers(4, 13)) for _ in range(12)]
    budgets = [int(rng.integers(3, 9)) for _ in range(12)]

    def run(share):
        eng = Engine(m, params, config=EngineConfig(
            max_len=32, max_new_tokens=16, num_slots=4, page_size=8,
            max_prompt_len=64, prefix_share=share))
        r = np.random.default_rng(5)
        for rid, (n, b) in enumerate(zip(tails, budgets)):
            eng.submit(Request(rid=rid, prompt=np.concatenate(
                [prefix, r.integers(0, cfg.vocab_size, size=n)]
            ).astype(np.int32), max_new_tokens=b))
        assert all(q.status == "ok" for q in eng.run())
        eng.slots.pool.check_invariants()
        return eng.decode_stats

    shared, cold = run(True), run(False)
    assert shared["prefix_hit_ratio"] > 0 and shared["pages_shared"] > 0
    assert cold["prefix_hit_ratio"] == 0.0
    assert shared["decoded_tokens"] == cold["decoded_tokens"]
    assert shared["kv_memory_ratio"] < cold["kv_memory_ratio"]


def test_recurrent_and_hybrid_stacks_gate_sharing_off():
    """State lanes are neither paged nor content-addressable: sharing must
    disable itself (and report zero hits) rather than corrupt state."""
    cfg, m, params = _model("mamba2-370m")
    eng = Engine(m, params, max_len=16, max_new_tokens=4, num_slots=2)
    assert not eng.prefix_share  # pure-recurrent: not even paged
    rng = np.random.default_rng(2)
    p = rng.integers(0, cfg.vocab_size, size=7).astype(np.int32)
    eng.submit(Request(rid=0, prompt=p, max_new_tokens=3))
    eng.run()
    assert eng.decode_stats["prefix_hit_ratio"] == 0.0
    assert eng.decode_stats["pages_shared"] == 0
