"""Mixed-step serving (chunked prefill interleaved with decode in one
jitted step): token identity vs the phase-serialized engine across
prefill budgets, mid-decode arrivals, preemption mid-prefill, prefix
sharing / CoW, the batched suffix sweep, and the support gating."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.errors import UnsupportedConfigError
from repro.models.transformer import Model
from repro.serve import Engine, EngineConfig, Request
from repro.serve.faults import FaultPlan


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2.5-32b", "smoke", dtype="float32")
    m = Model(cfg)
    return cfg, m, m.init(jax.random.key(0))


@pytest.fixture(scope="module")
def star():
    # starcoder2 smoke carries short-window ring lanes: the mixed step's
    # dedup ring write + position-recovery masks are on the hot path.
    cfg = get_config("starcoder2-15b", "smoke", dtype="float32")
    m = Model(cfg)
    return cfg, m, m.init(jax.random.key(0))


def _prompts(cfg, lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def _run(model, params, prompts, budgets, *, ticks=None, expect_ok=True,
         **kw):
    """Run one engine over the workload; returns ({rid: output}, engine).
    ``ticks`` submits request i when the run loop reaches ticks[i]
    (mid-decode arrivals); None submits everything up front."""
    eng = Engine(model, params, **kw)
    reqs = [Request(rid=rid, prompt=p, max_new_tokens=b)
            for rid, (p, b) in enumerate(zip(prompts, budgets))]
    if ticks is None:
        for r in reqs:
            eng.submit(r)
        done = eng.run()
    else:
        done = eng.run(arrivals=list(zip(ticks, reqs)))
    assert sorted(r.rid for r in done) == list(range(len(prompts)))
    if expect_ok:
        assert all(r.status == "ok" for r in done)
    return {r.rid: r.output for r in done}, eng


# ---------------------------------------------------------------------------
# token identity vs the phase-serialized engine
# ---------------------------------------------------------------------------

WORKLOAD_KW = dict(max_len=16, max_new_tokens=8, num_slots=3,
                   max_prompt_len=40)
LENGTHS = [5, 25, 12, 18]     # short, chunked-long, mid, mid
BUDGETS = [6, 5, 4, 6]
TICKS = [1, 1, 3, 6]          # two up front, two arriving mid-decode


# Chunk-step budgets of the token-identity cases: the default (None: a row
# for every prompt streaming, up to every slot) and at most two max_len
# rows a step.
ROW_BUDGETS = [None, 2 * WORKLOAD_KW["max_len"]]


@pytest.mark.parametrize("prefill_budget", [4, 16, None, 32])
def test_mixed_matches_serialized_greedy(qwen, prefill_budget):
    """Same tokens at every chunk granularity — one tiny chunk per step,
    one max_len row per step, at most two rows per step, and a row for
    every prompt streaming (the default) — with requests arriving
    mid-decode, against the phase-serialized engine on the identical
    arrival schedule."""
    cfg, m, params = qwen
    prompts = _prompts(cfg, LENGTHS)
    ref, _ = _run(m, params, prompts, BUDGETS, ticks=TICKS,
                  mixed=False, **WORKLOAD_KW)
    got, eng = _run(m, params, prompts, BUDGETS, ticks=TICKS,
                    mixed=True, prefill_budget=prefill_budget,
                    **WORKLOAD_KW)
    assert got == ref
    st = eng.decode_stats
    assert st["mixed"] and st["mixed_steps"] > 0
    assert st["prefill_chunk_tokens"] == sum(LENGTHS)
    # TTFT is recorded for every completed request, in both engines.
    assert sorted(st["ttft"]) == list(range(len(LENGTHS)))
    # clock 0 is legal: a short prompt submitted and fully prefilled in
    # the same iteration gets its first token with no waiting step.
    assert all(v["clock"] >= 0 and v["wall_s"] >= 0.0
               for v in st["ttft"].values())


@pytest.mark.parametrize("prefill_budget", [5] + ROW_BUDGETS)
def test_mixed_matches_serialized_sampled(qwen, prefill_budget):
    """Seeded sampling: chunk completion must draw the first token with
    the same (request, position) key the serialized prefill uses."""
    cfg, m, params = qwen
    prompts = _prompts(cfg, LENGTHS, seed=3)
    kw = dict(temperature=0.8, top_k=12, seed=11, **WORKLOAD_KW)
    ref, _ = _run(m, params, prompts, BUDGETS, ticks=TICKS,
                  mixed=False, **kw)
    got, _ = _run(m, params, prompts, BUDGETS, ticks=TICKS,
                  mixed=True, prefill_budget=prefill_budget, **kw)
    assert got == ref


@pytest.mark.parametrize("prefill_budget", [6] + ROW_BUDGETS)
@pytest.mark.parametrize("sample_kw", [
    {},  # greedy
    dict(temperature=0.7, top_k=8, seed=5),  # sampled
])
def test_mixed_matches_serialized_on_ring_lanes(star, sample_kw,
                                                prefill_budget):
    """Windowed (ring) lanes: chunks wrap the ring mid-prefill and decode
    pushes past the window — the dedup write and position-recovery masks
    must keep canonical ring phase identical to the serialized engine."""
    cfg, m, params = star
    prompts = _prompts(cfg, [7, 25, 14], seed=2)
    budgets = [5, 6, 5]
    kw = dict(max_len=16, max_new_tokens=8, num_slots=2,
              max_prompt_len=40, **sample_kw)
    ref, _ = _run(m, params, prompts, budgets, mixed=False, **kw)
    got, eng = _run(m, params, prompts, budgets, mixed=True,
                    prefill_budget=prefill_budget, **kw)
    assert got == ref
    assert eng.decode_stats["mixed_steps"] > 0


@pytest.mark.parametrize("prefill_budget", [4] + ROW_BUDGETS)
def test_mixed_preemption_mid_prefill_matches_clean_run(qwen,
                                                        prefill_budget):
    """A forced preemption while a prompt is half-prefilled requeues it as
    a continuation; the resumed run must still emit exactly the clean
    serialized tokens (chunk state is discarded, pages are released, and
    the re-prefill starts from scratch)."""
    cfg, m, params = qwen
    prompts = _prompts(cfg, [25, 6], seed=4)
    budgets = [5, 5]
    ref, _ = _run(m, params, prompts, budgets, mixed=False, **WORKLOAD_KW)
    # budget 4/step: the 25-token prompt is mid-prefill for ~6 iterations,
    # so iterations 2-3 preempt it (youngest-first) while half-streamed;
    # with whole rows, they preempt whichever request is youngest then.
    got, eng = _run(m, params, prompts, budgets, mixed=True,
                    prefill_budget=prefill_budget,
                    faults=FaultPlan(preempt_at=(2, 3)), **WORKLOAD_KW)
    assert got == ref
    assert eng.decode_stats["preemptions"] >= 2


@pytest.mark.parametrize("prefill_budget", ROW_BUDGETS)
def test_mixed_prefix_hit_and_cow_identity(qwen, prefill_budget):
    """Chunked prefill over a mapped shared prefix: the suffix streams
    through chunk rows while the prefix pages stay shared (CoW on the
    tail), and tokens match both the sharing-off mixed engine and the
    serialized engine."""
    cfg, m, params = qwen
    rng = np.random.default_rng(6)
    pre = rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)
    A = np.concatenate([pre, rng.integers(0, cfg.vocab_size, size=6)
                        ]).astype(np.int32)
    B = np.concatenate([pre, rng.integers(0, cfg.vocab_size, size=9)
                        ]).astype(np.int32)
    kw = dict(max_len=16, max_new_tokens=6, num_slots=2, max_prompt_len=40,
              page_size=8, prefill_budget=prefill_budget)
    eng = Engine(m, params, mixed=True, **kw)
    eng.submit(Request(rid=0, prompt=A, max_new_tokens=5))
    out = {r.rid: r.output for r in eng.run()}
    eng.submit(Request(rid=1, prompt=B, max_new_tokens=5))
    out.update({r.rid: r.output for r in eng.run()})
    st = eng.decode_stats
    assert st["prefix_hit_ratio"] > 0 and st["pages_shared"] > 0
    eng.slots.pool.check_invariants()
    for rid, prompt in ((0, A), (1, B)):
        for ref_kw in (dict(mixed=True, prefix_share=False),
                       dict(mixed=False)):
            ref = Engine(m, params, **kw, **ref_kw)
            ref.submit(Request(rid=rid, prompt=prompt, max_new_tokens=5))
            assert {r.rid: r.output for r in ref.run()}[rid] == out[rid], \
                f"rid {rid} diverged vs {ref_kw}"


@pytest.mark.parametrize("prefill_budget,rows", [(None, 7), (32, 2)])
def test_chunk_step_takes_bursts_fifo(qwen, prefill_budget, rows):
    """Two bursts (four prompts, then three arriving over two decoding
    slots): the chunk step takes a row for every prompt streaming, up to
    R (every slot at the default budget, two at two max_len rows), at a
    count it is compiled at (the largest they fill: three prompts take
    two rows and the third waits in the queue), oldest admission first;
    it pays for no padded row; the decode step runs in every iteration
    where a slot decodes and is skipped where none does; and the tokens
    are the serialized engine's."""
    cfg, m, params = qwen
    S = WORKLOAD_KW["max_len"]
    prompts = _prompts(cfg, [5, 7, 25, 12, 30, 9, 20], seed=12)
    ticks = [1, 1, 1, 1, 4, 4, 4]
    kw = {**WORKLOAD_KW, "num_slots": 7}
    ref, _ = _run(m, params, prompts, [8] * 7, ticks=ticks, mixed=False,
                  **kw)
    eng = Engine(m, params, config=EngineConfig(
        mixed=True, prefill_budget=prefill_budget, **kw))
    assert eng._chunk_rows == rows
    assert eng._row_counts == ((1, 2, 4, 7) if rows == 7 else (1, 2))
    decodes = []
    inner = eng._decode

    def decode(*args):
        decodes.append(len(results))
        return inner(*args)

    eng._decode = decode
    reqs = [Request(rid=i, prompt=p, max_new_tokens=8)
            for i, p in enumerate(prompts)]
    results, chunk_rids = [], []
    while len(results) < ticks[-1] or eng.has_work():
        due = [q for q, t in zip(reqs, ticks) if t == len(results) + 1]
        res = eng.step(submits=due)
        if res.chunk_slots is not None:
            assert res.kind == "mixed"
            assert res.chunk_slots.size in eng._row_counts
            chunk_rids.append([eng.slots.request[s].rid
                               for s in res.chunk_slots])
        results.append(res)
    eng.finish_run()
    assert {q.rid: q.output for q in reqs} == ref
    # The first burst runs in one step where R allows; FIFO: rows go in
    # admission order, and requests reach the chunk step in arrival order.
    assert len(chunk_rids[0]) == min(4, rows)
    assert all(step == sorted(step) for step in chunk_rids)
    flat = [rid for step in chunk_rids for rid in step]
    assert list(dict.fromkeys(flat)) == list(range(7))
    st = eng.decode_stats
    assert st["mixed_columns"] == S * len(flat)
    assert st["mixed_steps"] == len(chunk_rids)
    assert st["prefill_chunk_tokens"] == sum(map(len, prompts))
    assert st["chunk_waits"] > 0
    # The decode step ran exactly where a slot decoded (n_new 1 outside
    # the chunk rows: every chunk here is longer than one token).
    decoding = [i for i, r in enumerate(results) if r.kind is not None
                and (r.n_new == 1).any()]
    assert decodes == decoding
    assert results[0].kind == "mixed" and 0 not in decodes


# ---------------------------------------------------------------------------
# batched suffix prefills (serialized engine, several hits in one sweep)
# ---------------------------------------------------------------------------


def test_batched_suffix_prefill_one_sweep(qwen):
    """Two prefix-cache hits with DISTINCT prefixes ride one multi-row
    suffix sweep (the PR 5 hits-admit-solo restriction is retired) and
    still decode exactly like solo serialized runs."""
    cfg, m, params = qwen
    rng = np.random.default_rng(8)
    pre1 = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    pre2 = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    warm1 = np.concatenate([pre1, rng.integers(0, cfg.vocab_size, size=4)
                            ]).astype(np.int32)
    warm2 = np.concatenate([pre2, rng.integers(0, cfg.vocab_size, size=5)
                            ]).astype(np.int32)
    hit1 = np.concatenate([pre1, rng.integers(0, cfg.vocab_size, size=7)
                           ]).astype(np.int32)
    hit2 = np.concatenate([pre2, rng.integers(0, cfg.vocab_size, size=6)
                           ]).astype(np.int32)
    kw = dict(max_len=16, max_new_tokens=6, num_slots=4, max_prompt_len=40,
              page_size=8, mixed=False)
    eng = Engine(m, params, **kw)
    eng.submit(Request(rid=0, prompt=warm1, max_new_tokens=4))
    eng.submit(Request(rid=1, prompt=warm2, max_new_tokens=4))
    out = {r.rid: r.output for r in eng.run()}
    n_sweeps = len(eng.stats)
    eng.submit(Request(rid=2, prompt=hit1, max_new_tokens=5))
    eng.submit(Request(rid=3, prompt=hit2, max_new_tokens=5))
    out.update({r.rid: r.output for r in eng.run()})
    batched = [s for s in eng.stats[n_sweeps:] if s["n_requests"] == 2]
    assert batched, "hit requests were not grouped into one suffix sweep"
    assert eng.decode_stats["prefix_hit_ratio"] > 0
    for rid, prompt in ((2, hit1), (3, hit2)):
        ref = Engine(m, params, max_len=16, max_new_tokens=6, num_slots=4,
                     max_prompt_len=40, page_size=8, mixed=False,
                     prefix_share=False)
        ref.submit(Request(rid=rid, prompt=prompt, max_new_tokens=5))
        assert {r.rid: r.output for r in ref.run()}[rid] == out[rid]


# ---------------------------------------------------------------------------
# gating + parameter validation
# ---------------------------------------------------------------------------


def test_mixed_gating():
    attn = get_config("qwen2.5-32b", "smoke")
    recur = get_config("mamba2-370m", "smoke")
    # auto: on for paged attention stacks, off otherwise
    assert Engine(Model(attn), params=None).mixed
    assert not Engine(Model(attn), params=None, paged=False).mixed
    assert not Engine(Model(recur), params=None).mixed
    assert not Engine(Model(attn), params=None, mixed=False).mixed
    with pytest.raises(UnsupportedConfigError):
        Engine(Model(recur), params=None, mixed=True)
    with pytest.raises(UnsupportedConfigError):
        Engine(Model(attn), params=None, paged=False, mixed=True)
    with pytest.raises(ValueError):
        Engine(Model(attn), params=None, prefill_budget=0)


def test_mixed_budget_bounds_chunk_tokens_per_step(qwen):
    """prefill_budget is a hard per-step cap: with budget B and decode
    riding along, no mixed step streams more than B fresh prompt tokens
    (so prefill can never starve in-flight decodes of the step)."""
    cfg, m, params = qwen
    prompts = _prompts(cfg, [25, 25], seed=9)
    got, eng = _run(m, params, prompts, [4, 4], mixed=True,
                    prefill_budget=3, **WORKLOAD_KW)
    st = eng.decode_stats
    assert st["prefill_chunk_tokens"] == 50
    assert st["mixed_steps"] >= int(np.ceil(50 / 3))
