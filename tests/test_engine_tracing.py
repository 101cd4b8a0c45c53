"""What the serving engine writes for a profiler and for the caller that
steps it: host spans around ``Engine.step`` and its phases, name scopes in the
compiled step programs, the per-step dispatch record (``StepResult``) and
the per-request host-clock stamps (``Request``)."""
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.layers import ATTN_SCOPE, KV_WRITE_SCOPE
from repro.models.transformer import Model
from repro.serve import Engine, EngineConfig, Request, spans

ENGINE = dict(max_len=16, max_new_tokens=6, num_slots=3, max_prompt_len=40,
              page_size=8)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2.5-32b", "smoke", dtype="float32")
    m = Model(cfg)
    return cfg, m, m.init(jax.random.key(0))


def _requests(cfg, lengths, budgets, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=b)
            for i, (n, b) in enumerate(zip(lengths, budgets))]


def _drive(eng, reqs, ticks):
    """Submit request i at step ticks[i]; step until done. Returns each
    step's result with the slot lengths before the step."""
    out, k, it = [], 0, 0
    while k < len(reqs) or eng.has_work():
        it += 1
        due = []
        while k < len(reqs) and ticks[k] <= it:
            due.append(reqs[k])
            k += 1
        out.append(eng.step(submits=due))
    eng.finish_run()
    return out


def test_step_records_and_request_stamps(qwen):
    """Every dispatch is recorded with its kind, the lane length before it
    and its fresh tokens per slot; chunk rows add up to the streamed
    prompt tokens; each request's stamps are in order. The fourth request
    arrives while the others decode, so one mixed step carries both."""
    cfg, m, params = qwen
    lengths = [12, 12, 12, 9]
    reqs = _requests(cfg, lengths, [5, 4, 6, 3])
    eng = Engine(m, params, config=EngineConfig(mixed=True, **ENGINE))
    results = _drive(eng, reqs, [1, 1, 1, 4])
    kinds = [r.kind for r in results]
    assert {"mixed", "decode"} <= set(kinds) <= {"mixed", "decode", None}
    st = eng.decode_stats
    assert kinds.count("mixed") == st["mixed_steps"]
    assert kinds.count("decode") + kinds.count("mixed") == st["steps"]
    chunk = decoded = 0
    for r in results:
        if r.kind is None:
            assert r.starts is None and r.n_new is None
            continue
        assert r.starts.shape == r.n_new.shape == (ENGINE["num_slots"],)
        assert r.pages_in_use > 0
        if r.kind == "decode":
            assert set(r.n_new.tolist()) <= {0, 1}
            decoded += int(r.n_new.sum())
            continue
        # Every prompt fits one chunk of more than one token, so the
        # rows with n_new > 1 are the chunk rows and the rest decode.
        chunk += int(r.n_new[r.n_new > 1].sum())
        assert set(r.n_new[r.n_new <= 1].tolist()) <= {0, 1}
    assert decoded > 0
    assert chunk == st["prefill_chunk_tokens"] == sum(lengths)
    for q in reqs:
        assert q.status == "ok"
        assert (q.submitted_s <= q.admitted_s <= q.first_token_s
                <= q.finished_s)
        assert st["ttft"][q.rid]["wall_s"] == pytest.approx(
            q.first_token_s - q.submitted_s)
        assert "first_token_clock" not in st["ttft"][q.rid]


def test_starts_follow_the_lanes(qwen):
    """A slot's start is its lane length before the dispatch: the next
    record of a slot that kept its request starts where this one ended."""
    cfg, m, params = qwen
    reqs = _requests(cfg, [7, 30], [6, 5])
    eng = Engine(m, params, config=EngineConfig(mixed=True, **ENGINE))
    results = [r for r in _drive(eng, reqs, [1, 2]) if r.kind is not None]
    for a, b in zip(results, results[1:]):
        for s in np.flatnonzero(a.n_new):
            if b.n_new[s] and not a.finished:
                assert b.starts[s] == a.starts[s] + a.n_new[s]
    # The 7-token prompt is one chunk; the 30-token one streams as 16 + 14
    # from lane position 0.
    chunks = [(int(r.starts[s]), int(r.n_new[s])) for r in results
              for s in np.flatnonzero(r.n_new > 1)]
    assert chunks == [(0, 7), (0, 16), (16, 14)]


def test_decode_engine_records_every_active_slot(qwen):
    cfg, m, params = qwen
    reqs = _requests(cfg, [5, 6], [4, 4])
    eng = Engine(m, params, config=EngineConfig(mixed=False, **ENGINE))
    results = _drive(eng, reqs, [1, 1])
    dec = [r for r in results if r.kind is not None]
    assert dec and {r.kind for r in dec} == {"decode"}
    for r in dec:
        assert r.n_new.sum() >= 1 and set(r.n_new.tolist()) <= {0, 1}
    for q in reqs:
        assert (q.submitted_s <= q.admitted_s <= q.first_token_s
                <= q.finished_s)


def test_shed_request_is_stamped_at_the_door(qwen):
    cfg, m, params = qwen
    eng = Engine(m, params, config=EngineConfig(max_pending=1, **ENGINE))
    a, b = _requests(cfg, [5, 5], [2, 2])
    eng.submit(a)
    eng.submit(b)
    assert b.status == "shed"
    assert b.submitted_s <= b.finished_s and b.admitted_s is None
    eng.run()
    assert a.submitted_s <= a.admitted_s <= a.first_token_s <= a.finished_s


@pytest.mark.parametrize("mixed", [True, False])
def test_step_spans_in_a_profiler_trace(qwen, tmp_path, mixed):
    """Each step writes ``engine.step`` with its phases inside it, in
    phase order; the admit span names the admitted requests."""
    from jax.profiler import ProfileData

    cfg, m, params = qwen
    eng = Engine(m, params, config=EngineConfig(mixed=mixed, **ENGINE))
    warm = _requests(cfg, [6], [2], seed=5)
    _drive(eng, warm, [1])  # compile outside the trace
    reqs = _requests(cfg, [6, 9], [3, 3])
    jax.profiler.start_trace(str(tmp_path))
    _drive(eng, reqs, [1, 1])
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    pd = ProfileData.from_file(path[-1])
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
            dict(ev.stats))
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("engine.")]
    steps = sorted((e for e in evs if e[0] == spans.STEP),
                   key=lambda e: e[1])
    phases = sorted((e for e in evs if e[0] != spans.STEP),
                    key=lambda e: e[1])
    assert steps and {e[0] for e in phases} <= set(spans.PHASES)
    dispatched = 0
    for name, lo, hi, _ in steps:
        inside = [e for e in phases if lo <= e[1] and e[2] <= hi]
        order = [spans.PHASES.index(e[0]) for e in inside]
        assert order == sorted(order)
        dispatched += spans.DISPATCH in [e[0] for e in inside]
    assert dispatched >= 2
    assert all(any(lo <= e[1] < hi for _, lo, hi, _ in steps)
               for e in phases)
    admitted = [e[3].get("rids") for e in phases if e[0] == spans.ADMIT]
    assert {str(r) for r in admitted if r is not None} == {"0 1"}


def test_step_programs_carry_their_names_and_scopes(qwen):
    """The trace finds the step programs by ``decode_fn`` / ``mixed_fn``
    in their module names, and a device operation's scope in the op
    metadata: both programs write the KV pool under ``kv_write`` and
    attend under ``attn_kernel``."""
    cfg, m, params = qwen
    eng = Engine(m, params, config=EngineConfig(mixed=True, **ENGINE))
    hlo = eng.compiled_steps()
    assert set(hlo) == {"decode", "mixed"}
    for kind, text in hlo.items():
        module = re.search(r"HloModule (\S+)", text).group(1)
        assert f"{kind}_fn" in module
        names = re.findall(r'op_name="([^"]*)"', text)
        for scope in (KV_WRITE_SCOPE, ATTN_SCOPE):
            assert any(f"/{scope}/" in n for n in names), (kind, scope)
