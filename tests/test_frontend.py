"""Async front-end, steppable engine, dispatcher replicas, and the
EngineConfig/SamplingParams API surface.

The load-bearing property throughout: every external driver — a manual
``step()`` loop, the asyncio ``Frontend``, a multi-replica
``Dispatcher`` — replays a ``(tick, Request)`` trace **byte-identically**
to the synchronous ``Engine.run``, for greedy and seeded-sampled
requests alike, because ``run`` itself is a thin loop over ``step``.
On top of that: cancellation frees slot + pages mid-decode, the fleet
prefix index restores pages published on another replica, the legacy
kwargs shim warns exactly once, and config validation refuses the
documented unsupported combinations at construction.
"""
import asyncio
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.errors import UnsupportedConfigError
from repro.models.transformer import Model
from repro.serve import (
    Dispatcher,
    Engine,
    EngineConfig,
    Frontend,
    Request,
    SamplingParams,
    TERMINAL_STATUSES,
)
from repro.serve import engine as engine_mod
from repro.serve.pages import FleetPrefixIndex


@pytest.fixture(scope="module")
def smoke_model():
    # float32 so token identity across drivers is exact (bf16 near-tie
    # argmaxes can legitimately flip between evaluation orders)
    cfg = get_config("qwen2.5-32b", "smoke", dtype="float32")
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    return cfg, m, params


ECFG = EngineConfig(max_len=64, max_new_tokens=8, num_slots=4, page_size=8,
                    mixed=True, prefill_budget=16)


def _trace(cfg, n=6, sampled=True, seed=0):
    """Fresh (tick, Request) arrivals — every other request carries
    per-request SamplingParams when ``sampled``. Requests are stateful:
    build a new copy per engine under comparison."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(1, cfg.vocab_size,
                              size=int(rng.integers(3, 14))).astype(np.int32)
        sp = (SamplingParams(temperature=0.8, top_k=5, seed=500 + i)
              if sampled and i % 2 else None)
        out.append((1 + 2 * i, Request(rid=i, prompt=prompt,
                                       max_new_tokens=5, sampling=sp)))
    return out


def _outputs(done):
    return {r.rid: (r.status, tuple(r.output)) for r in done}


# ---------------------------------------------------------------------------
# step(): run() is a thin loop over it — external stepping is identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampled", [False, True])
def test_manual_step_loop_matches_run(smoke_model, sampled):
    cfg, m, params = smoke_model
    ref = Engine(m, params, config=ECFG).run(
        arrivals=_trace(cfg, sampled=sampled))

    eng = Engine(m, params, config=ECFG)
    arr = sorted(_trace(cfg, sampled=sampled), key=lambda a: a[0])
    ai, emitted = 0, {}
    while eng.has_work() or ai < len(arr):
        due = []
        while ai < len(arr) and arr[ai][0] <= eng.iteration + 1:
            due.append(arr[ai][1])
            ai += 1
        res = eng.step(submits=due)
        for req, tok in res.emitted:
            emitted.setdefault(req.rid, []).append(tok)
    done = eng.finish_run()

    assert _outputs(done) == _outputs(ref)
    # StepResult.emitted carried every token exactly once, in order
    assert {rid: tuple(t) for rid, t in emitted.items()} == {
        r.rid: tuple(r.output) for r in done}


def test_step_result_finished_covers_every_request(smoke_model):
    cfg, m, params = smoke_model
    eng = Engine(m, params, config=ECFG)
    arr = _trace(cfg)
    ai, finished = 0, []
    while eng.has_work() or ai < len(arr):
        due = []
        while ai < len(arr) and arr[ai][0] <= eng.iteration + 1:
            due.append(arr[ai][1])
            ai += 1
        finished.extend(eng.step(submits=due).finished)
    done = eng.finish_run()
    assert sorted(r.rid for r in finished) == sorted(r.rid for r in done)
    assert all(r.status in TERMINAL_STATUSES for r in finished)


def test_run_refuses_mid_session(smoke_model):
    cfg, m, params = smoke_model
    eng = Engine(m, params, config=ECFG)
    eng.step(submits=[Request(rid=0, prompt=[3, 4, 5], max_new_tokens=4)])
    with pytest.raises(RuntimeError, match="session"):
        eng.run()
    eng.finish_run()
    eng.run()  # a sealed session no longer blocks run()


# ---------------------------------------------------------------------------
# Frontend: async submit/stream vs synchronous run
# ---------------------------------------------------------------------------


def _drive_frontend(engine, arrivals):
    async def main():
        streamed = {}
        async with Frontend(engine) as fe:
            handles = [fe.submit(r, tick=t) for t, r in arrivals]

            async def consume(h):
                streamed[h.request.rid] = [tok async for tok in h]

            await asyncio.gather(*(consume(h) for h in handles))
        return streamed, fe

    return asyncio.run(main())


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "seeded-sampled"])
def test_frontend_token_identical_to_run(smoke_model, sampled):
    cfg, m, params = smoke_model
    ref = Engine(m, params, config=ECFG).run(
        arrivals=_trace(cfg, sampled=sampled))

    eng = Engine(m, params, config=ECFG)
    streamed, fe = _drive_frontend(eng, _trace(cfg, sampled=sampled))

    assert _outputs(fe.results) == _outputs(ref)
    # the per-token stream IS the final output, token for token
    assert streamed == {r.rid: list(r.output) for r in fe.results}
    # every finished request's first-token latency is recorded
    assert all(fe.stats["ttft"][r.rid]["wall_s"] >= 0 for r in fe.results)


def test_frontend_result_resolves_terminal_status(smoke_model):
    cfg, m, params = smoke_model
    eng = Engine(m, params, config=ECFG)

    async def main():
        async with Frontend(eng) as fe:
            h = fe.submit(Request(rid=0, prompt=[2, 3, 4], max_new_tokens=3))
            req = await h.result()
            assert h.done()
        return req

    req = asyncio.run(main())
    assert req.status == "ok"
    assert len(req.output) == 3


# ---------------------------------------------------------------------------
# cancellation: slot + pages freed mid-decode
# ---------------------------------------------------------------------------


def test_cancel_mid_decode_frees_pages(smoke_model):
    cfg, m, params = smoke_model
    # prefix_share=False: nothing retained, so a clean pool returns to
    # exactly zero occupancy after the cancel
    eng = Engine(m, params, config=EngineConfig(
        max_len=64, max_new_tokens=64, num_slots=4, page_size=8,
        prefix_share=False))

    async def main():
        async with Frontend(eng) as fe:
            h = fe.submit(Request(rid=0, prompt=list(range(2, 12)),
                                  max_new_tokens=64))
            got = 0
            async for _ in h:
                got += 1
                if got == 3:
                    assert eng.slots.pool.memory_ratio() > 0
                    assert await h.cancel()
                    break
            req = await h.result()
        return req, got

    req, got = asyncio.run(main())
    assert req.status == "cancelled"
    assert got == 3
    assert len(req.output) >= 3  # tokens already decoded are kept
    assert not eng.slots.active.any()
    assert eng.slots.pool.memory_ratio() == 0.0
    # a second cancel is a no-op on a terminal request
    assert eng.cancel(req) is False


def test_cancel_before_submission_never_reaches_engine(smoke_model):
    cfg, m, params = smoke_model
    eng = Engine(m, params, config=ECFG)

    async def main():
        async with Frontend(eng) as fe:
            # tick far in the future with no other work: the drive loop
            # would need many idle steps to reach it — cancel first
            h = fe.submit(Request(rid=7, prompt=[2, 3], max_new_tokens=2),
                          tick=10_000)
            h2 = fe.submit(Request(rid=8, prompt=[4, 5], max_new_tokens=2))
            assert await h.cancel()
            await h2.result()
        return fe

    fe = asyncio.run(main())
    outs = _outputs(fe.results)
    assert outs[7][0] == "cancelled" and outs[7][1] == ()
    assert outs[8][0] == "ok"


def test_cancel_is_counted_and_terminal(smoke_model):
    cfg, m, params = smoke_model
    eng = Engine(m, params, config=ECFG)
    req = Request(rid=0, prompt=[3, 4, 5, 6], max_new_tokens=32)
    eng.step(submits=[req])
    assert eng.cancel(req) is True
    done = eng.finish_run()
    assert "cancelled" in TERMINAL_STATUSES
    assert _outputs(done)[0][0] == "cancelled"
    assert eng.decode_stats["cancelled"] == 1


# ---------------------------------------------------------------------------
# Dispatcher: replicas + fleet prefix sharing
# ---------------------------------------------------------------------------


def test_dispatcher_replicas_token_identical(smoke_model):
    cfg, m, params = smoke_model
    ref = Engine(m, params, config=ECFG).run(arrivals=_trace(cfg, n=8))

    disp = Dispatcher([Engine(m, params, config=ECFG) for _ in range(2)])
    done = disp.run(arrivals=_trace(cfg, n=8))

    assert _outputs(done) == _outputs(ref)
    # the trace actually spread over both replicas
    assert all(c > 0 for c in disp.decode_stats["routed_counts"])
    # decoded_tokens counts decode-step tokens (first tokens come from
    # prefill), merged across both replicas
    assert disp.decode_stats["decoded_tokens"] == sum(
        len(r.output) - 1 for r in ref)
    # the merged TTFT map holds every finished request of both replicas
    assert all(disp.decode_stats["ttft"][r.rid]["wall_s"] >= 0
               for r in done)


def test_dispatcher_routes_least_loaded_deterministically(smoke_model):
    cfg, m, params = smoke_model
    disp = Dispatcher([Engine(m, params, config=ECFG) for _ in range(2)])
    reqs = [Request(rid=i, prompt=[2 + i, 3, 4], max_new_tokens=2)
            for i in range(4)]
    # idle fleet: ties always break to replica 0 first, then alternate as
    # load accrues within the same routing pass
    for r in reqs:
        disp.route(r)
    assert disp.routed_counts == [2, 2]
    assert disp.cancel(Request(rid=99, prompt=[2], max_new_tokens=1)) is False


def test_fleet_prefix_restored_on_second_replica(smoke_model):
    cfg, m, params = smoke_model
    pcfg = EngineConfig(max_len=64, max_new_tokens=4, num_slots=4,
                        page_size=8)
    disp = Dispatcher([Engine(m, params, config=pcfg) for _ in range(2)])
    assert disp.fleet is not None
    a, b = disp.replicas
    prefix = list(range(2, 2 + 24))  # 3 full pages

    ra = a.run(arrivals=[(1, Request(rid=0, prompt=prefix + [7, 8],
                                     max_new_tokens=4))])
    assert disp.fleet.published > 0
    rb = b.run(arrivals=[(1, Request(rid=1, prompt=prefix + [7, 8],
                                     max_new_tokens=4))])

    # replica B never prefilled the prefix pages itself: they came out of
    # the fleet's host tier, and the tokens still match replica A's
    assert b.decode_stats["fleet_restored_pages"] > 0
    assert b.decode_stats["prefix_hit_ratio"] > 0
    assert disp.fleet.hits > 0
    assert tuple(ra[0].output) == tuple(rb[0].output)


def test_fleet_requires_prefix_share(smoke_model):
    cfg, m, params = smoke_model
    eng = Engine(m, params, config=EngineConfig(
        max_len=64, num_slots=4, page_size=8, prefix_share=False))
    with pytest.raises(UnsupportedConfigError, match="prefix"):
        eng.attach_fleet(FleetPrefixIndex())


# ---------------------------------------------------------------------------
# EngineConfig + legacy shim + SamplingParams
# ---------------------------------------------------------------------------


def test_legacy_kwargs_warn_once_and_match_config(smoke_model, monkeypatch):
    cfg, m, params = smoke_model
    monkeypatch.setattr(engine_mod, "_LEGACY_KWARGS_WARNED", False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = Engine(m, params, max_len=64, max_new_tokens=8,
                        num_slots=4, page_size=8, mixed=True,
                        prefill_budget=16)
        Engine(m, params, max_len=64, num_slots=4)  # second: no new warning
    deprecations = [x for x in w if issubclass(x.category,
                                               DeprecationWarning)]
    assert len(deprecations) == 1
    assert "EngineConfig" in str(deprecations[0].message)
    assert legacy.config == ECFG

    ref = Engine(m, params, config=ECFG).run(arrivals=_trace(cfg))
    assert _outputs(legacy.run(arrivals=_trace(cfg))) == _outputs(ref)


def test_config_and_legacy_kwargs_are_exclusive(smoke_model):
    cfg, m, params = smoke_model
    with pytest.raises(TypeError, match="config"):
        Engine(m, params, config=ECFG, max_len=64)
    with pytest.raises(TypeError, match="unexpected keyword"):
        Engine(m, params, max_lenn=64)


def test_validate_refuses_documented_unsupported_configs():
    rcfg = get_config("recurrentgemma-2b", "smoke")
    with pytest.raises(UnsupportedConfigError, match="mixed"):
        EngineConfig(mixed=True).validate(rcfg)
    with pytest.raises(ValueError, match="prefill_budget"):
        EngineConfig(prefill_budget=0).validate(
            get_config("qwen2.5-32b", "smoke"))


def test_per_request_sampling_matches_engine_wide(smoke_model):
    cfg, m, params = smoke_model
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab_size, size=7).astype(np.int32)
               for _ in range(3)]

    # engine-wide sampling, per-request seeds
    eng_w = Engine(m, params, config=EngineConfig(
        max_len=64, max_new_tokens=6, num_slots=4, page_size=8,
        temperature=0.8, top_k=5))
    ref = eng_w.run(arrivals=[
        (1, Request(rid=i, prompt=p, max_new_tokens=6, seed=900 + i))
        for i, p in enumerate(prompts)])

    # greedy engine, the SAME sampling carried per-request
    eng_p = Engine(m, params, config=EngineConfig(
        max_len=64, max_new_tokens=6, num_slots=4, page_size=8))
    per = eng_p.run(arrivals=[
        (1, Request(rid=i, prompt=p, max_new_tokens=6,
                    sampling=SamplingParams(temperature=0.8, top_k=5,
                                            seed=900 + i)))
        for i, p in enumerate(prompts)])

    assert _outputs(per) == _outputs(ref)


def test_mixed_greedy_and_sampled_batch(smoke_model):
    """Greedy and sampled requests share one batch: the greedy lanes must
    emit exactly what an all-greedy engine emits for the same prompts."""
    cfg, m, params = smoke_model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=6).astype(np.int32)
               for _ in range(4)]

    eng_g = Engine(m, params, config=EngineConfig(
        max_len=64, max_new_tokens=5, num_slots=4, page_size=8))
    all_greedy = eng_g.run(arrivals=[
        (1, Request(rid=i, prompt=p, max_new_tokens=5))
        for i, p in enumerate(prompts)])
    greedy_out = _outputs(all_greedy)

    eng_x = Engine(m, params, config=EngineConfig(
        max_len=64, max_new_tokens=5, num_slots=4, page_size=8))
    mixed = eng_x.run(arrivals=[
        (1, Request(rid=i, prompt=p, max_new_tokens=5,
                    sampling=(SamplingParams(temperature=0.9, top_k=4,
                                             seed=7 + i)
                              if i % 2 else None)))
        for i, p in enumerate(prompts)])
    mixed_out = _outputs(mixed)

    for i in range(4):
        if i % 2 == 0:
            assert mixed_out[i] == greedy_out[i]
        else:
            assert mixed_out[i][0] == "ok"


def test_public_surface_is_importable():
    import repro.serve as serve
    assert set(serve.__all__) == {
        "Engine", "EngineConfig", "Request", "SamplingParams",
        "Frontend", "Dispatcher", "FaultPlan", "TERMINAL_STATUSES"}
    for name in serve.__all__:
        assert getattr(serve, name) is not None
