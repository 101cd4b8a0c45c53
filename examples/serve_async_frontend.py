"""Async serving over the steppable slot engine: submit requests from
asyncio coroutines, stream tokens as they decode, cancel mid-stream, and
fan a trace out over two engine replicas that share prompt prefixes
through a fleet index.

Four sections (docs/serving.md, "Async front-end & replicas"):

1. **Streaming**: ``Frontend.submit`` returns a handle immediately;
   ``async for tok in handle`` yields each token the step it retires.
   Mixed per-request sampling — greedy and ``SamplingParams``-carrying
   requests share the same jitted decode step.
2. **Token identity**: the same arrival trace through the async front
   end and through synchronous ``Engine.run`` produces byte-identical
   outputs (the front end only re-packages ``Engine.step``).
3. **Cancellation**: cancelling a handle mid-decode frees its slot and
   pages immediately — pool occupancy returns to baseline without
   waiting for the request's token budget.
4. **Replicas + fleet prefix**: a ``Dispatcher`` routes deterministically
   over two replicas; a prompt prefix prefilled on replica A is restored
   on replica B from the fleet's host-memory tier instead of being
   recomputed.

  PYTHONPATH=src python examples/serve_async_frontend.py
"""
import asyncio

import jax
import numpy as np

from repro.configs import get_config
from repro.models.transformer import Model
from repro.serve import (Dispatcher, Engine, EngineConfig, Frontend,
                         Request, SamplingParams)

ECFG = EngineConfig(max_len=64, max_new_tokens=8, num_slots=4, page_size=8,
                    mixed=True, prefill_budget=16)


def make_requests(cfg, n=8):
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(n):
        prompt = rng.integers(1, cfg.vocab_size,
                              size=int(rng.integers(4, 16))).astype(np.int32)
        # odd rids sample (per-request params), even rids stay greedy —
        # one mixed batch, one compiled step
        sp = (SamplingParams(temperature=0.8, top_k=5, seed=100 + i)
              if i % 2 else None)
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=6,
                            sampling=sp))
    return reqs


async def serve_streaming(model, params, cfg):
    eng = Engine(model, params, config=ECFG)
    streamed = {}
    async with Frontend(eng) as fe:
        handles = [fe.submit(r, tick=1 + 2 * i)
                   for i, r in enumerate(make_requests(cfg))]

        async def consume(h):
            toks = [tok async for tok in h]
            streamed[h.request.rid] = toks

        await asyncio.gather(*(consume(h) for h in handles))
    return streamed


async def serve_cancel(model, params, cfg):
    eng = Engine(model, params, config=EngineConfig(
        max_len=64, max_new_tokens=64, num_slots=4, page_size=8,
        prefix_share=False))
    async with Frontend(eng) as fe:
        h = fe.submit(Request(rid=0, prompt=list(range(2, 12)),
                              max_new_tokens=64))
        got = 0
        async for _ in h:
            got += 1
            if got == 3:
                await h.cancel()
                break
        req = await h.result()
    return req, got, eng.slots.pool.memory_ratio()


def main():
    cfg = get_config("qwen2.5-32b", "smoke", dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.key(0))

    # ---- 1+2: stream async, then replay the same trace synchronously ----
    streamed = asyncio.run(serve_streaming(model, params, cfg))
    eng_ref = Engine(model, params, config=ECFG)
    ref = eng_ref.run(arrivals=[(1 + 2 * i, r) for i, r in
                                enumerate(make_requests(cfg))])
    ref_out = {r.rid: list(r.output) for r in ref}
    assert streamed == ref_out, "async streaming diverged from Engine.run"
    print(f"streamed {len(streamed)} requests "
          f"(every other one sampled at T=0.8/top-k 5), e.g. rid 1 -> "
          f"{streamed[1]}")
    print("token-identical to synchronous Engine.run on the same trace")

    # ---- 3: cancellation frees pages mid-decode ----
    req, got, ratio = asyncio.run(serve_cancel(model, params, cfg))
    print(f"cancelled rid {req.rid} after {got} streamed tokens: "
          f"status={req.status}, pool occupancy back to {ratio:.2f}")

    # ---- 4: two replicas, one fleet prefix index ----
    prefix = list(range(2, 2 + 24))  # 3 full pages of shared system prompt
    replicas = [Engine(model, params, config=EngineConfig(
        max_len=64, max_new_tokens=4, num_slots=4, page_size=8))
        for _ in range(2)]
    disp = Dispatcher(replicas)
    a, b = replicas
    a.run(arrivals=[(1, Request(rid=0, prompt=prefix + [7, 8],
                                max_new_tokens=4))])
    b.run(arrivals=[(1, Request(rid=1, prompt=prefix + [9, 10],
                                max_new_tokens=4))])
    print(f"fleet prefix: replica A published {disp.fleet.published} "
          f"pages; replica B restored {b.decode_stats['fleet_restored_pages']}"
          f" from the host tier (prefix hit ratio "
          f"{b.decode_stats['prefix_hit_ratio']:.2f}) — one prefill per "
          f"fleet, not per replica")

    # the dispatcher itself is steppable: same trace, merged stats
    replicas2 = [Engine(model, params, config=ECFG) for _ in range(2)]
    disp2 = Dispatcher(replicas2)
    done = disp2.run(arrivals=[(1 + 2 * i, r) for i, r in
                               enumerate(make_requests(cfg))])
    d_out = {r.rid: list(r.output) for r in done}
    assert d_out == ref_out, "replicated fleet diverged from single engine"
    print(f"dispatcher over 2 replicas: routed {disp2.decode_stats['routed_counts']}, "
          f"token-identical to the single engine "
          f"({disp2.decode_stats['decoded_tokens']} tokens, "
          f"{disp2.decode_stats['steps']} replica-steps)")


if __name__ == "__main__":
    main()
