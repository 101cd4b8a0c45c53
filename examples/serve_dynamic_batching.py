"""Serve small models through the T-REX-style continuous-batching engine:
short prompts share prefill weight sweeps (dynamic batching), long prompts
are chunked instead of rejected, and decode runs one jitted step over a slot
table of per-request cache lanes with mid-decode admissions. Reports both
utilization metrics: prefill packing fill and per-step decode slot
occupancy.

Two stacks go through the same engine to show the slot-state table is
cache-kind agnostic (docs/serving.md):

* a dense GQA transformer (full-attention KV lanes, packed prefill), and
* a recurrentgemma-style hybrid (RG-LRU recurrent state lanes + ring-
  buffered short-window attention lanes, row-per-request prefill) — the
  stacks that used to fall back to seed-style lock-step decode.

The last section serves a misbehaving burst through the failure-hardened
path (docs/serving.md, "Serving failure model"): a bounded pending queue
sheds overload, deadlines expire stragglers, an injected NaN is
quarantined to its slot — and every request comes back in a counted
terminal status.

  PYTHONPATH=src python examples/serve_dynamic_batching.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.data import request_lengths
from repro.models.transformer import Model
from repro.serve import (Engine, EngineConfig, FaultPlan, Request,
                         TERMINAL_STATUSES)


def main():
    cfg = get_config("qwen2.5-32b", "smoke")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    # page_size=16 (instead of the TDA-block default) so the footprint
    # tracks occupancy finely and the 48-token demo prefix spans 3 pages.
    # mixed=False pins the phase-serialized engine: this section
    # demonstrates packed prefill sweeps (`eng.stats`), which the default
    # mixed step replaces with chunk rows (the last section compares the
    # two head-to-head).
    eng = Engine(model, params, config=EngineConfig(
        max_len=64, max_new_tokens=8, num_slots=8, page_size=16,
        mixed=False))

    rng = np.random.default_rng(0)
    lens = list(request_lengths(24, max_len=64, dist="bert"))
    lens[3] = 90  # one over-long prompt: chunked solo prefill, not rejected
    for rid, n in enumerate(lens):
        eng.submit(Request(rid=rid, prompt=rng.integers(
            0, cfg.vocab_size, size=n).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 9))))
    done = eng.run()

    print(f"served {len(done)} requests, e.g. request 0 -> "
          f"{[r for r in done if r.rid == 0][0].output}")
    fills = [s["utilization"] for s in eng.stats]
    reqs = sum(s["n_requests"] for s in eng.stats)
    rows = sum(s["rows"] for s in eng.stats)
    print(f"packed {reqs} requests into {rows} prefill rows "
          f"({reqs / rows:.2f} req/weight-sweep, paper: up to 4)")
    print(f"mean prefill fill {np.mean(fills):.2f} vs "
          f"unpacked {np.mean(lens) / 64:.2f} "
          f"-> {np.mean(fills) / (np.mean(lens) / 64):.2f}x "
          f"(paper: up to 3.31x)")
    ds = eng.decode_stats
    print(f"decode: {ds['decoded_tokens']} tokens in {ds['steps']} steps, "
          f"per-step slot utilization {ds['slot_utilization']:.2f} "
          f"(the serving-side PE-utilization analogue)")
    print(f"paged lane pool: {ds['kv_pages_total']} pages x "
          f"{eng.config.page_size} tokens, mean occupancy "
          f"{ds['kv_memory_ratio']:.2f} of capacity "
          f"(contiguous lanes would pin 1.00), "
          f"{ds['preemptions']} preemptions "
          f"(cache footprint follows occupancy — see docs/serving.md)")

    # ---- prefix sharing: a "system prompt" seeds the cache, then six
    # requests reuse it — their prefix pages are mapped, not recomputed.
    pre = rng.integers(0, cfg.vocab_size, size=48).astype(np.int32)
    eng.submit(Request(rid=99, prompt=np.concatenate(
        [pre, pre[:5]]).astype(np.int32), max_new_tokens=2))
    eng.run()  # publishes the prefix pages (retained after release)
    for rid in range(6):
        eng.submit(Request(rid=100 + rid, prompt=np.concatenate(
            [pre, rng.integers(0, cfg.vocab_size,
                               size=int(rng.integers(4, 12)))]
        ).astype(np.int32), max_new_tokens=4))
    eng.run()
    ds = eng.decode_stats
    print(f"prefix sharing: 6 requests behind one 48-token system prefix "
          f"-> hit ratio {ds['prefix_hit_ratio']:.2f}, "
          f"{ds['pages_shared']} page mappings served from shared pages "
          f"(copy-on-write keeps them output-invisible)")

    # ---- same engine, recurrent + ring cache kinds (no lock-step path) ----
    rcfg = get_config("recurrentgemma-2b", "smoke")
    rmodel = Model(rcfg)
    rparams = rmodel.init(jax.random.key(1))
    reng = Engine(rmodel, rparams, config=EngineConfig(
        max_len=16, max_new_tokens=6, num_slots=4))
    for rid, n in enumerate(rng.integers(3, 14, size=12)):
        reng.submit(Request(rid=rid, prompt=rng.integers(
            0, rcfg.vocab_size, size=int(n)).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 7))))
    rdone = reng.run()
    rds = reng.decode_stats
    print(f"\nrecurrent hybrid ({rcfg.name}): served {len(rdone)} requests "
          f"through RG-LRU state lanes + local-window ring lanes")
    print(f"decode: {rds['decoded_tokens']} tokens in {rds['steps']} steps, "
          f"slot utilization {rds['slot_utilization']:.2f}, "
          f"kv-block ratio {rds['kv_block_ratio']:.2f} "
          f"(row-per-request right-aligned prefill; see docs/serving.md)")

    # ---- degraded serving: a misbehaving burst through the hardened
    # path. max_pending bounds the queue (newest submits shed), tight
    # ttl_steps expire whatever queues too long, and a seeded FaultPlan
    # injects a NaN mid-decode — quarantined to its slot while every
    # other request keeps its exact tokens. Audits re-check the pool
    # invariants every iteration.
    deng = Engine(model, params, config=EngineConfig(
        max_len=64, max_new_tokens=8, num_slots=2, page_size=16,
        max_pending=8, audit=True),
        faults=FaultPlan(seed=3, nan_at=((2, 0),)))
    for rid, n in enumerate(request_lengths(16, max_len=64, dist="bert")):
        deng.submit(Request(rid=200 + rid, prompt=rng.integers(
            0, cfg.vocab_size, size=int(n)).astype(np.int32),
            max_new_tokens=6, ttl_steps=40))
    ddone = deng.run()
    dds = deng.decode_stats
    counts = {s: n for s, n in dds["status_counts"].items() if n}
    print(f"\ndegraded burst (2 slots, max_pending=8, ttl=40 ticks, one "
          f"injected NaN, audits on): {len(ddone)} requests back, "
          f"per-status counts {counts}")
    assert sum(dds["status_counts"].values()) == len(ddone)
    assert all(r.status in TERMINAL_STATUSES for r in ddone)
    failed = [r for r in ddone if r.status == "failed"]
    if failed:
        print(f"  e.g. request {failed[0].rid} failed: "
              f"{failed[0].status_reason}")
    print(f"  faults injected {dds['faults_injected']}, "
          f"{dds['audit_violations']} audit violations "
          f"(every fault lands in a counted terminal status — "
          f"tests/test_faults.py pins this)")

    # ---- bursty mid-decode arrivals: chunked prefill interleaved with
    # decode (the chunk step's rows beside the decode step in each
    # iteration) vs the phase-serialized engine.
    # Three waves of long prompts (4-8x the chunk width) land while
    # earlier admissions are still decoding; `run(arrivals=...)` replays
    # the identical schedule through both engines. TTFT is reported in
    # wall seconds (host time where it runs, not a chip figure) and in
    # run-loop clock ticks (docs/serving.md, "Interleaved chunked
    # prefill").
    burst = [(t, int(n)) for t, n in
             zip([1] * 6 + [4] * 5 + [8] * 5,
                 rng.integers(280, 500, size=16))]

    def burst_arrivals():
        r = np.random.default_rng(5)
        return [(t, Request(rid=400 + i, prompt=r.integers(
                     0, cfg.vocab_size, size=n).astype(np.int32),
                     max_new_tokens=int(r.integers(2, 6))))
                for i, (t, n) in enumerate(burst)]

    print("\nbursty mid-decode arrivals (16 long prompts in 3 waves):")
    for mixed in (True, False):
        beng = Engine(model, params, config=EngineConfig(
            max_len=64, max_new_tokens=8, num_slots=8, page_size=8,
            max_prompt_len=512, prefix_share=False, mixed=mixed))
        bdone = beng.run(arrivals=burst_arrivals())
        bds = beng.decode_stats
        wall = [v["wall_s"] for v in bds["ttft"].values()]
        ticks = [v["clock"] for v in bds["ttft"].values()]
        tag = ("mixed step  " if mixed else "serialized  ")
        print(f"  {tag} ttft p50/p99 = {np.percentile(wall, 50):.3f}/"
              f"{np.percentile(wall, 99):.3f} s "
              f"({np.percentile(ticks, 50):.0f}/"
              f"{np.percentile(ticks, 99):.0f} ticks), "
              f"slot utilization {bds['slot_utilization']:.2f}, "
              f"{bds['mixed_steps']} mixed steps "
              f"({len(bdone)} requests ok)")


if __name__ == "__main__":
    main()
