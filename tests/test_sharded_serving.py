"""Device-count-parametrized equivalence suite for tensor-parallel sharded
decode: the slot engine on a ``make_local_mesh(1, N)`` mesh must produce
**token-identical** output to the single-device engine for every config
family, mesh size, and serving feature — sharding is a placement decision,
never a semantics change.

Every test runs in a ``mesh_cpu`` subprocess (forced host devices; the
parent session keeps exactly 1 device) and compares baseline (``mesh=None``)
against sharded runs *inside the same child*, so both see identical jax
versions, seeds, and workloads. Configs use ``dtype="float32"``: the KV-head
merge is exact and the row-parallel linear psums reorder only f32
accumulation, so greedy argmax is deterministic at f32 — bfloat16 smoke
configs carry ~3e-2 intrinsic path noise that flips near-tie argmaxes even
between two UNSHARDED evaluation orders, which would pin noise, not the
sharding contract.

Covered: full attention (MHA) at N in {1, 2, 4}, GQA at N in {1, 2},
GQA whose kv_heads don't divide the mesh (validated construction error),
ring/windowed lanes, sampled decode (seeded sampling is placement- and
mesh-invariant), and preemption + prefix-sharing/CoW invisibility under
audit on a mesh.
"""
import pathlib

import pytest

# Shared child preamble: model/engine builders + a runner that serves the
# same workload through baseline and sharded engines and diffs the output
# streams (tokens AND terminal statuses).
COMMON = """
import numpy as np
from repro.configs import get_config
from repro.core.errors import UnsupportedConfigError
from repro.launch.mesh import make_local_mesh
from repro.models.transformer import Model
from repro.serve import Engine, FaultPlan, Request


def build(arch, **over):
    cfg = get_config(arch, "smoke", dtype="float32", **over)
    m = Model(cfg)
    return cfg, m, m.init(jax.random.key(0))


def serve(m, params, prompts, mesh, budgets=None, **eng_kw):
    eng = Engine(m, params, mesh=mesh, **eng_kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(
            rid=i, prompt=np.asarray(p, np.int32).copy(),
            max_new_tokens=budgets[i] if budgets else eng.max_new))
    done = eng.run()
    outs = {d.rid: (d.status, tuple(d.output)) for d in done}
    return outs, eng.decode_stats


def prompts_for(cfg, n, base=5, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=base + i).astype(np.int32)
            for i in range(n)]


def diff(base, shard):
    bad = {r: (base[r], shard[r]) for r in base
           if base.get(r) != shard.get(r)}
    return {str(r): [list(map(str, b)), list(map(str, s))]
            for r, (b, s) in bad.items()}
"""


def test_full_attention_token_identity_mesh_1_2_4(mesh_cpu):
    """MHA full-attention greedy decode: bit-identical token streams on
    meshes of 1, 2, and 4 ranks (1-rank mesh == no mesh is part of the
    contract: tensor_parallel_size treats them as the same program)."""
    r = mesh_cpu(4, COMMON + """
cfg, m, params = build("qwen1.5-4b")
prompts = prompts_for(cfg, 4)
kw = dict(max_len=16, max_new_tokens=4, num_slots=2)
base, _ = serve(m, params, prompts, None, **kw)
mismatches = {}
for n in (1, 2, 4):
    shard, st = serve(m, params, prompts, make_local_mesh(1, n), **kw)
    mismatches[n] = diff(base, shard)
    assert st["tp_ranks"] == n, (n, st["tp_ranks"])
print(json.dumps({"mismatches": mismatches,
                  "n_done": len(base),
                  "tokens": sum(len(t) for _, t in base.values())}))
""")
    assert r["n_done"] == 4 and r["tokens"] > 0
    assert all(not m for m in r["mismatches"].values()), r["mismatches"]


def test_gqa_token_identity_mesh_1_2(mesh_cpu):
    """GQA (kv_heads=2 < n_heads=4): grouped q heads follow their kv head
    across ranks; token streams identical at N in {1, 2}."""
    r = mesh_cpu(2, COMMON + """
cfg, m, params = build("qwen2.5-32b")
assert cfg.kv_heads < cfg.n_heads  # the test is about GQA
prompts = prompts_for(cfg, 4)
kw = dict(max_len=16, max_new_tokens=4, num_slots=2)
base, _ = serve(m, params, prompts, None, **kw)
mismatches = {}
for n in (1, 2):
    shard, _ = serve(m, params, prompts, make_local_mesh(1, n), **kw)
    mismatches[n] = diff(base, shard)
print(json.dumps({"mismatches": mismatches, "n_done": len(base)}))
""")
    assert r["n_done"] == 4
    assert all(not m for m in r["mismatches"].values()), r["mismatches"]


def test_indivisible_kv_heads_refused_at_construction(mesh_cpu):
    """kv_heads=2 on a 4-way model axis cannot give every rank a whole
    head: Engine must refuse at construction with an actionable
    UnsupportedConfigError, not fail at trace time."""
    r = mesh_cpu(4, COMMON + """
cfg, m, params = build("qwen2.5-32b")
assert cfg.kv_heads == 2
mesh = make_local_mesh(1, 4)
try:
    Engine(m, params, max_len=16, max_new_tokens=4, num_slots=2, mesh=mesh)
    outcome = {"raised": False}
except UnsupportedConfigError as e:
    msg = str(e)
    outcome = {"raised": True,
               "names_counts": "kv_heads=2" in msg and "4-way" in msg}
print(json.dumps(outcome))
""")
    assert r["raised"], "indivisible GQA config must be refused"
    assert r["names_counts"], "the error must name the offending counts"


def test_ring_windowed_token_identity_mesh_1_2(mesh_cpu):
    """Sliding-window (ring-lane) stack: the window mask and canonical
    ring phase are rank-local, so sharded ring decode is token-identical
    too (starcoder2 smoke: kv_heads=2 bounds the mesh at 2)."""
    r = mesh_cpu(2, COMMON + """
cfg, m, params = build("starcoder2-15b")
assert cfg.sliding_window is not None
prompts = prompts_for(cfg, 4, base=6, seed=1)
kw = dict(max_len=16, max_new_tokens=6, num_slots=2)
base, _ = serve(m, params, prompts, None, **kw)
mismatches = {}
for n in (1, 2):
    shard, _ = serve(m, params, prompts, make_local_mesh(1, n), **kw)
    mismatches[n] = diff(base, shard)
print(json.dumps({"mismatches": mismatches, "n_done": len(base)}))
""")
    assert r["n_done"] == 4
    assert all(not m for m in r["mismatches"].values()), r["mismatches"]


def test_sampled_decode_seed_stable_across_meshes(mesh_cpu):
    """Sampled decode (temperature/top-k, per-request seeds keyed on
    absolute position): the drawn tokens must be the SAME on every mesh
    size — sampling is a function of (seed, position, logits), and at f32
    the logits are placement-invariant."""
    r = mesh_cpu(4, COMMON + """
cfg, m, params = build("qwen1.5-4b")
prompts = prompts_for(cfg, 4, seed=3)
kw = dict(max_len=16, max_new_tokens=5, num_slots=2,
          temperature=0.8, top_k=8, seed=7)
base, _ = serve(m, params, prompts, None, **kw)
mismatches = {}
for n in (1, 2, 4):
    shard, _ = serve(m, params, prompts, make_local_mesh(1, n), **kw)
    mismatches[n] = diff(base, shard)
print(json.dumps({"mismatches": mismatches, "n_done": len(base),
                  "tokens": sum(len(t) for _, t in base.values())}))
""")
    assert r["n_done"] == 4 and r["tokens"] > 0
    assert all(not m for m in r["mismatches"].values()), r["mismatches"]


def test_preemption_and_cow_invisible_under_sharding(mesh_cpu):
    """The full paged feature set on a mesh: shared-prefix prompts (page
    mapping + copy-on-write) and FORCED preemptions (FaultPlan schedule —
    the pool-pressure path organically preempts only on larger workloads,
    and the test must not depend on tuning), with per-step invariant
    audits on. Preempt-requeue resumes and CoW must stay invisible in the
    token streams, identically so on the mesh."""
    r = mesh_cpu(2, COMMON + """
cfg, m, params = build("qwen2.5-32b")
rng = np.random.default_rng(5)
common = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
prompts = [np.concatenate(
    [common, rng.integers(0, cfg.vocab_size, size=3 + i).astype(np.int32)])
    for i in range(6)]
kw = dict(max_len=24, max_new_tokens=6, num_slots=2, page_size=4,
          pool_frac=0.55, prefix_share=True, audit=True,
          faults=FaultPlan(seed=0, preempt_at=(3, 7)))
base, bs = serve(m, params, prompts, None, **kw)
shard, ss = serve(m, params, prompts, make_local_mesh(1, 2), **kw)
print(json.dumps({"mismatch": diff(base, shard),
                  "preemptions": [bs["preemptions"], ss["preemptions"]],
                  "pages_shared": [bs["pages_shared"], ss["pages_shared"]],
                  "hit": [bs["prefix_hit_ratio"], ss["prefix_hit_ratio"]],
                  "audit_violations": [bs["audit_violations"],
                                       ss["audit_violations"]],
                  "statuses": sorted(s for s, _ in base.values())}))
""")
    assert not r["mismatch"], r["mismatch"]
    # the workload must actually exercise what it claims to pin
    assert min(r["preemptions"]) > 0, "no preemption happened: " + str(r)
    assert min(r["pages_shared"]) > 0, "no page was shared: " + str(r)
    assert min(r["hit"]) > 0.0
    assert r["audit_violations"] == [0, 0]
    assert set(r["statuses"]) == {"ok"}


def test_per_rank_kv_bytes_scale_inversely_with_mesh(mesh_cpu):
    """decode_stats accounting: kv_bytes_per_token is a workload property
    (identical across meshes — same tokens, same visited blocks) while
    kv_bytes_per_token_per_rank is exactly 1/N of it: each rank streams
    only its Hkv/N head-slice of every visited page."""
    r = mesh_cpu(4, COMMON + """
cfg, m, params = build("qwen1.5-4b")
prompts = prompts_for(cfg, 4)
kw = dict(max_len=16, max_new_tokens=4, num_slots=2)
rows = {}
for n in (1, 2, 4):
    mesh = None if n == 1 else make_local_mesh(1, n)
    _, st = serve(m, params, prompts, mesh, **kw)
    rows[n] = {"kvpt": st["kv_bytes_per_token"],
               "per_rank": st["kv_bytes_per_token_per_rank"],
               "tp": st["tp_ranks"], "tokens": st["decoded_tokens"]}
print(json.dumps(rows))
""")
    kvpt = {n: row["kvpt"] for n, row in r.items()}
    assert len(set(kvpt.values())) == 1, kvpt  # workload-invariant
    for n, row in r.items():
        assert row["tp"] == int(n)
        assert row["per_rank"] == pytest.approx(row["kvpt"] / int(n))
    toks = {row["tokens"] for row in r.values()}
    assert len(toks) == 1 and toks.pop() > 0


def test_params_and_pages_built_straight_into_shards(mesh_cpu):
    """``init_params(model, seed, mesh)`` creates every leaf in its shards
    (each rank holds 1/4 of a column-parallel weight) with the same values
    as a single-device init; the engine keeps those leaves as they are, and
    its page pool is created KV-head-sharded."""
    out = mesh_cpu(4, """
        import json
        from repro.launch.mesh import make_local_mesh
        from repro.launch.serve import build_model, init_params
        from repro.serve import Engine, EngineConfig
        m = build_model("qwen1.5-4b", "smoke")
        mesh = make_local_mesh(1, 4)
        p = init_params(m, 0, mesh)
        whole = init_params(m, 0)
        same = all(bool(jnp.array_equal(a, b)) for a, b in
                   zip(jax.tree.leaves(p), jax.tree.leaves(whole)))
        wq = p["layers"]["attn"]["wq"]["w"]
        eng = Engine(m, p, config=EngineConfig(max_len=16, num_slots=2),
                     mesh=mesh)
        kept = all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                          jax.tree.leaves(p)))
        kv = jax.tree.leaves(eng.slots.caches)[0]
        print(json.dumps({
            "same": same, "kept": kept, "wq_bytes": wq.nbytes,
            "wq_shard_bytes": sorted({s.data.nbytes
                                      for s in wq.addressable_shards}),
            "kv_bytes": kv.nbytes,
            "kv_shard_bytes": sorted({s.data.nbytes
                                      for s in kv.addressable_shards}),
            "kv_devices": len({s.device for s in kv.addressable_shards})}))
    """)
    assert out["same"] and out["kept"]
    assert out["wq_shard_bytes"] == [out["wq_bytes"] // 4]
    assert out["kv_shard_bytes"] == [out["kv_bytes"] // 4]
    assert out["kv_devices"] == 4


def test_tp4_tda_engine_matches_reference_logits(mesh_cpu):
    """starcoder2 at a smoke size that splits as the published model does
    over four ranks (4 KV heads, 12 query heads each; head size 8), with
    the published block (biases on every linear, seeded nonzero by the
    benchmark's own weights, LayerNorm eps 1e-5, window 32): the Engine
    on a 1x4 mesh with ``decode_attn="tda"`` runs the paged kernels per
    rank (interpret mode), and the logits of every step it serves —
    prompt chunks through the mixed step, then decode through the page
    pool — match the benchmark's plain reference (``reference.py``, a
    full float32 forward with no cache) at the same positions, and match
    the same engine with ``decode_attn="dense"`` and the one-device
    engine. Prompts pass the window, so the lanes are rings. Float32
    throughout: the only differences are the order of float32 sums
    (kernel tiles, row-parallel all-reduce), a few 1e-6 on logits of
    unit size; the limit is 5e-5."""
    bench = pathlib.Path(__file__).resolve().parents[1] / "benchmarks/chip"
    path = f"import sys\nsys.path.insert(0, {str(bench)!r})\n"
    r = mesh_cpu(4, COMMON + path + """
import reference
import weights
from repro.models import transformer as T

records = []


def tap(name):
    orig = getattr(T.Model, name)

    def step(self, params, batch, caches, cache_index, *a, **kw):
        out = orig(self, params, batch, caches, cache_index, *a, **kw)
        if name == "mixed_step":
            logits, n_new = out[0], a[0]
        else:
            logits, n_new = out[0][:, 0], jnp.ones_like(cache_index)
        jax.debug.callback(
            lambda *v: records.append((name, [np.asarray(x) for x in v])),
            logits, cache_index, n_new, kw["slot_mask"])
        return out

    setattr(T.Model, name, step)


tap("mixed_step")
tap("decode_step")

cfg = get_config("starcoder2-15b", "smoke", dtype="float32", n_heads=48,
                 n_kv_heads=4, d_head=8)
assert (cfg.linear_bias, cfg.norm_eps) == (True, 1e-5)
m = Model(cfg)
params = weights.make_weights(m, 2**33 + 7)
lens = [37, 9, 44, 21, 30]
rng = np.random.default_rng(3)
prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]


def serve_logits(mesh, attn):
    eng = Engine(m, params, mesh=mesh, max_len=16, max_new_tokens=6,
                 num_slots=3, max_prompt_len=48, page_size=8,
                 decode_attn=attn)
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=6)
            for i, p in enumerate(prompts)]
    for q in reqs:
        eng.submit(q)
    rows = {}
    while eng.has_work():
        before = list(eng.slots.request)
        n0 = len(records)
        res = eng.step()
        jax.effects_barrier()
        after = list(eng.slots.request)
        for name, (logits, ci, nn, act) in records[n0:]:
            # The chunk step's rows are the slots it ran, in row order.
            slots = res.chunk_slots if name == "mixed_step" \
                else np.arange(act.size)
            for b in np.flatnonzero(act & (nn > 0)):
                s = slots[b]
                req = after[s] if after[s] is not None else before[s]
                rows[(req.rid, int(ci[b] + nn[b] - 1))] = logits[b]
    eng.finish_run()
    assert all(q.status == "ok" for q in reqs)
    return rows, {q.rid: list(q.output) for q in reqs}, eng.decode_attn


tp, out_tp, attn = serve_logits(make_local_mesh(1, 4), "tda")
assert attn == "tda"
dense, out_dense, _ = serve_logits(make_local_mesh(1, 4), "dense")
one, out_one, _ = serve_logits(None, "tda")

ref = reference.Reference(
    {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
     "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
     "rope_theta": cfg.rope_theta, "sliding_window": cfg.sliding_window,
     "model_type": "starcoder2", "norm_epsilon": 1e-5,
     "hidden_act": "gelu_pytorch_tanh"}, 64, 64)
ref_gap = 0.0
for rid, out in out_tp.items():
    seq = np.concatenate([prompts[rid], np.asarray(out, np.int32)])
    pos = np.array(sorted(p for r_, p in tp if r_ == rid), np.int32)
    want = np.asarray(ref._logits(params, jnp.asarray(seq),
                                  jnp.asarray(pos), False))
    got = np.stack([tp[(rid, int(p))] for p in pos])
    ref_gap = max(ref_gap, float(np.abs(got - want).max()))
steps_seen = {p for _, p in tp}
print(json.dumps({
    "rows": len(tp), "max_pos": max(steps_seen),
    "ref": ref_gap,
    "dense": max(float(np.abs(tp[k] - dense[k]).max()) for k in tp),
    "one": max(float(np.abs(tp[k] - one[k]).max()) for k in tp),
    "same_keys": sorted(map(list, tp)) == sorted(map(list, dense))
                 == sorted(map(list, one)),
    "tokens_same": out_tp == out_dense == out_one}))
""")
    # 5 requests x 6 served tokens, and the prompt chunks that end short
    # of a prompt's last token.
    assert r["rows"] >= 30 and r["max_pos"] > 40, r
    assert r["same_keys"] and r["tokens_same"], r
    assert r["ref"] < 5e-5 and r["dense"] < 5e-5 and r["one"] < 5e-5, r
