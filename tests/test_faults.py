"""Chaos suite for the failure-hardened slot engine (docs/serving.md,
"Serving failure model").

The two load-bearing properties, asserted here rather than hoped:

* **Fault isolation**: under a seeded :class:`FaultPlan` mixing
  page-allocation failures, forced preemptions, NaN logits, and stalls,
  every *surviving* request's token stream is bit-identical to a
  fault-free run — recovery machinery (preempt-and-requeue, head-block,
  quarantine) never perturbs unaffected traffic.
* **No silent drops, no deadlocks**: every submitted request comes back
  with exactly one terminal ``status``; every injected fault is tallied
  in ``decode_stats["faults_injected"]`` and reconciles against the
  terminal counters; every run terminates (the no-progress watchdog
  bounds the worst case).

Plus unit coverage for each pillar alone: deadlines (queued and
in-flight), load shedding, never-admissible rejection under ``page_cap``,
NaN quarantine isolation, preemption-budget escalation, the watchdog,
the audit machinery's ability to actually catch corruption, and the
construction-time ``UnsupportedConfigError`` for mesh + compressed-MoE
deployments.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import Model
from repro.serve import (
    TERMINAL_STATUSES,
    AuditError,
    Engine,
    FaultInjector,
    FaultPlan,
    PagePool,
    Request,
    UnsupportedConfigError,
)


@pytest.fixture(scope="module")
def fm():
    # float32: reference runs ride different XLA graphs than faulted runs
    # only through prefill shapes (continuation re-prefills); bf16
    # jit noise could flip near-tied argmax across those shapes.
    cfg = get_config("qwen2.5-32b", "smoke", dtype="float32")
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    return cfg, m, params


def _workload(cfg, n=6, seed=11):
    rng = np.random.default_rng(seed)
    lengths = [int(rng.integers(3, 14)) for _ in range(n)]
    budgets = [int(rng.integers(3, 9)) for _ in range(n)]
    prompts = [rng.integers(0, cfg.vocab_size, size=L).astype(np.int32)
               for L in lengths]
    return prompts, budgets


def _run(m, params, prompts, budgets, *, req_kw=None, **kw):
    kw.setdefault("max_len", 16)
    kw.setdefault("max_new_tokens", 16)
    kw.setdefault("num_slots", 4)
    eng = Engine(m, params, **kw)
    reqs = []
    for rid, (p, b) in enumerate(zip(prompts, budgets)):
        r = Request(rid=rid, prompt=p, max_new_tokens=b,
                    **(req_kw[rid] if req_kw else {}))
        reqs.append(r)
        eng.submit(r)
    done = eng.run()
    return done, eng


# ---------------------------------------------------------------------------
# determinism of the injector itself
# ---------------------------------------------------------------------------


def test_injector_schedule_is_reproducible():
    plan = FaultPlan(seed=9, p_nan_logits=0.2, p_forced_preempt=0.3,
                     p_alloc_fail=0.25, p_stall=0.2, max_faults=12,
                     nan_at=((3, 1),), preempt_at=(5,), stall_at=((2, 7),))
    active = np.array([True, True, True, False])

    def trace():
        inj = FaultInjector(plan)
        out = []
        for step in range(20):
            ticks = inj.begin_step(step, 4, active)
            m = inj.nan_mask()
            out.append((ticks, None if m is None else m.tolist(),
                        inj.forced_preempt(),
                        [inj.alloc_fail() for _ in range(3)]))
        return out, dict(inj.counts)

    a, ca = trace()
    b, cb = trace()
    assert a == b and ca == cb, "seeded schedule must be bit-reproducible"
    assert sum(ca.values()) > 0, "plan was supposed to inject something"


def test_injector_scheduled_faults_fire_exactly():
    plan = FaultPlan(nan_at=((2, 0), (2, 3)), preempt_at=(4,),
                     alloc_fail_at=(1,), stall_at=((3, 9),))
    inj = FaultInjector(plan)
    active = np.ones(4, bool)
    for step in range(6):
        ticks = inj.begin_step(step, 4, active)
        if step == 2:
            np.testing.assert_array_equal(
                inj.nan_mask(), [True, False, False, True])
        else:
            assert inj.nan_mask() is None
        assert inj.forced_preempt() == (step == 4)
        assert inj.alloc_fail() == (step == 1)  # every call fails that step
        assert inj.alloc_fail() == (step == 1)
        assert ticks == (9 if step == 3 else 0)
    # nan_at is restricted to *active* slots
    inj = FaultInjector(plan)
    inj.begin_step(2, 4, np.array([True, False, False, False]))
    np.testing.assert_array_equal(
        inj.nan_mask(), [True, False, False, False])


def test_fault_plan_requires_known_type(fm):
    cfg, m, params = fm
    with pytest.raises(TypeError):
        Engine(m, params, faults={"p_nan_logits": 0.1})


# ---------------------------------------------------------------------------
# the tentpole property: chaos in, clean survivors + full accounting out
# ---------------------------------------------------------------------------


def test_chaos_survivors_bit_identical_and_accounted(fm):
    """Seeded chaos (alloc failures + forced preemptions + NaN logits +
    stalls) against a paged engine with audits on: the run terminates,
    every request lands in a terminal status, survivors' tokens are
    bit-identical to a fault-free run, failures reconcile against the
    injector's tally — and an identical second run replays identically."""
    cfg, m, params = fm
    prompts, budgets = _workload(cfg, n=6)
    clean, _ = _run(m, params, prompts, budgets, paged=True, page_size=16)
    assert all(r.status == "ok" for r in clean)
    ref = {r.rid: list(r.output) for r in clean}

    plan = FaultPlan(seed=5, p_alloc_fail=0.05, p_forced_preempt=0.2,
                     p_nan_logits=0.04, p_stall=0.1, max_faults=10)

    def chaos():
        done, eng = _run(m, params, prompts, budgets, paged=True,
                         page_size=16, faults=plan, audit=True)
        return done, eng.decode_stats

    done, st = chaos()
    assert sorted(r.rid for r in done) == list(range(len(prompts)))
    assert all(r.status in TERMINAL_STATUSES for r in done)
    inj = st["faults_injected"]
    assert sum(inj.values()) > 0, "plan injected nothing; weak test"
    # no deadline/shedding configured: only ok/failed are reachable, and
    # the only failure source is the NaN quarantine (preemption budget is
    # unbounded by default). A nan drawn for a slot that was preempted
    # later in the same iteration is a no-op, so <= not ==; the exact
    # one-injection-one-failure accounting is pinned by the scheduled
    # nan_at test below.
    assert st["completed_ok"] + st["failed"] == len(prompts)
    assert st["failed"] <= inj["nan_logits"]
    assert st["audit_violations"] == 0
    for r in done:
        if r.status == "ok":
            assert list(r.output) == ref[r.rid], \
                f"rid {r.rid} survived the chaos but its tokens changed"
        else:  # quarantined: kept the clean prefix it had already emitted
            assert list(r.output) == ref[r.rid][:len(r.output)]
            assert "non-finite" in r.status_reason
    # replay: a FaultPlan rebuilds a fresh injector per run, so the whole
    # recovery trace is deterministic across engines
    done2, st2 = chaos()
    assert {r.rid: (r.status, list(r.output)) for r in done2} \
        == {r.rid: (r.status, list(r.output)) for r in done}
    assert st2["faults_injected"] == inj
    assert st2["status_counts"] == st["status_counts"]


# ---------------------------------------------------------------------------
# deadlines (virtual clock)
# ---------------------------------------------------------------------------


def test_deadline_expires_queued_requests(fm):
    cfg, m, params = fm
    prompts, _ = _workload(cfg, n=4, seed=3)
    budgets = [12, 4, 4, 4]
    req_kw = [{}] + [{"ttl_steps": 3}] * 3
    done, eng = _run(m, params, prompts, budgets, num_slots=1,
                     req_kw=req_kw)
    by = {r.rid: r for r in done}
    assert by[0].status == "ok" and len(by[0].output) == 12
    for rid in (1, 2, 3):
        assert by[rid].status == "timed_out"
        assert "queue" in by[rid].status_reason
        assert by[rid].output == []
    assert eng.decode_stats["timed_out"] == 3


def test_deadline_expires_in_flight_requests(fm):
    cfg, m, params = fm
    prompts, _ = _workload(cfg, n=1, seed=4)
    done, eng = _run(m, params, prompts, [12], num_slots=2,
                     req_kw=[{"ttl_steps": 4}])
    (r,) = done
    assert r.status == "timed_out" and "in-flight" in r.status_reason
    assert 0 < len(r.output) < 12  # partial progress is kept
    assert eng.decode_stats["timed_out"] == 1


def test_engine_default_ttl_applies_when_request_has_none(fm):
    cfg, m, params = fm
    prompts, _ = _workload(cfg, n=1, seed=4)
    done, _ = _run(m, params, prompts, [12], num_slots=2,
                   default_ttl_steps=4)
    assert done[0].status == "timed_out"


def test_stall_faults_age_deadlines(fm):
    """An injected stall adds virtual-clock ticks, so a deadline that a
    clean run would meet expires under the stall — deterministically."""
    cfg, m, params = fm
    prompts, _ = _workload(cfg, n=1, seed=5)
    done, _ = _run(m, params, prompts, [6], req_kw=[{"ttl_steps": 10}])
    assert done[0].status == "ok"  # 6 tokens well inside 10 ticks
    done, eng = _run(m, params, prompts, [6], req_kw=[{"ttl_steps": 10}],
                     faults=FaultPlan(stall_at=((2, 50),)))
    assert done[0].status == "timed_out"
    assert eng.decode_stats["faults_injected"]["stall"] == 1


# ---------------------------------------------------------------------------
# load shedding + admission rejection
# ---------------------------------------------------------------------------


def test_load_shedding_bounds_the_pending_queue(fm):
    cfg, m, params = fm
    prompts, budgets = _workload(cfg, n=5, seed=6)
    done, eng = _run(m, params, prompts, budgets, max_pending=2)
    by = {r.rid: r for r in done}
    assert len(done) == 5, "shed requests must still be returned"
    # deterministic policy: the newest submits lose, FIFO keeps its order
    for rid in (0, 1):
        assert by[rid].status == "ok"
    for rid in (2, 3, 4):
        assert by[rid].status == "shed"
        assert "max_pending" in by[rid].status_reason
        assert by[rid].output == []
    assert eng.decode_stats["shed"] == 3
    assert eng.decode_stats["completed_ok"] == 2


def test_never_admissible_request_rejected_not_head_blocking(fm):
    """Under a hard page_cap, a prompt whose lane can never be allocated
    is refused at submit with status="rejected" instead of parking at the
    queue head and starving everything behind it (the old FIFO
    head-block)."""
    cfg, m, params = fm
    rng = np.random.default_rng(7)
    # cache_len = 32 + 16 = 48 -> 3 pages of 16; cap the pool at 2 pages
    # so any prompt needing a 3rd page is never admissible.
    eng = Engine(m, params, max_len=16, max_new_tokens=16, num_slots=2,
                 paged=True, page_size=16, page_cap=2)
    big = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=40).astype(np.int32), max_new_tokens=4)
    ok = Request(rid=1, prompt=rng.integers(
        0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=4)
    eng.submit(big)
    assert big.status == "rejected"  # decided at the door, pre-run
    assert "never admissible" in big.status_reason
    assert eng.scheduler.pending() == 0, "rejected request must not queue"
    eng.submit(ok)
    done = eng.run()
    by = {r.rid: r for r in done}
    assert by[1].status == "ok" and len(by[1].output) == 4
    assert by[0].status == "rejected"
    assert eng.decode_stats["rejected"] == 1


def test_oversized_prompt_still_raises_with_status_set(fm):
    """The scheduler's hard cache-capacity bound is a caller bug and still
    raises — but the request carries the rejection status for uniform
    accounting."""
    cfg, m, params = fm
    eng = Engine(m, params, max_len=16, num_slots=2)
    req = Request(rid=0, prompt=np.arange(
        eng.max_prompt_len + 1, dtype=np.int32))
    with pytest.raises(ValueError):
        eng.submit(req)
    assert req.status == "rejected" and req.status_reason


def test_page_cap_failure_mid_decode_fails_request_not_engine(fm):
    """A request that fits at admission but cannot grow its next decode
    page even with every other slot evicted (page_cap) is failed — the
    engine keeps running instead of raising."""
    cfg, m, params = fm
    rng = np.random.default_rng(8)
    # 12-token prompt fits in 1 page under cap=2, but budget 8 grows the
    # lane past position 16 (page 1) and then 32 (page 2 > cap).
    eng = Engine(m, params, max_len=16, max_new_tokens=32, num_slots=2,
                 paged=True, page_size=16, page_cap=2)
    eng.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=25))
    done = eng.run()
    (r,) = done
    assert r.status == "failed" and "page_cap" in r.status_reason
    assert 0 < len(r.output) < 25


# ---------------------------------------------------------------------------
# NaN quarantine
# ---------------------------------------------------------------------------


def test_nan_quarantine_isolates_one_slot(fm):
    cfg, m, params = fm
    prompts, budgets = _workload(cfg, n=4, seed=9)
    budgets = [8, 8, 8, 8]
    clean, _ = _run(m, params, prompts, budgets, paged=True, page_size=16)
    ref = {r.rid: list(r.output) for r in clean}
    done, eng = _run(m, params, prompts, budgets, paged=True, page_size=16,
                     faults=FaultPlan(nan_at=((1, 1),)), audit=True)
    st = eng.decode_stats
    assert st["faults_injected"]["nan_logits"] == 1
    assert st["failed"] == 1 and st["completed_ok"] == 3
    for r in done:
        if r.status == "failed":
            assert "non-finite" in r.status_reason
            # quarantined at iteration 1: prefill token + one decode step
            assert list(r.output) == ref[r.rid][:len(r.output)]
            assert len(r.output) < len(ref[r.rid])
        else:
            assert r.status == "ok"
            assert list(r.output) == ref[r.rid], \
                "NaN quarantine leaked into a healthy slot"


# ---------------------------------------------------------------------------
# preemption budget + watchdog
# ---------------------------------------------------------------------------


def test_preemption_budget_escalates_thrash_to_failed(fm):
    cfg, m, params = fm
    prompts, _ = _workload(cfg, n=2, seed=10)
    budgets = [8, 8]
    # rid 1 (always the youngest) tolerates one preempt-requeue cycle;
    # forced preemptions at iterations 1..4 burn through it, then fall on
    # rid 0 whose budget is unbounded (engine default) — it must finish
    # with the clean run's exact tokens despite being bounced twice.
    clean, _ = _run(m, params, prompts, budgets, paged=True, page_size=16)
    ref = {r.rid: list(r.output) for r in clean}
    done, eng = _run(m, params, prompts, budgets, paged=True, page_size=16,
                     faults=FaultPlan(preempt_at=(1, 2, 3, 4)),
                     req_kw=[{}, {"max_preemptions": 1}])
    by = {r.rid: r for r in done}
    assert by[1].status == "failed"
    assert "preemption budget" in by[1].status_reason
    assert by[0].status == "ok" and list(by[0].output) == ref[0]
    st = eng.decode_stats
    assert st["preemptions_recovered"] >= 2  # rid 1 once + rid 0's bounces
    assert st["preemptions"] > st["preemptions_recovered"]  # 1 escalation


def test_watchdog_fails_a_permanently_blocked_head(fm):
    """Every allocation attempt failing (injected) head-blocks the queue
    with zero active slots; the watchdog must fail the head after
    `watchdog_patience` idle iterations so run() terminates."""
    cfg, m, params = fm
    prompts, _ = _workload(cfg, n=1, seed=12)
    done, eng = _run(
        m, params, prompts, [4], paged=True, page_size=16,
        watchdog_patience=5,
        faults=FaultPlan(alloc_fail_at=tuple(range(200))))
    (r,) = done
    assert r.status == "failed" and "watchdog" in r.status_reason
    assert r.output == []
    # terminated promptly: patience + a couple of setup iterations
    assert eng.decode_stats["clock_ticks"] < 20


# ---------------------------------------------------------------------------
# audits: the checker must actually catch corruption
# ---------------------------------------------------------------------------


def test_audit_catches_refcount_corruption():
    pool = PagePool([32], num_slots=2, page_size=16)
    pool.alloc_prefix(0, 20)
    pool.check_invariants()  # clean pool passes
    c = pool.classes[32]
    c.refcount[int(c.table[0, 0])] += 1  # corrupt: phantom reference
    with pytest.raises(AuditError) as ei:
        pool.check_invariants()
    assert ei.value.check == "refcount-drift"
    assert "[audit:refcount-drift]" in str(ei.value)


def test_audit_catches_lane_bounds_violation():
    pool = PagePool([32], num_slots=2, page_size=16)
    pool.alloc_prefix(0, 20)
    pool.check_lane_bounds(0, 19)   # [0, 20) resident: fine
    pool.check_write_private(0, 19)
    c = pool.classes[32]
    c.table[0, 1] = c.FREE  # corrupt: drop the lane's second page
    with pytest.raises(AuditError):
        pool.check_lane_bounds(0, 19)


def test_audit_mode_is_transparent_on_a_healthy_run(fm):
    """audit=True must not change a single token — it only observes."""
    cfg, m, params = fm
    prompts, budgets = _workload(cfg, n=4, seed=13)
    plain, _ = _run(m, params, prompts, budgets, paged=True, page_size=16)
    audited, eng = _run(m, params, prompts, budgets, paged=True,
                        page_size=16, audit=True)
    assert {r.rid: list(r.output) for r in audited} \
        == {r.rid: list(r.output) for r in plain}
    assert eng.decode_stats["audit_violations"] == 0
    assert eng.audit


# ---------------------------------------------------------------------------
# unsupported deployments fail at construction, not mid-decode
# ---------------------------------------------------------------------------


class _StubMesh:
    """Duck-typed mesh: the construction checks read ``mesh.devices.size``
    (the compressed-MoE refusal keys on physical device count),
    ``mesh.axis_names`` (the tensor-parallel dispatch predicate) and the
    batch axes' sizes in ``mesh.shape`` (the chunk step's rows). No
    ``model`` axis, so ``tensor_parallel_size`` stays 1 and the sharded
    placement path is off — real multi-device meshes are exercised in
    subprocesses via the ``mesh_cpu`` fixture."""

    axis_names = ("data",)
    shape = {"data": 2}

    class devices:
        size = 2


def test_mesh_plus_compressed_moe_rejected_at_construction():
    from repro.core.factorized import FactorizationConfig, project_wd_leaves
    fcfg = FactorizationConfig(enabled=True, min_dim=32, rank=32, nnz=8)
    cfg = get_config("dbrx-132b", "smoke", dtype="float32",
                     factorization=fcfg)
    m = Model(cfg)
    params = project_wd_leaves(m.init(jax.random.key(0)), fcfg)
    mc, cparams, _ = m.compress_params(params)
    with pytest.raises(UnsupportedConfigError, match="wd_vq"):
        Engine(mc, cparams, max_len=16, num_slots=2, mesh=_StubMesh())
    # every neighbouring configuration stays constructible:
    Engine(mc, cparams, max_len=16, num_slots=2)            # no mesh
    Engine(m, params, max_len=16, num_slots=2, mesh=_StubMesh())  # dense


def test_moe_ffn_backstop_raises_for_callers_bypassing_engine():
    from repro.models.moe import moe_ffn
    p = {"w_up": {"wd_vq": None}}  # the raise fires before any other field
    with pytest.raises(UnsupportedConfigError):
        moe_ffn(p, None, cfg=None, dicts=None, mesh=_StubMesh())
