"""The engine's spans and scopes read beside the trace reduction
(``engine_trace.py``), on hand-made records and on a smoke engine traced on
the CPU; the mixed kernel's roofline reader; and the existing readers'
values on the first fixture, pinned. CPU only."""
import gzip
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import engine_trace as E  # noqa: E402
import harness  # noqa: E402
import trace_reduce as T  # noqa: E402
import work  # noqa: E402
from repro.serve import spans  # noqa: E402
from test_chipbench_rehearsal import SMOKE_CONFIG, SMOKE_TRAFFIC  # noqa: E402

FIXTURE = HERE / "fixtures" / "decode_trace.json.gz"

HLO = {
    "decode": """HloModule jit_decode_fn, entry_computation_layout={}
  %copy.5 = f32[4]{0} copy(f32[4]{0} %p), metadata={op_name="jit(decode_fn)/while/body/kv_write/dynamic_slice" stack_frame_id=2}
  ROOT %fusion.1 = f32[4]{0} fusion(f32[4]{0} %copy.5), kind=kLoop, calls=%fc, metadata={op_name="jit(decode_fn)/while/body/attn_kernel/dot_general"}
  %bitcast.2 = f32[4]{0} bitcast(f32[4]{0} %p)
""",
    "mixed": """HloModule jit_mixed_fn
  %copy.5 = f32[4]{0} copy(f32[4]{0} %p), metadata={op_name="jit(mixed_fn)/while/body/attn_kernel/dynamic_slice"}
""",
}


def test_hlo_op_names_reads_the_metadata():
    names = E.hlo_op_names(HLO["decode"])
    assert names == {
        "copy.5": "jit(decode_fn)/while/body/kv_write/dynamic_slice",
        "fusion.1": "jit(decode_fn)/while/body/attn_kernel/dot_general"}


def test_op_paths_follow_the_program_they_ran_in():
    """``copy.5`` is a KV write in the decode program and an attention
    input in the mixed one: the run it falls in decides."""
    names = {k: E.hlo_op_names(t) for k, t in HLO.items()}
    runs = [(0, 100, "jit_decode_fn(1)"), (200, 300, "jit_mixed_fn(2)")]
    ops = [(10, "%copy.5 = f32[4] copy(%p)"), (20, "%fusion.1 = f32[4] fusion()"),
           (30, "%bitcast.2 = f32[4] bitcast()"),
           (210, "%copy.5 = f32[4] copy(%p)"), (150, "%copy.5 = f32[4] copy()")]
    got = E._op_paths(ops, runs, names)
    paths = [got["paths"][i] if i >= 0 else None for i in got["op_path"]]
    assert paths == [names["decode"]["copy.5"], names["decode"]["fusion.1"],
                     None, names["mixed"]["copy.5"], None]


def _engine_spans():
    # Two steps: a decode step (sync 6 of 10) and a mixed step (sync 30
    # of 40), and an admission outside any step.
    return [[spans.STEP, 100, 10], [spans.PACK, 101, 1],
            [spans.DISPATCH, 102, 1], [spans.SYNC, 103, 6],
            [spans.RETIRE, 109, 1],
            [spans.STEP, 200, 40], [spans.ADMIT, 201, 4],
            [spans.SYNC, 205, 30], [spans.ADMIT, 500, 2]]


def test_step_phases_and_host_time():
    ph = E.step_phase_ms(_engine_spans(), ["decode", "mixed"], "decode")
    assert ph[spans.STEP] == pytest.approx(10e-6)
    assert ph[spans.SYNC] == pytest.approx(6e-6)
    assert ph[spans.ADMIT] == 0
    assert E.host_ms(_engine_spans(), ["decode", "mixed"]) == \
        pytest.approx(4e-6)
    assert E.host_ms(_engine_spans(), ["decode", "mixed"], "mixed") == \
        pytest.approx(10e-6)
    assert E.host_ms(_engine_spans(), ["mixed", "mixed"]) is None
    with pytest.raises(ValueError):
        E.step_phase_ms(_engine_spans(), ["decode"])


def test_phase_gaps_name_idle_time_by_engine_phase():
    facts = [T.op_facts("%fusion.1 = f32[] fusion()")]
    rec = {"devices": {"0": {"ops": [[0, 100, 0], [104, 5, 0],
                                     [235, 300, 0]],
                             "facts": facts, "modules": []}},
           "spans": [["bench.window", 0, 600], ["bench.step", 99, 12],
                     ["bench.step", 199, 42]]}
    extra = {"engine_spans": _engine_spans()}
    gaps = E.phase_gaps(rec, extra)
    # idle 100..104 (mid 102: dispatch), 109..235 (mid 172) and 535..600
    # (window).
    assert gaps == pytest.approx({spans.DISPATCH: 4e-9,
                                  "bench.window": 191e-9})
    assert sum(gaps.values()) == pytest.approx(
        T.Reduced(rec).window_s - T.Reduced(rec).busy_s())


def test_scope_ms_counts_leaf_ops_under_the_scope_per_run():
    facts = [T.op_facts("%while.1 = () while()"),
             T.op_facts("%copy.5 = f32[4] copy(%p)"),
             T.op_facts("%fusion.1 = f32[4] fusion()")]
    ops = [[0, 90, 0], [10, 20, 1], [40, 30, 2], [110, 40, 1],
           [200, 50, 1]]
    rec = {"devices": {"0": {"ops": ops, "facts": facts, "modules": [
        ["jit_decode_fn(1)", 0, 100], ["jit_decode_fn(1)", 100, 100],
        ["jit_mixed_fn(2)", 200, 100]]}},
        "spans": [["bench.window", 0, 300]]}
    kv = "jit(decode_fn)/while/body/kv_write/dynamic_slice"
    extra = {"scopes": {"0": {"paths": [kv, "jit(x)/attn_kernel/dot"],
                              "op_path": [-1, 0, 1, 0, 0]}}}
    r = T.Reduced(rec)
    # decode runs: kv_write ops 20 + 40 ns over two runs; the mixed run's
    # op is outside the decode program.
    assert E.scope_ms(r, extra, "kv_write", "decode_fn") == \
        pytest.approx(30e-6)
    assert E.scope_ms(r, extra, "attn_kernel", "decode_fn") == \
        pytest.approx(15e-6)
    assert E.scope_ms(r, extra, "kv_write", "mixed_fn") == \
        pytest.approx(50e-6)
    assert E.scope_ms(r, {"scopes": {}}, "kv_write", "decode_fn") is None
    top = E.scope_of_ops(r, extra)
    assert top[0] == ["copy.5", pytest.approx(110e-9), [kv]]


def test_queue_wait_and_record_rows():
    stamps = [(1.0, 1.5), (2.0, 2.1), (3.0, None), (0.5, 0.9), (9.0, 9.1)]
    # in [1, 4): waits 500, 100 and (4 - 3) s for the one never admitted.
    assert E.queue_wait_ms(stamps, 1.0, 4.0) == pytest.approx(500.0)
    assert E.queue_wait_ms(stamps, 1.0, 4.0, q=0) == pytest.approx(100.0)
    assert E.queue_wait_ms([], 0, 1) is None
    res = [{"kind": "mixed", "starts": [0, 7, 3], "n_new": [5, 1, 0]},
           {"kind": "decode", "starts": [5, 8, 0], "n_new": [1, 1, 0]},
           {"kind": None, "starts": None, "n_new": None}]
    assert E.record_rows(res) == [(0, 5), (7, 1)]
    assert E.record_rows(res, "decode") == [(5, 1), (8, 1)]


def _context(steps, trace, peaks=None):
    spec = harness.load_cell("qwen1.5-4b.prefill")
    return harness.Context(
        shape=work.Shape.from_config(spec["config"]), steps=steps,
        counters={}, window_s=1.0, trace=trace,
        peaks=peaks or harness.peaks_for("TPU v5 lite"), chips=1,
        num_slots=8, chunk_width=512, total_pages=128)


class _Kernel:
    def __init__(self, calls, secs):
        self.calls, self.secs = calls, secs

    def kernel_calls(self, name):
        assert name == "tda_mixed_attention"
        return self.calls, self.secs


def test_mixed_roofline_by_hand():
    read = harness.metric_reader("tda_mixed_attention_roofline")
    steps = [harness.StepRec("mixed", [(2048, 512), (1000, 1)], 1),
             harness.StepRec("decode", [(1001, 1)], 1)]
    ctx = _context(steps, _Kernel(40, 0.05))
    # 40 layers x 20 heads x 128 dims; keys: the chunk's 512 tokens over
    # 2048 resident ones and causally among themselves, the decode row's
    # one token over 1001.
    keys = 512 * 2048 + 512 * 513 // 2 + 1001
    flops = 4.0 * 40 * 20 * 128 * keys
    nbytes = (2048 + 512 + 1001) * 2 * 20 * 128 * 2 * 40
    least = max(flops / 197e12, nbytes / 819e9)
    assert read(ctx) == pytest.approx(100.0 * least / 0.05)
    assert flops / 197e12 > nbytes / 819e9  # compute sets it here
    assert read(_context(steps, None)) is None
    assert read(_context(steps, _Kernel(0, 0.0))) is None
    assert read(_context(steps[1:], _Kernel(40, 0.05))) is None


@pytest.fixture(scope="module")
def fixture():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def _fixture_context(fx):
    spec = harness.load_cell(fx["cell"])
    return harness.Context(
        shape=work.Shape.from_config(spec["config"]),
        steps=[harness.StepRec(s["kind"], s["rows"], s["emitted"])
               for s in fx["steps"]],
        counters=fx["counters"], window_s=fx["window_s"],
        trace=T.Reduced(fx["trace"]),
        peaks=harness.peaks_for(fx["device_kind"]), chips=1,
        num_slots=fx["num_slots"], chunk_width=fx["chunk_width"],
        total_pages=fx["total_pages"])


# The accepted readers' values on the first fixture, as they read it before
# the engine carried spans and scopes: nothing here may move them.
FIRST_FIXTURE = {
    "slot_occupancy": 100.0,
    "kv_pool_use": 26.674107142857142,
    "step_ms.decode": 75.329481,
    "step_ms.mixed": 722.0432920000001,
    "mfu": 9.559190494916914,
    "tda_paged_decode_attention_roofline": 18.13907245690226,
    "idle_share": 3.1510650940889895,
}


@pytest.mark.parametrize("name", sorted(FIRST_FIXTURE))
def test_accepted_readers_unchanged_on_the_first_fixture(fixture, name):
    ctx = _fixture_context(fixture)
    assert harness.metric_reader(name)(ctx) == FIRST_FIXTURE[name]


def test_mixed_roofline_on_the_first_fixture(fixture):
    """The fixture's one mixed step is the first admission: 16 chunk rows
    from position 0."""
    v = harness.metric_reader("tda_mixed_attention_roofline")(
        _fixture_context(fixture))
    assert 0 < v <= 100


@pytest.fixture(scope="module")
def smoke_window():
    """A smoke engine through the harness's window, traced on the CPU."""
    import engine_phases

    spec = harness.load_cell("qwen1.5-4b.decode")
    config = {**spec["config"], **SMOKE_CONFIG}
    traffic = {**spec["traffic"], **SMOKE_TRAFFIC}
    devs, _ = harness.start(1, require_tpu=False)
    model, _params, engine, _ = harness.build(config, traffic, 5, 1, devs)
    vocab = model.cfg.vocab_size
    harness.warm_up(engine, traffic, 5, vocab)
    programs = engine.compiled_steps()
    slots = []
    step = engine.step

    def step_with_slots(*a, **kw):
        before = harness._slots(engine)
        res = step(*a, **kw)
        slots.append((before, harness._slots(engine)))
        return res

    engine.step = step_with_slots
    win, results, tmp = engine_phases.traced_window(
        engine, traffic, 5, 1.0, vocab)
    rec, extra = engine_phases.reduce_trace(tmp, programs)
    assert not pathlib.Path(tmp).exists()
    summary = engine_phases.summary(
        {**spec, "config": config}, win, rec, extra, results,
        harness.peaks_for("TPU v5 lite"), engine)
    return win, rec, extra, results, slots, programs, summary


def test_engine_spans_nest_inside_bench_step_in_phase_order(smoke_window):
    win, rec, extra, results, _, programs, _ = smoke_window
    assert rec["devices"] == {} and extra["scopes"] == {}
    steps = sorted((s for s in extra["engine_spans"] if s[0] == spans.STEP),
                   key=lambda s: s[1])
    bench = sorted((s for s in rec["spans"] if s[0] == "bench.step"),
                   key=lambda s: s[1])
    assert len(steps) == len(bench) == len(win["steps"]) == len(results)
    for (_, s, d), (_, bs, bd) in zip(steps, bench):
        assert bs <= s and s + d <= bs + bd
    phases = sorted((s for s in extra["engine_spans"] if s[0] != spans.STEP),
                    key=lambda s: s[1])
    for _, s, d in steps:
        inside = [spans.PHASES.index(p[0]) for p in phases
                  if s <= p[1] and p[1] + p[2] <= s + d]
        assert inside == sorted(inside)
    assert len(phases) == sum(
        1 for p in phases
        if any(s <= p[1] and p[1] + p[2] <= s + d for _, s, d in steps))
    admitted = {int(r) for _, rids in extra["admits"]
                for r in rids.split()}
    assert admitted == {rid for rid, r in win["recs"].items()
                        if r.request.admitted_s is not None}
    kinds = [r["kind"] for r in results]
    assert E.host_ms(extra["engine_spans"], kinds) > 0
    # The programs the trace would attribute: both carry the scopes.
    for kind in ("decode", "mixed"):
        paths = E.hlo_op_names(programs[kind]).values()
        assert any("/kv_write/" in p for p in paths)


def test_step_records_match_the_harness_rows(smoke_window):
    """``StepResult.starts`` / ``n_new`` give the rows the harness rebuilds
    from the slot table, on every step where nothing finished."""
    win, _rec, _extra, results, slots, _, _ = smoke_window
    checked = 0
    for res, step, (before, after) in zip(results, win["steps"], slots):
        rows = [(s, n) for s, n in zip(res["starts"] or [],
                                       res["n_new"] or []) if n > 0]
        if res["kind"] is None:
            assert step.rows == []
            continue
        finished = [s for s in range(len(before[0]))
                    if before[0][s] and not after[0][s]]
        if finished:
            continue
        assert rows == harness._rows(before, after, set())
        assert rows == step.rows
        checked += 1
    assert checked >= 5


def test_phase_summary_on_the_cpu(smoke_window):
    """``engine_phases.summary`` reads the smoke window through the same
    readers: host time from the spans, queue wait from the stamps, and no
    device figure where the CPU trace holds no TPU."""
    win, _rec, extra, results, _, _, out = smoke_window
    kinds = [r["kind"] for r in results]
    assert out["steps"]["decode"] == kinds.count("decode") > 0
    assert out["host_ms.decode"] == E.host_ms(extra["engine_spans"], kinds)
    assert out["phase_ms.decode"][spans.STEP] >= out["host_ms.decode"] > 0
    assert out["queue_wait_p50_ms"] >= 0
    assert out["kv_write_ms.decode"] is None
    assert out["tda_mixed_attention_roofline"] is None


def test_request_stamps_are_ordered(smoke_window):
    win = smoke_window[0]
    done = 0
    for r in win["recs"].values():
        q = r.request
        assert q.submitted_s is not None
        if q.admitted_s is not None:
            assert q.submitted_s <= q.admitted_s
        if q.first_token_s is not None:
            assert q.admitted_s <= q.first_token_s
        if q.finished_s is not None:
            assert q.first_token_s <= q.finished_s
            done += 1
    assert done >= 1
    waits = [(r.request.submitted_s, r.request.admitted_s)
             for r in win["recs"].values()]
    assert E.queue_wait_ms(waits, win["t0"], win["t_end"]) >= 0


# ---------------------------------------------------------------------------
# the second fixture: the decode cell's first 1.2 s on a TPU v5e, with the
# engine's spans, the operations' scope paths, the step records and the
# request stamps (engine_phases.py --fixture)
# ---------------------------------------------------------------------------

ENGINE_FIXTURE = HERE / "fixtures" / "decode_trace_engine.json.gz"
# The whole-layer moves of the page pool in the decode program: each
# layer's slice, its relayout copy and the update back (PERF.md §5).
POOL_MOVES = {"constant_dynamic-slice_fusion.12",
              "constant_dynamic-slice_fusion.13", "copy.187", "copy.190",
              "copy_dynamic-update-slice_fusion.4",
              "copy_dynamic-update-slice_fusion.5"}


@pytest.fixture(scope="module")
def engine_fixture():
    with gzip.open(ENGINE_FIXTURE, "rt") as f:
        return json.load(f)


def test_engine_fixture_spans_nest_and_split_the_step_gaps(engine_fixture):
    fx = engine_fixture
    assert fx["device_kind"] == "TPU v5 lite"
    ex = fx["engine"]
    steps = sorted((s for s in ex["engine_spans"] if s[0] == spans.STEP),
                   key=lambda s: s[1])
    bench = sorted((s for s in fx["trace"]["spans"] if s[0] == "bench.step"),
                   key=lambda s: s[1])
    assert len(steps) == len(bench) == len(fx["results"]) == len(fx["steps"])
    for (_, s, d), (_, bs, bd) in zip(steps, bench):
        assert bs <= s and s + d <= bs + bd
    for _, s, d in steps:
        inside = [spans.PHASES.index(p[0]) for p in ex["engine_spans"]
                  if p[0] != spans.STEP and s <= p[1]
                  and p[1] + p[2] <= s + d]
        assert inside == sorted(inside) and len(inside) >= 7
    # What bench.step held is now named by phase; every other gap keeps
    # its name and size.
    before = T.Reduced(fx["trace"]).gap_seconds_by_span()
    after = E.phase_gaps(fx["trace"], ex)
    split = sum(v for k, v in after.items()
                if k == "bench.step" or k.startswith("engine."))
    assert split == pytest.approx(before["bench.step"], rel=1e-9)
    assert any(k.startswith("engine.") and v > 0 for k, v in after.items())
    assert {k: v for k, v in after.items() if k.startswith("bench.")
            and k != "bench.step"} == pytest.approx(
        {k: v for k, v in before.items() if k != "bench.step"})
    assert ex["admits"][0][1].split() == [str(i) for i in range(16)]


def test_engine_fixture_records_give_the_harness_rows(engine_fixture):
    fx = engine_fixture
    for res, step in zip(fx["results"], fx["steps"]):
        assert res["kind"] == step["kind"]
        assert [list(r) for r in E.record_rows([res], res["kind"])] == \
            step["rows"]
        assert res["pages_in_use"] > 0
    # The mixed kernel's share from the records equals the reader's over
    # the harness's rows.
    ctx = _fixture_context(fx)
    via_steps = harness.metric_reader("tda_mixed_attention_roofline")(ctx)
    ctx.steps = [harness.StepRec(r["kind"], E.record_rows([r], r["kind"]),
                                 0) for r in fx["results"]]
    assert harness.metric_reader("tda_mixed_attention_roofline")(ctx) == \
        pytest.approx(via_steps, rel=1e-12)
    assert 0 < via_steps <= 100


def test_engine_fixture_charges_the_pool_copies_to_kv_write(engine_fixture):
    """The decode step's device time under ``kv_write`` is the pool's
    whole-layer moves plus the small scatter, and is most of the step."""
    fx = engine_fixture
    r = T.Reduced(fx["trace"])
    ex = fx["engine"]
    kv = E.scope_ms(r, ex, "kv_write", "decode_fn")
    step = 1e3 * float(np.mean(r.module_runs("decode_fn")))
    dev = fx["trace"]["devices"]["0"]
    ops = np.asarray(dev["ops"], np.int64).reshape(-1, 3)
    runs = [m for m in dev["modules"] if "decode_fn" in m[0]]
    moves = 0
    for _name, s, d in runs:
        sel = (ops[:, 0] >= s) & (ops[:, 0] < s + d)
        moves += sum(int(du) for _st, du, i in ops[sel]
                     if dev["facts"][i]["name"] in POOL_MOVES)
    moves_ms = moves * 1e-6 / len(runs)
    assert 0.95 * kv <= moves_ms <= kv
    assert 0.5 * step < kv < step
    # Each of those operations is under kv_write in the decode program.
    names = {row[0]: row[2] for row in E.scope_of_ops(r, ex, top=40)}
    for name in POOL_MOVES & set(names):
        assert any("decode_fn" in p and "/kv_write/" in p
                   for p in names[name]), name
    assert E.scope_ms(r, ex, "attn_kernel", "decode_fn") > 0


def test_engine_fixture_host_time_and_queue_wait(engine_fixture):
    fx = engine_fixture
    ex = fx["engine"]
    kinds = [r["kind"] for r in fx["results"]]
    ph = E.step_phase_ms(ex["engine_spans"], kinds)
    host = E.host_ms(ex["engine_spans"], kinds)
    assert 0 < host < ph[spans.STEP]
    assert host == pytest.approx(sum(ph[p] for p in spans.PHASES
                                     if p != spans.SYNC), rel=0.05)
    stamps = list(fx["requests"].values())
    for sub, adm, first, _fin in stamps:
        assert sub <= adm <= first
    wait = E.queue_wait_ms([(s[0], s[1]) for s in stamps], fx["t0"],
                           fx["t_end"])
    assert 0 <= wait < ph[spans.STEP]
