"""The trace reduction, on hand-made intervals and on a small trace of the
``qwen1.5-4b.decode`` cell recorded on a TPU v5e (``record_fixture.py``):
busy union, idle share, time per operation and program, and idle gaps
named by the host span they fell in. The per-layer readers on that trace
yield no share above 100%. CPU only."""
import gzip
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import trace_reduce as T  # noqa: E402
import work  # noqa: E402

FIXTURE = HERE / "fixtures" / "decode_trace.json.gz"


def test_merge_busy_and_gaps():
    iv = np.array([[0, 10], [5, 12], [20, 30], [25, 26], [40, 41]])
    m = T.merge(iv)
    assert m.tolist() == [[0, 12], [20, 30], [40, 41]]
    assert T.busy_ns(m, 0, 50) == 23
    assert T.busy_ns(m, 6, 25) == 11
    assert T.idle_gaps(m, 0, 50).tolist() == [[12, 20], [30, 40], [41, 50]]
    assert T.idle_gaps(m, 5, 22).tolist() == [[12, 20]]
    assert T.merge(np.zeros((0, 2))).shape == (0, 2)


def test_span_names_pick_the_innermost():
    spans = [["bench.window", 0, 100], ["bench.step", 10, 30],
             ["bench.record", 40, 5], ["bench.step", 50, 40]]
    t = np.array([5, 15, 42, 60, 95, 200])
    assert T.span_names(t, spans) == [
        "bench.window", "bench.step", "bench.record", "bench.step",
        "bench.window", "outside the window"]


def test_op_facts_name_the_operation_not_its_operands():
    f = T.op_facts("%convert.229 = bf16[16,20,128] convert(f32[16,20,128] "
                   "%tda_paged_decode_attention.9)")
    assert (f["name"], f["kind"]) == ("convert.229", "convert")
    f = T.op_facts("%while.5 = (s32[]) while((s32[]) %tuple.64)")
    assert f["kind"] == "while"
    assert T.op_facts("%all-reduce-start.3 = f32[8] all-reduce-start("
                      "f32[8] %x)")["kind"] == "all-reduce-start"


def _synthetic():
    # Two chips, one window [0, 100]; a container op around two leaves.
    facts = [T.op_facts("%while.1 = () while()"),
             T.op_facts("%fusion.1 = f32[] fusion()"),
             T.op_facts("%tda_paged_decode_attention.2 = f32[] custom-call()"),
             T.op_facts("%all-reduce.3 = f32[] all-reduce()")]
    dev0 = {"ops": [[10, 50, 0], [10, 20, 1], [30, 30, 2], [70, 10, 3]],
            "facts": facts, "modules": [["jit_decode_fn(1)", 10, 70]]}
    dev1 = {"ops": [[10, 30, 1], [50, 10, 3]], "facts": facts,
            "modules": [["jit_decode_fn(1)", 10, 50]]}
    spans = [["bench.window", 0, 100], ["bench.step", 5, 80],
             ["bench.record", 85, 10]]
    return {"devices": {"0": dev0, "1": dev1}, "spans": spans}


def test_reduction_on_synthetic_ops():
    r = T.Reduced(_synthetic())
    assert r.window_s == pytest.approx(100e-9)
    # chip 0 busy 10..60 and 70..80 = 60; chip 1 10..40 and 50..60 = 40.
    assert r.busy_s() == pytest.approx(50e-9)
    assert r.idle_share() == pytest.approx(0.5)
    ops = r.op_seconds("0")
    assert "while.1" not in ops  # a container counts only through its body
    assert ops == pytest.approx({"fusion.1": 20e-9,
                                 "tda_paged_decode_attention": 30e-9,
                                 "all-reduce.3": 10e-9})
    assert r.kernel_calls("tda_paged_decode_attention")[1] == \
        pytest.approx(15e-9)  # 30 ns on chip 0, none on chip 1
    # collectives: chip 0 10 of 60 leaf ns, chip 1 10 of 40.
    assert r.collective_share() == pytest.approx((10 / 60 + 10 / 40) / 2)
    assert r.module_runs("decode_fn") == pytest.approx([70e-9, 50e-9])
    gaps = r.gap_seconds_by_span()
    assert sum(gaps.values()) == pytest.approx(50e-9)
    # chip 0 idle: 0..10 (step from 5: mid 5 -> step), 60..70 (step),
    # 80..100 (mid 90 -> record); chip 1: 0..10, 40..50, 60..100 (mid 80
    # -> step).
    assert gaps["bench.record"] == pytest.approx(10e-9)
    assert gaps["bench.step"] == pytest.approx(40e-9)
    b = r.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


@pytest.fixture(scope="module")
def fixture():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(fixture):
    return T.Reduced(fixture["trace"])


def test_fixture_busy_idle_and_gaps(fixture, reduced):
    r = reduced
    assert fixture["device_kind"] == "TPU v5 lite"
    assert r.devices == ["0"]
    assert 0 < r.busy_s() <= r.window_s
    assert r.idle_share() == pytest.approx(1 - r.busy_s() / r.window_s)
    assert r.window_s == pytest.approx(fixture["window_s"], rel=0.02)
    gaps = r.gap_seconds_by_span()
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s(),
                                               rel=1e-6)
    assert set(gaps) <= {"bench.window", "bench.step", "bench.submit",
                         "bench.record", "bench.wait"}
    # One TPU core runs one operation at a time: leaf operations' time
    # adds up to the busy time.
    total = sum(r.op_seconds("0").values())
    assert total == pytest.approx(r.busy_s(), rel=0.02)
    for name, _ in r.breakdown()["device_ops"]:
        assert " = " not in name


def test_fixture_programs_and_kernel_match_the_steps(fixture, reduced):
    kinds = [s["kind"] for s in fixture["steps"]]
    assert "mixed" in kinds and "decode" in kinds
    assert len(reduced.module_runs("decode_fn")) == kinds.count("decode")
    assert len(reduced.module_runs("mixed_fn")) == kinds.count("mixed")
    calls, secs = reduced.kernel_calls("tda_paged_decode_attention")
    layers = json.load(open(HERE / "configs" / "qwen1.5-4b.json"))[
        "num_hidden_layers"]
    assert calls == layers * kinds.count("decode") and secs > 0
    calls, _ = reduced.kernel_calls("tda_mixed_attention")
    assert calls == layers * kinds.count("mixed")


def test_no_share_passes_100_on_the_fixture(fixture, reduced):
    spec = harness.load_cell(fixture["cell"])
    ctx = harness.Context(
        shape=work.Shape.from_config(spec["config"]),
        steps=[harness.StepRec(s["kind"], s["rows"], s["emitted"])
               for s in fixture["steps"]],
        counters=fixture["counters"], window_s=fixture["window_s"],
        trace=reduced, peaks=harness.peaks_for(fixture["device_kind"]),
        chips=1, num_slots=fixture["num_slots"],
        chunk_width=fixture["chunk_width"],
        total_pages=fixture["total_pages"])
    got = {}
    for m in spec["per_layer"]:
        v = harness.metric_reader(m["name"])(ctx)
        assert v is not None, m["name"]
        got[m["name"]] = v
        if m["unit"] == "%":
            assert 0 < v <= 100, (m["name"], v)
    assert got["step_ms.mixed"] > got["step_ms.decode"] > 0


def test_read_xplane_finds_the_harness_spans(tmp_path):
    """A CPU trace holds no TPU plane, only the host spans."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    rec = T.read_xplane(T.find_xplane(str(tmp_path)))
    assert rec["devices"] == {}
    names = [s[0] for s in rec["spans"]]
    assert names.count("bench.window") == 1 and "bench.step" in names
    r = T.Reduced(rec)
    assert r.idle_share() is None and r.busy_s() == 0.0
