"""The benchmark's data files: BENCHMARK.json keeps to its contract, every
name it holds is a file, and a new configuration, traffic mix or metric is
found by its name with no existing file edited. CPU only."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"][0] == "python3"
    for word in bench["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert word.startswith("benchmarks/chip/")
        assert (ROOT / word).is_file()
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # A full check of 24 cells fits its time: 2 + 14 x cells runs, each
    # allowed run_seconds + 60, 2 x 90 s of compile a cell, 1200 spare.
    cells = 24
    total = ((2 + 14 * cells) * (bench["run_seconds"] + 60)
             + cells * 180 + 1200)
    assert total <= 43200
    assert len(json.dumps(bench)) <= 64 * 1024


def test_configs_and_cells(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert 1 <= len(configs) <= 24
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(configs)
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line_ok(c["why"])
        assert _line_ok(c["source"])
        assert c["file"].startswith("benchmarks/chip/")
        assert c["file"] not in files
        files.add(c["file"])
        with open(ROOT / c["file"]) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    assert len({w["name"] for w in cells}) == len(cells)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line_ok(w["why"])
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert e2e["setup_s"]["bound"] <= 0.25
    names = list(e2e) + [m["name"] for m in bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    reports = {c: {n for n, m in e2e.items()
                   if c in m.get("workloads", cells)} for c in cells}
    for c, got in reports.items():
        assert "setup_s" in got and len(got) >= 2, c
    layers_of = {}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and _line_ok(m["layer"])
        assert m["moves"] in e2e
        for c in m.get("workloads", cells):
            assert c in cells and m["moves"] in reports[c], (m["name"], c)
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        layers_of.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in cells:
        assert any(c in m.get("workloads", cells)
                   for m in bench["per_layer"]), c
    # Beside each kernel roofline, the whole step's share of the peak
    # moves the same metric in the same cells.
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            mfus = [x for x in bench["per_layer"] if "mfu" in x["name"]
                    and x["moves"] == m["moves"]]
            for c in m.get("workloads", cells):
                assert any(c in x.get("workloads", cells) for x in mfus)


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("TPU v99")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen1.5-4b.decode", "--seed", "3", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_run_without_tpu_fails_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_paths_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_new_config_mix_and_metric_are_files_and_entries(tmp_path):
    """A later PR adds a cell by adding files and entries: here a dummy
    configuration, traffic mix and per-layer metric in a copy of the
    benchmark, found by name with no existing file edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    with open(HERE / "configs" / "qwen1.5-4b.json") as f:
        cfg = json.load(f)
    cfg.update(name="dummy-model", num_hidden_layers=8,
               reduced=["num_hidden_layers"])
    (chip / "configs" / "dummy-model.json").write_text(json.dumps(cfg))
    mix = {"kind": "poisson", "rate_per_s": 1.0,
           "prompt_tokens": {"dist": "uniform", "low": 16, "high": 32},
           "output_tokens": {"dist": "uniform", "low": 4, "high": 8},
           "engine": {"num_slots": 2, "max_len": 32, "max_prompt_len": 32,
                      "max_new_tokens": 8},
           "check": {"tokens": 8, "max_requests": 1, "min_tokens": 4}}
    (chip / "traffic" / "dummy_mix.json").write_text(json.dumps(mix))
    (chip / "metrics" / "dummy_metric.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx else None\n")
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "dummy-model", "source": cfg["source"],
        "file": "benchmarks/chip/configs/dummy-model.json",
        "reduced": ["num_hidden_layers"], "why": "a test's dummy"})
    bench["workloads"].append({
        "name": "dummy-model.mix", "config": "dummy-model",
        "traffic": "dummy_mix", "chips": 1, "why": "a test's dummy"})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p90_s":
            m["workloads"].append("dummy-model.mix")
    bench["per_layer"].append({
        "name": "dummy_metric", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "itl_p90_s", "workloads": ["dummy-model.mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = harness.load_cell("dummy-model.mix", tmp_path)
    assert spec["config"]["num_hidden_layers"] == 8
    assert spec["traffic"]["rate_per_s"] == 1.0
    assert [m["name"] for m in spec["per_layer"]] == ["dummy_metric"]
    assert {m["name"] for m in spec["end_to_end"]} == {"itl_p90_s",
                                                       "setup_s"}
    assert harness.metric_reader("dummy_metric", tmp_path)(object()) == 42.0
    # The cells already there read as before.
    old = harness.load_cell("qwen1.5-4b.decode", tmp_path)
    assert old == harness.load_cell("qwen1.5-4b.decode", ROOT)
    after = {p: p.read_bytes() for p in before}
    assert after == before


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (HERE / "metrics").glob("*.py")))
def test_every_reader_reads_nothing_from_an_empty_run(name):
    """A reader with nothing to read returns nothing, never a 0 share."""
    import work
    ctx = harness.Context(
        shape=work.Shape.from_config(json.load(open(
            HERE / "configs" / "qwen1.5-4b.json"))),
        steps=[], counters=dict.fromkeys(
            ("steps", "mixed_steps", "chunk_tokens", "decoded_tokens",
             "active_slot_steps", "pages_used_steps", "preemptions"), 0),
        window_s=0.0, trace=None, peaks=harness.peaks_for("TPU v5 lite"),
        chips=1, num_slots=16, chunk_width=512, total_pages=128)
    assert harness.metric_reader(name)(ctx) is None
