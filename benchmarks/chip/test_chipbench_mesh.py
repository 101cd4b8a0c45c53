"""The four-chip cell (``starcoder2-15b.tp4``, prepared in
``configs/starcoder2-15b.json`` and ``traffic/completion.json`` and not
yet in ``BENCHMARK.json``) rehearsed at smoke size on four virtual CPU
devices, through the same harness code the chip runs: the engine on a 1x4 mesh with
KV-head-sharded attention, the window and the comparison with the
reference. With the exchange between chips left out (the row-parallel
output and down projections keep each rank's partial sum), and with each
fault the one-chip rehearsal plants (a served token altered, the decode
step's state returned unchanged, half the batch left out), ``correct``
comes out false.

Each case runs in a child process, which asks the CPU backend for four
devices before JAX starts, on a copy of the benchmark whose
``BENCHMARK.json`` holds the cell."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

CHILD = r"""
import json, pathlib, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
assert len(jax.devices()) == 4, jax.devices()
import harness

fault = sys.argv[3]
if fault == "no_exchange":
    # The row-parallel linears (attention output, feed-forward down) each
    # keep their rank's partial product: no all-reduce joins them. (The
    # attention merge's psum alone is no exchange to leave out: each rank's
    # own heads are whole, and the output projection reads only those.)
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh
    import repro.models.layers as layers
    mesh = make_local_mesh(1, 4)
    linear = layers.apply_linear

    def apply_linear(p, x, dicts, family, *a, **kw):
        if not family.endswith(("_o", "_down")):
            return linear(p, x, dicts, family, *a, **kw)
        lead = (None,) * (x.ndim - 1)
        y = jax.shard_map(lambda x, w: x @ w, mesh=mesh, check_vma=False,
                          in_specs=(P(*lead, "model"), P("model", None)),
                          out_specs=P(*lead, None))(x, p["w"])
        return y + p["b"] if "b" in p else y

    layers.apply_linear = apply_linear

import test_chipbench_rehearsal as one_chip  # the faults planted in one chip
patch = {"alter_tokens": one_chip._alter_tokens,
         "state_unchanged": one_chip._state_unchanged,
         "half_batch_left_out": one_chip._half_batch_left_out}.get(fault)


config = {
    "program": {"arch": "starcoder2-15b", "variant": "smoke",
                "overrides": {"n_heads": 8, "n_kv_heads": 4,
                              "sliding_window": 64},
                "engine": {"page_size": 8}},
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 8,
    "head_dim": 16, "num_key_value_heads": 4, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 1e6, "sliding_window": 64,
    # Sound runs read about 0.06 at this size, the faults 1 and more.
    "check": {"logit_gap_limit": 0.2}}
traffic = {
    "prompt_tokens": {"dist": "loguniform", "low": 8, "high": 32},
    "output_tokens": {"dist": "loguniform", "low": 6, "high": 16},
    "engine": {"num_slots": 4, "max_len": 16, "max_prompt_len": 32,
               "max_new_tokens": 16},
    "check": {"tokens": 10**6, "max_requests": 10**6, "min_tokens": 16}}
result, lines = harness.run(
    "starcoder2-15b.tp4", 2**33 + 5, 1.5, False, require_tpu=False,
    root=pathlib.Path(sys.argv[4]), config_override=config,
    traffic_override=traffic, patch=patch)
print(json.dumps(result))
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark's root if its ``BENCHMARK.json`` holds the four-chip
    cell, else a copy whose ``BENCHMARK.json`` does."""
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    if any(w["name"] == "starcoder2-15b.tp4" for w in bench["workloads"]):
        return ROOT
    root = tmp_path_factory.mktemp("tp4")
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench["configs"].append({
        "name": "starcoder2-15b",
        "source": "https://huggingface.co/bigcode/starcoder2-15b",
        "file": "benchmarks/chip/configs/starcoder2-15b.json",
        "reduced": [], "why": "four-chip rehearsal"})
    bench["workloads"].append({
        "name": "starcoder2-15b.tp4", "config": "starcoder2-15b",
        "traffic": "completion", "chips": 4, "why": "four-chip rehearsal"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _child(root, fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run(
        [sys.executable, "-c", CHILD, str(HERE), str(ROOT / "src"), fault,
         str(root)], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_chip_rehearsal_is_correct_and_reports_no_metric(root):
    result = _child(root, "none")
    assert result["correct"], result["checks"]
    assert result["metrics"] == {}
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": 4, "memory_peak_bytes": 0}
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("fault", ["no_exchange", "alter_tokens",
                                   "state_unchanged", "half_batch_left_out"])
def test_a_broken_four_chip_step_is_not_correct(root, fault):
    result = _child(root, fault)
    assert not result["correct"]
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
