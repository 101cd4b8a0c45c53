"""The benchmark's four-chip cell, ``starcoder2-15b.tp4``, rehearsed on four
virtual CPU devices through the harness the chip runs, with the engine on
the TDA path: the paged write, decode and mixed kernels run once per rank
(interpret mode) on each rank's KV heads, at a smoke size that splits as
the published model does (4 KV heads, 12 query heads each) and with its
block (biases on every linear, seeded nonzero; LayerNorm eps 1e-5). A
sound run is ``correct``; with the exchange between ranks left out (the
row-parallel output and down projections keep each rank's partial sum)
and with each fault the one-chip rehearsal plants, it is not.

The child script, the faults and the traffic are those of the dense-path
rehearsal, ``benchmarks/chip/test_chipbench_mesh.py``; this one changes
only the configuration it passes to ``harness.run``: 48 query heads of 8
and ``decode_attn="tda"``."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

import test_chipbench_mesh as dense  # noqa: E402

# Merged over the dense rehearsal's configuration (its "program" key by
# key). Sound runs read 0.01 to 0.06 at this size, the faults 1 and more.
TDA = {"program": {"overrides": {"n_heads": 48, "n_kv_heads": 4,
                                 "d_head": 8, "sliding_window": 64},
                   "engine": {"page_size": 8, "decode_attn": "tda"}},
       "num_attention_heads": 48, "head_dim": 8}

# Run before the dense child: ``harness.run`` takes the TDA configuration.
CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import harness
tda, run = json.loads(sys.argv[5]), harness.run


def run_tda(*a, config_override, **kw):
    program = {**config_override["program"], **tda["program"]}
    return run(*a, config_override={**config_override, **tda,
                                    "program": program}, **kw)


harness.run = run_tda
""" + dense.CHILD


def _child(fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run(
        [sys.executable, "-c", CHILD, str(BENCH), str(ROOT / "src"), fault,
         str(ROOT), json.dumps(TDA)], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_chip_tda_rehearsal_is_correct():
    result = _child("none")
    assert result["correct"], result["checks"]
    assert result["device"]["count"] == 4
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("fault", ["no_exchange", "alter_tokens",
                                   "state_unchanged", "half_batch_left_out"])
def test_a_broken_four_chip_tda_step_is_not_correct(fault):
    result = _child(fault)
    assert not result["correct"]
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
