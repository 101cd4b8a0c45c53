"""A CPU rehearsal of one cell's whole run at smoke size, through the same
harness code the chip runs: the window, the result line and the comparison
with the reference. Off the TPU the run reports no metric. With the timed
path broken underneath, ``correct`` comes out false, once for each fault a
served cell on one chip can have."""
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402

SMOKE_CONFIG = {
    "program": {"arch": "qwen1.5-4b", "variant": "smoke",
                "overrides": {"rope_theta": 5e6},
                "engine": {"page_size": 8}},
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256}
SMOKE_TRAFFIC = {
    "requests": 64,
    "rate_per_s": 20.0,  # the open loop's, at smoke size
    "prompt_tokens": {"dist": "loguniform", "low": 8, "high": 40},
    "output_tokens": {"dist": "loguniform", "low": 6, "high": 16},
    "engine": {"num_slots": 4, "max_len": 16, "max_prompt_len": 40,
               "max_new_tokens": 16},
    # Check every finished request, so a fault in any row shows.
    "check": {"tokens": 10**6, "max_requests": 10**6, "min_tokens": 16}}
SEED = 2**32 + 77


def _run(patch=None, seconds=1.5, cell="qwen1.5-4b.decode", control=False):
    return harness.run(cell, SEED, seconds, False, require_tpu=False,
                       config_override=SMOKE_CONFIG,
                       traffic_override=SMOKE_TRAFFIC, patch=patch,
                       control=control)


def _wrap_decode(engine, fn):
    inner = engine._decode

    def decode(*args):
        return fn(inner, args)

    engine._decode = decode


def _alter_tokens(engine):
    import jax.numpy as jnp

    def fn(inner, args):
        nxt, caches = inner(*args)
        return jnp.where(nxt >= 0, (nxt + 1) % 256, nxt), caches

    _wrap_decode(engine, fn)


def _state_unchanged(engine):
    def fn(inner, args):
        nxt, _caches = inner(*args)
        return nxt, args[2]

    _wrap_decode(engine, fn)


def _half_batch_left_out(engine):
    import jax.numpy as jnp

    def fn(inner, args):
        args = list(args)
        active = args[4]
        half = jnp.arange(active.shape[0]) < active.shape[0] // 2
        args[4] = active & half
        return inner(*args)

    _wrap_decode(engine, fn)


@pytest.mark.parametrize("cell", ["qwen1.5-4b.decode", "qwen1.5-4b.prefill"])
def test_rehearsal_is_correct_and_reports_no_metric(cell):
    result, lines = _run(cell=cell)
    assert result["correct"], lines
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    gap = result["checks"]["max_logit_gap"]
    assert 0 <= gap["value"] < gap["limit"]
    assert any(line.startswith("check max_logit_gap") for line in lines)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _half_batch_left_out])
def test_a_broken_step_is_not_correct(fault):
    result, lines = _run(patch=fault)
    assert not result["correct"], lines
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_rounding_is_float8_e4m3():
    """The control's rounding, written without a float8 conversion (which
    a TPU elides), agrees with one on the CPU."""
    import jax.numpy as jnp
    import reference

    rng = np.random.default_rng(0)
    x = (rng.standard_normal((64, 300))
         * np.exp(rng.standard_normal((64, 1)))).astype(np.float32)
    scale = np.abs(x).max(-1, keepdims=True) / 448.0
    want = np.asarray(jnp.asarray(x / scale).astype(jnp.float8_e4m3fn)
                      .astype(jnp.float32)) * scale
    got = np.asarray(reference.fp8_round(jnp.asarray(x), -1))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.abs(got - x).max() > 0


def test_control_reads_far_above_the_sound_gap():
    """The control (the reference computed in float8 e4m3, put in the
    program's place) through the harness's own comparison: on what one
    seed's window served, the sound run is correct and the control is not,
    its widest gap above the limit and the limit above the sound gap. At
    this size sound runs read 0.026-0.049 and the control 0.60-1.16 over
    nine seeds; the chip readings, at the cells' own size, are in
    PERF.md."""
    sound, _ = _run()
    ctl, lines = _run(control=True)
    assert sound["correct"] and not ctl["correct"], lines
    s, c = sound["checks"]["max_logit_gap"], ctl["checks"]["max_logit_gap"]
    assert s["limit"] == c["limit"]
    assert s["value"] < c["limit"] < c["value"]
    assert c["value"] > 3 * s["value"]
    assert ctl["checks"]["checked_tokens"]["value"] >= 256
