"""``work.py`` against hand counts at qwen1.5-4b and starcoder2-15b widths.
CPU only."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import work  # noqa: E402


def _shape(name):
    with open(HERE / "configs" / f"{name}.json") as f:
        return work.Shape.from_config(json.load(f))


def test_qwen_hand_counts():
    s = _shape("qwen1.5-4b")
    assert (s.layers, s.d_model, s.heads, s.kv_heads, s.head_dim) == \
        (40, 2560, 20, 20, 128)
    assert s.gated_ffn and s.window is None
    # q, k, v, o: 4 x 2560 x 2560; gate, up, down: 3 x 2560 x 6912.
    assert work.layer_matmul_params(s) == 4 * 2560 * 2560 + 3 * 2560 * 6912
    assert work.layer_matmul_params(s) * 40 == 3_171_942_400
    assert work.token_flops(s, 1) == 2 * 3_171_942_400
    # 409,600 B of keys and values per resident token over 40 layers.
    assert work.kv_token_bytes(s) * s.layers == 409_600
    assert work.head_flops(s, 3) == 3 * 2 * 2560 * 151936
    # one 16 x 512 mixed step of fresh tokens: 52 TFLOP in the layers.
    assert abs(work.token_flops(s, 16 * 512) - 52.0e12) < 0.05e12


def test_starcoder_hand_counts():
    s = _shape("starcoder2-15b")
    assert (s.heads, s.kv_heads, s.head_dim, s.window) == (48, 4, 128, 4096)
    assert not s.gated_ffn
    attn = 6144 * 128 * (2 * 48 + 2 * 4)
    assert work.layer_matmul_params(s) == attn + 2 * 6144 * 24576
    assert work.kv_token_bytes(s) == 2 * 4 * 128 * 2


@pytest.mark.parametrize("start,n,window,want", [
    (0, 4, None, 1 + 2 + 3 + 4),
    (5, 1, None, 6),
    (0, 0, None, 0),
    (0, 4, 3, 1 + 2 + 3 + 3),
    (10, 2, 3, 6),
    (1, 4, 4, 2 + 3 + 4 + 4),
])
def test_ctx_sum(start, n, window, want):
    s = work.Shape(1, 8, 1, 1, 8, 8, 8, False, window)
    assert work.ctx_sum(s, start, n) == want
    brute = sum(min(p + 1, window or 10**9) for p in range(start, start + n))
    assert brute == want


def test_step_flops_counts_only_real_work():
    s = _shape("qwen1.5-4b")
    rows = [(100, 1), (0, 512)]
    want = (work.token_flops(s, 513) + work.attn_flops(s, 101 + 512 * 513 // 2)
            + work.head_flops(s, 1))
    assert work.step_flops(s, rows, 1) == pytest.approx(want)


def test_roofline_names_its_bound():
    t, b = work.roofline_time(197e12, 1.0, 197e12, 819e9)
    assert (t, b) == (1.0, "compute")
    t, b = work.roofline_time(1.0, 819e9, 197e12, 819e9)
    assert (t, b) == (1.0, "memory")
