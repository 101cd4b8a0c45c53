"""The load generators: deterministic per seed, every seed offering the same
sizes and gaps (in another order in the closed loop, in one order in the
open loop), and the realized lengths and rate those of the traffic files.
CPU only."""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import loadgen  # noqa: E402

MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))
BIG_SEED = 2**33 + 12345


def _mix(name):
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _take(gen, n):
    out = gen.start()
    t = 0.0
    while len(out) < n:
        t += 0.05
        out += gen.due(t) + gen.completed(t, 1)
    return out[:n]


def _loguniform_mean(lo, hi):
    return (hi + 1 - lo) / np.log((hi + 1) / lo)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    tr = _mix(mix)
    a = _take(loadgen.make_generator(tr, BIG_SEED, 1000, 400.0), 40)
    b = _take(loadgen.make_generator(tr, BIG_SEED, 1000, 400.0), 40)
    assert [(s.max_new, s.due) for s in a] == [(s.max_new, s.due) for s in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = _take(loadgen.make_generator(tr, BIG_SEED + 2**32, 1000, 400.0), 40)
    assert not np.array_equal(a[0].prompt[:8], c[0].prompt[:8])
    same_order = [len(s.prompt) for s in a] == [len(s.prompt) for s in c]
    assert same_order == (tr["kind"] == "poisson")


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_match_the_file(mix):
    tr = _mix(mix)
    for key in ("prompt_tokens", "output_tokens"):
        d = tr[key]
        q = loadgen.quantile_lengths(d, 4096)
        assert q.min() >= d["low"] and q.max() <= d["high"]
        if d["dist"] == "loguniform":
            want = _loguniform_mean(d["low"], d["high"]) - 0.5
            assert abs(q.mean() - want) / want < 0.01
        # Every seed draws the same multiset of sizes.
    n = int(tr.get("requests", 64))
    g1 = loadgen.make_generator(tr, 1, 1000, 400.0)
    g2 = loadgen.make_generator(tr, 2, 1000, 400.0)
    assert sorted(g1.sizes.prompts) == sorted(g2.sizes.prompts)
    assert sorted(g1.sizes.outs) == sorted(g2.sizes.outs)
    assert len(g1.sizes.prompts) >= min(n, 8)
    for s in _take(g1, 30):
        assert tr["prompt_tokens"]["low"] <= len(s.prompt) \
            <= tr["prompt_tokens"]["high"]
        assert tr["engine"]["max_prompt_len"] >= len(s.prompt)
        assert tr["engine"]["max_new_tokens"] >= s.max_new


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_open_loop_rate(seed):
    tr = {"kind": "poisson", "rate_per_s": 2.5,
          "prompt_tokens": {"dist": "loguniform", "low": 1024, "high": 4096},
          "output_tokens": {"dist": "loguniform", "low": 16, "high": 128},
          "engine": {"num_slots": 8}}
    seconds = 40.0
    gen = loadgen.make_generator(tr, seed, 1000, seconds)
    due = gen.due(seconds)
    # The gap set is the exponential's quantiles: the realized rate is the
    # file's within the last gap's share.
    assert abs(len(due) / seconds - 2.5) / 2.5 < 0.08
    gaps = np.diff([0.0] + list(gen.dues))
    assert abs(gaps.mean() - 1 / 2.5) / (1 / 2.5) < 0.02
    other = loadgen.make_generator(tr, seed + 1, 1000, seconds)
    assert np.allclose(sorted(gaps), sorted(np.diff([0.0] + list(
        other.dues))))
    assert all(a.due <= b.due for a, b in zip(due, due[1:]))


def test_closed_loop_sends_on_completion():
    tr = _mix("decode")
    gen = loadgen.make_generator(tr, 3, 1000, 10.0)
    first = gen.start()
    assert len(first) == tr["clients"] and all(s.due == 0.0 for s in first)
    assert gen.due(5.0) == [] and gen.next_due() is None
    nxt = gen.completed(1.25, 3)
    assert [s.due for s in nxt] == [1.25] * 3
    assert [s.index for s in nxt] == [16, 17, 18]


def test_unknown_kind_and_bad_bounds():
    with pytest.raises(ValueError):
        loadgen.make_generator({"kind": "bursty"}, 0, 10, 1.0)
    with pytest.raises(ValueError):
        loadgen.quantile_lengths({"dist": "uniform", "low": 9, "high": 3}, 4)
