"""Load generators: a closed loop of clients and an open loop of arrivals.

Both draw from one fixed set of request sizes per traffic file: the sizes
are the quantiles of the file's length distributions at evenly spaced
probabilities, put in a blocked order in which every run of a few
consecutive requests spans the distribution. The seed draws the prompt
tokens (uniform over the vocabulary, so no two prompts share a prefix).
In the closed loop it also shuffles the sizes within each run, so runs
with different seeds differ by that order and not by the amount of work.
The open loop's window holds only some 18 arrivals, too few for any order
to stand for the others: there the sizes and the gaps between arrivals
(the quantiles of an exponential distribution at the file's rate) come in
one blocked order for every seed, and seeds differ only in their tokens.

A generator hands out ``Spec`` records; the harness turns them into the
program's requests. Times are seconds from the start of the window.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np

__all__ = ["Spec", "ClosedLoop", "OpenLoop", "make_generator",
           "quantile_lengths"]


@dataclasses.dataclass
class Spec:
    """One request to send: its prompt tokens, output budget and due time
    (``None`` until a closed-loop client frees up)."""
    index: int
    prompt: np.ndarray
    max_new: int
    due: Optional[float] = None


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the evenly spaced quantiles ``(i + 0.5) / n`` of the
    distribution ``dist``: ``{"dist": "loguniform" | "uniform", "low",
    "high"}``, bounds inclusive."""
    lo, hi = int(dist["low"]), int(dist["high"])
    if not 1 <= lo <= hi:
        raise ValueError(f"bad length bounds {lo}..{hi}")
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
    elif kind == "uniform":
        x = lo + u * (hi + 1 - lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def blocked_order(n: int, block: int, rng: np.random.Generator
                  ) -> np.ndarray:
    """An order of ``n`` sorted items in which every run of ``block``
    consecutive items holds one item of each of ``block`` strata (so each
    run spans the distribution), the runs in a fixed order and the seed's
    ``rng`` shuffling only within a run. Any seed therefore puts the same
    sizes into the first run, and into each later one."""
    block = max(1, min(block, n))
    runs = n // block
    order = np.arange(runs * block).reshape(block, runs).T  # run j: j::runs
    order = order[np.random.default_rng(0x5EED).permutation(runs)]
    order = np.stack([rng.permutation(r) for r in order]).reshape(-1)
    return np.concatenate([order, np.arange(runs * block, n)])


class _Sizes:
    """The fixed set of ``n`` request sizes in a blocked order (see
    ``blocked_order``) that the seed shuffles, or with ``shuffle=False``
    one order for every seed, repeated as often as needed; prompt tokens
    are drawn from the seed as each request is made."""

    def __init__(self, traffic: dict, n: int, block: int, seed: int,
                 vocab: int, shuffle: bool = True):
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
        order = rng if shuffle else np.random.default_rng(0x0DE5)
        self.prompts = quantile_lengths(traffic["prompt_tokens"], n)[
            blocked_order(n, block, order)]
        self.outs = quantile_lengths(traffic["output_tokens"], n)[
            blocked_order(n, block, order)]
        self.rng = rng
        self.vocab = vocab
        self.made = 0

    def make(self, due: Optional[float]) -> Spec:
        i = self.made
        self.made += 1
        j = i % len(self.prompts)
        toks = self.rng.integers(0, self.vocab, size=int(self.prompts[j]),
                                 dtype=np.int32)
        return Spec(i, toks, int(self.outs[j]), due)


class ClosedLoop:
    """``clients`` clients, each sending its next request the moment its
    previous one ends. All clients send their first request at time 0.
    Sizes come from a set of ``requests`` in the seed's order, repeated
    if a window outruns it."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.clients = int(traffic["clients"])
        self.sizes = _Sizes(traffic, int(traffic["requests"]), self.clients,
                            seed, vocab)

    def start(self) -> List[Spec]:
        return [self.sizes.make(0.0) for _ in range(self.clients)]

    def due(self, now: float) -> List[Spec]:
        return []

    def completed(self, now: float, n: int) -> List[Spec]:
        """``n`` requests ended at ``now``: their clients send again."""
        return [self.sizes.make(now) for _ in range(n)]

    def next_due(self) -> Optional[float]:
        return None


class OpenLoop:
    """Arrivals at ``rate_per_s``, gaps drawn as exponential quantiles (a
    Poisson process with its randomness confined to a fixed order), sent
    whether or not earlier requests have ended. Runs of as many requests
    as the engine has slots span the size distribution. Sizes and arrival
    times are the same for every seed; the seed draws the tokens."""

    def __init__(self, traffic: dict, seed: int, vocab: int,
                 seconds: float):
        rate = float(traffic["rate_per_s"])
        if rate <= 0:
            raise ValueError("rate_per_s must be positive")
        # Arrivals cover the window with room to spare; the gap set is
        # fixed by the count, so every seed sends the same gaps.
        block = int(traffic["engine"]["num_slots"])
        n = int(math.ceil(rate * seconds * 1.25 / block) + 1) * block
        self.sizes = _Sizes(traffic, n, block, seed, vocab, shuffle=False)
        u = (np.arange(n) + 0.5) / n
        gaps = (-np.log1p(-u) / rate)[
            blocked_order(n, block, np.random.default_rng(0xA441))]
        self.dues = np.cumsum(gaps)

    def start(self) -> List[Spec]:
        return []

    def due(self, now: float) -> List[Spec]:
        out = []
        while (self.sizes.made < len(self.dues)
               and self.dues[self.sizes.made] <= now):
            out.append(self.sizes.make(float(self.dues[self.sizes.made])))
        return out

    def completed(self, now: float, n: int) -> List[Spec]:
        return []

    def next_due(self) -> Optional[float]:
        if self.sizes.made < len(self.dues):
            return float(self.dues[self.sizes.made])
        return None


def make_generator(traffic: dict, seed: int, vocab: int, seconds: float):
    kind = traffic["kind"]
    if kind == "closed":
        return ClosedLoop(traffic, seed, vocab)
    if kind == "poisson":
        return OpenLoop(traffic, seed, vocab, seconds)
    raise ValueError(f"unknown traffic kind {kind!r}")
