"""The reduction from a profiler trace to device metrics.

``read_xplane`` turns the profiler's ``.xplane.pb`` into a compact record:
for each TPU, its operations (start, duration, name) and its program runs
(the "XLA Modules" line), with a few facts per distinct operation name; and
the host spans the benchmark sets (``bench.*`` trace annotations). All
times are nanoseconds on the trace's one clock. ``Reduced`` computes from
that record, within the traced window:

* busy time: the union of the intervals in which an operation ran;
* idle share: 1 - busy / window, averaged over the chips used;
* time per operation and per program, and the share of collectives;
* idle gaps, each named by the innermost host span it fell in.

The tests check this module on a small trace recorded on a TPU v5e
(``fixtures/``).
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["read_xplane", "merge", "busy_ns", "idle_gaps", "span_names",
           "Reduced", "KERNELS", "COLLECTIVE", "op_facts"]

# Pallas kernels of the program, found by name in an operation's facts.
KERNELS = ("tda_paged_decode_attention", "tda_mixed_attention",
           "tda_decode_attention", "dmm_matmul", "smm_matmul")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|psum")
# Operations that only hold others (a layer loop's ``while``): their time
# is their body's, so they count toward busy time but not per operation.
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIX = "bench."


def op_facts(text: str) -> dict:
    """An operation's short name (``fusion.12``, ``all-reduce.3``), its
    kind (``fusion``) and the start of its HLO text. The trace names a TPU
    operation by its whole HLO instruction, operands included, so only
    the part before `` = `` names the operation itself."""
    short = text.split(" = ", 1)[0].strip().lstrip("%")
    m = re.match(r"[A-Za-z_-]+", short)
    kind = m.group(0).rstrip("-_") if m else short
    return {"name": short, "kind": kind, "text": text[:300]}


def read_xplane(path: str) -> dict:
    """Compact record of one trace file (see the module docstring)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[list] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            names: Dict[str, int] = {}
            facts: List[dict] = []
            ops: List[Tuple[int, int, int]] = []
            mods: List[list] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        i = names.get(ev.name)
                        if i is None:
                            i = names[ev.name] = len(facts)
                            facts.append(op_facts(ev.name))
                        ops.append((int(ev.start_ns), int(ev.duration_ns),
                                    i))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mods.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
            devices[m.group(1)] = {"ops": ops, "facts": facts,
                                   "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    return {"devices": devices, "spans": spans}


def merge(iv: np.ndarray) -> np.ndarray:
    """Union of ``(start, end)`` intervals as disjoint sorted intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2), np.int64)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def busy_ns(merged: np.ndarray, lo: int, hi: int) -> int:
    """Length of the merged intervals inside ``[lo, hi]``."""
    if len(merged) == 0:
        return 0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return int((e - s).sum())


def idle_gaps(merged: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """The complement of the merged intervals inside ``[lo, hi]``."""
    edges = [lo]
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        edges += [max(s, lo), min(e, hi)]
    edges.append(hi)
    g = np.asarray(edges, np.int64).reshape(-1, 2)
    return g[g[:, 1] > g[:, 0]]


def span_names(times: np.ndarray, spans: Sequence[list]) -> List[str]:
    """For each time, the innermost (shortest) host span covering it.
    Spans of one name never overlap each other (each is one call of the
    harness's loop), so each name is one sorted search."""
    best = np.full(len(times), "outside the window", dtype=object)
    best_len = np.full(len(times), np.inf)
    by_name = collections.defaultdict(list)
    for name, s, d in spans:
        by_name[name].append((s, d))
    for name, sd in by_name.items():
        sd = np.asarray(sorted(sd), np.int64).reshape(-1, 2)
        j = np.searchsorted(sd[:, 0], times, side="right") - 1
        ok = j >= 0
        jj = np.where(ok, j, 0)
        cover = ok & (times < sd[jj, 0] + sd[jj, 1])
        dur = np.where(cover, sd[jj, 1], np.inf)
        take = dur < best_len
        best[take] = name
        best_len[take] = dur[take]
    return list(best)


class Reduced:
    """Device metrics of one traced window ``[lo, hi]`` (ns). Without a
    window, the ``bench.window`` span sets it."""

    def __init__(self, rec: dict, window: Optional[Tuple[int, int]] = None):
        self.rec = rec
        spans = rec["spans"]
        if window is None:
            w = [s for s in spans if s[0] == "bench.window"]
            if not w:
                raise ValueError("trace holds no bench.window span")
            window = (w[0][1], w[0][1] + w[0][2])
        self.lo, self.hi = int(window[0]), int(window[1])
        self.spans = [s for s in spans if s[1] < self.hi
                      and s[1] + s[2] > self.lo]
        self.devices = sorted(rec["devices"], key=int)
        self._dev = {}
        for d in self.devices:
            r = rec["devices"][d]
            ops = np.asarray(r["ops"], np.int64).reshape(-1, 3)
            keep = (ops[:, 0] < self.hi) & (ops[:, 0] + ops[:, 1] > self.lo)
            ops = ops[keep]
            iv = np.stack([ops[:, 0], ops[:, 0] + ops[:, 1]], 1)
            mods = [m for m in r["modules"]
                    if m[1] < self.hi and m[1] + m[2] > self.lo]
            self._dev[d] = {"ops": ops, "merged": merge(iv),
                            "facts": r["facts"], "modules": mods}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the chips."""
        if not self.devices:
            return 0.0
        return float(np.mean([busy_ns(v["merged"], self.lo, self.hi)
                              for v in self._dev.values()])) * 1e-9

    def idle_share(self) -> Optional[float]:
        if not self.devices or self.hi <= self.lo:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def label(self, d: str, i: int) -> str:
        """An operation's label: the program kernel it runs, else its short
        name."""
        name = self._dev[d]["facts"][i]["name"]
        for k in KERNELS:
            if name == k or name.startswith(k + "."):
                return k
        return name

    def _ids(self, d: str, pred) -> List[int]:
        return [i for i, f in enumerate(self._dev[d]["facts"]) if pred(f)]

    def _leaf_ops(self, d: str) -> np.ndarray:
        """Operations that are not containers of others."""
        ops = self._dev[d]["ops"]
        box = self._ids(d, lambda f: f["kind"] in CONTAINERS)
        return ops[~np.isin(ops[:, 2], box)]

    def op_seconds(self, d: str) -> Dict[str, float]:
        """Seconds per operation label on chip ``d`` (clipped to the
        window)."""
        v = self._dev[d]
        ops = self._leaf_ops(d)
        dur = (np.minimum(ops[:, 0] + ops[:, 1], self.hi)
               - np.maximum(ops[:, 0], self.lo))
        per_id = np.bincount(ops[:, 2], weights=dur,
                             minlength=len(v["facts"]))
        out: Dict[str, float] = collections.defaultdict(float)
        for i, t in enumerate(per_id):
            if t > 0:
                out[self.label(d, i)] += t * 1e-9
        return dict(out)

    def mean_op_seconds(self) -> Dict[str, float]:
        """Seconds per operation label, averaged over the chips."""
        tot: Dict[str, float] = collections.defaultdict(float)
        for d in self.devices:
            for k, t in self.op_seconds(d).items():
                tot[k] += t / len(self.devices)
        return dict(tot)

    def kernel_calls(self, kernel: str) -> Tuple[int, float]:
        """Calls of one program kernel and their summed device seconds,
        averaged over the chips."""
        n = t = 0.0
        for d in self.devices:
            ops = self._dev[d]["ops"]
            ids = [i for i in range(len(self._dev[d]["facts"]))
                   if self.label(d, i) == kernel]
            sel = np.isin(ops[:, 2], ids)
            n += int(sel.sum())
            t += float(ops[sel, 1].sum()) * 1e-9
        k = max(len(self.devices), 1)
        return int(round(n / k)), t / k

    def collective_share(self) -> Optional[float]:
        """Share of device operation time spent in collectives, averaged
        over the chips; ``None`` where no collective ran."""
        shares, seen = [], False
        for d in self.devices:
            ops = self._leaf_ops(d)
            total = float(ops[:, 1].sum())
            if total <= 0:
                continue
            ids = self._ids(d, lambda f: COLLECTIVE.search(f["name"]))
            sel = np.isin(ops[:, 2], ids)
            seen |= bool(sel.any())
            shares.append(float(ops[sel, 1].sum()) / total)
        return float(np.mean(shares)) if shares and seen else None

    def module_runs(self, pattern: str) -> List[float]:
        """Durations in seconds of the runs of programs whose name
        contains ``pattern``, all chips pooled."""
        out = []
        for d in self.devices:
            for name, s, dur in self._dev[d]["modules"]:
                if pattern in name and s >= self.lo and s + dur <= self.hi:
                    out.append(dur * 1e-9)
        return out

    def gap_seconds_by_span(self) -> Dict[str, float]:
        """Idle seconds per innermost host span, averaged over chips."""
        tot: Dict[str, float] = collections.defaultdict(float)
        for d in self.devices:
            g = idle_gaps(self._dev[d]["merged"], self.lo, self.hi)
            names = span_names((g[:, 0] + g[:, 1]) // 2, self.spans)
            for name, (s, e) in zip(names, g):
                tot[name] += (e - s) * 1e-9 / len(self.devices)
        return dict(tot)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.mean_op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.gap_seconds_by_span().items(),
                      key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def find_xplane(root: str) -> str:
    import glob
    found = sorted(glob.glob(f"{root}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]

