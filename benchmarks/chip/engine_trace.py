"""Engine.step's host spans and the step programs' name scopes in a profiler
trace, read beside ``trace_reduce.read_xplane``'s record.

``read_xplane`` keeps the harness's ``bench.*`` spans and each device
operation's name. The program writes more on the same clock: host spans
named ``engine.*`` (``repro.serve.spans``) nested inside ``bench.step``,
and name scopes (``kv_write``, ``attn_kernel``) in the op metadata of the
two step programs. A TPU's "XLA Ops" events name an operation by its HLO
instruction and carry no metadata, so ``read_engine`` maps each operation
to its scope path through the compiled HLO text of the program whose run
("XLA Modules") it fell in (``Engine.compiled_steps()``): names like
``fusion.12`` repeat across programs. The readers below turn that into
host and device time per step phase and per scope; ``engine_phases.py``
runs them on a cell.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

import numpy as np

import trace_reduce
from repro.serve import spans

__all__ = ["read_engine", "hlo_op_names", "phase_gaps",
           "step_phase_ms", "host_ms", "scope_ms", "scope_of_ops",
           "queue_wait_ms", "record_rows"]

_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"')


def hlo_op_names(hlo: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata of a compiled HLO module's
    text (instruction names are unique within a module)."""
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def read_engine(path: str, programs: Optional[Dict[str, str]] = None
                ) -> dict:
    """From one trace file: the ``engine.*`` host spans as ``[name, start,
    duration]`` (ns), each admission's ``[start, rids]``, the stat names the
    TPU's operation events carry, and, given ``programs`` (``{kind: HLO
    text}``, kinds ``decode`` / ``mixed``), per TPU the scope path of each
    operation in ``read_xplane``'s order: ``{"paths": [...], "op_path":
    [index or -1]}``."""
    from jax.profiler import ProfileData

    names = {k: hlo_op_names(t) for k, t in (programs or {}).items()}
    pd = ProfileData.from_file(path)
    eng: List[list] = []
    admits: List[list] = []
    stat_names: set = set()
    scopes: Dict[str, dict] = {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops, runs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        if not stat_names:
                            stat_names.update(k for k, _ in ev.stats)
                        ops.append((int(ev.start_ns), ev.name))
                elif line.name == "XLA Modules":
                    runs.extend((int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns), ev.name)
                                for ev in line.events)
            scopes[m.group(1)] = _op_paths(ops, sorted(runs), names)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith("engine."):
                        continue
                    eng.append([ev.name, int(ev.start_ns),
                                int(ev.duration_ns)])
                    if ev.name == spans.ADMIT:
                        rids = dict(ev.stats).get("rids")
                        if rids is not None:
                            admits.append([int(ev.start_ns), str(rids)])
    return {"engine_spans": eng, "admits": admits,
            "op_stats": sorted(stat_names), "scopes": scopes}


def _op_paths(ops, runs, names) -> dict:
    paths: List[str] = []
    index: Dict[str, int] = {}
    op_path = []
    starts = np.asarray([r[0] for r in runs], np.int64)
    js = np.searchsorted(starts, [t for t, _ in ops], side="right") - 1
    kinds = [next((k for k in names if f"{k}_fn" in r[2]), None)
             for r in runs]
    short: Dict[str, str] = {}
    for (t, text), j in zip(ops, js):
        path = None
        if j >= 0 and t < runs[j][1] and kinds[j] is not None:
            if text not in short:
                short[text] = trace_reduce.op_facts(text)["name"]
            path = names[kinds[j]].get(short[text])
        if path is None:
            op_path.append(-1)
            continue
        if path not in index:
            index[path] = len(paths)
            paths.append(path)
        op_path.append(index[path])
    return {"paths": paths, "op_path": op_path}


def phase_gaps(rec: dict, extra: dict) -> Dict[str, float]:
    """Device idle seconds named by the innermost span, engine phases
    included (``bench.step`` keeps only what lies outside
    ``engine.step``)."""
    return trace_reduce.Reduced(
        {**rec, "spans": rec["spans"] + extra["engine_spans"]}
    ).gap_seconds_by_span()


def _steps_and_children(engine_spans):
    steps = sorted((s for s in engine_spans if s[0] == spans.STEP),
                   key=lambda s: s[1])
    starts = np.asarray([s[1] for s in steps], np.int64)
    child: List[Dict[str, float]] = [dict() for _ in steps]
    for name, s, d in engine_spans:
        if name == spans.STEP:
            continue
        j = int(np.searchsorted(starts, s, side="right")) - 1
        if j >= 0 and s + d <= steps[j][1] + steps[j][2]:
            child[j][name] = child[j].get(name, 0) + d
    return steps, child


def step_phase_ms(engine_spans, kinds: Sequence[Optional[str]],
                  kind: str = "decode") -> Dict[str, float]:
    """Mean milliseconds per step of ``kind`` in each phase and in the
    whole ``engine.step``; ``kinds`` are the steps' kinds in order."""
    steps, child = _steps_and_children(engine_spans)
    if len(steps) != len(kinds):
        raise ValueError(f"{len(steps)} engine.step spans for "
                         f"{len(kinds)} step records")
    pick = [i for i, k in enumerate(kinds) if k == kind]
    if not pick:
        return {}
    out = {spans.STEP: float(np.mean([steps[i][2] for i in pick])) * 1e-6}
    for p in spans.PHASES:
        out[p] = float(np.mean([child[i].get(p, 0) for i in pick])) * 1e-6
    return out


def host_ms(engine_spans, kinds: Sequence[Optional[str]],
            kind: str = "decode") -> Optional[float]:
    """Mean host milliseconds of a step of ``kind``: ``engine.step`` less
    its ``engine.sync`` child, the wait for the device."""
    ph = step_phase_ms(engine_spans, kinds, kind)
    if not ph:
        return None
    return ph[spans.STEP] - ph[spans.SYNC]


def _scoped(reduced, extra, d, scope):
    sc = extra["scopes"].get(d)
    if sc is None or len(sc["op_path"]) != len(reduced.rec["devices"][d]
                                               ["ops"]):
        return None
    hit = np.asarray([f"/{scope}/" in p for p in sc["paths"]] + [False])
    return hit[np.asarray(sc["op_path"], np.int64)]


def scope_ms(reduced, extra, scope: str, program: str) -> Optional[float]:
    """Mean device milliseconds per run of ``program`` (``decode_fn``,
    ``mixed_fn``) of the leaf operations under name scope ``scope``,
    averaged over the chips."""
    per_chip = []
    for d in reduced.devices:
        hit = _scoped(reduced, extra, d, scope)
        runs = [m for m in reduced._dev[d]["modules"] if program in m[0]]
        if hit is None or not runs:
            continue
        ops = np.asarray(reduced.rec["devices"][d]["ops"],
                         np.int64).reshape(-1, 3)
        rs = np.asarray(sorted(m[1:] for m in runs), np.int64)
        j = np.searchsorted(rs[:, 0], ops[:, 0], side="right") - 1
        inside = (j >= 0) & (ops[:, 0] < (rs[:, 0] + rs[:, 1])[j])
        box = [i for i, f in enumerate(reduced.rec["devices"][d]["facts"])
               if f["kind"] in trace_reduce.CONTAINERS]
        leaf = ~np.isin(ops[:, 2], box)
        sel = inside & leaf & hit
        per_chip.append(float(ops[sel, 1].sum()) * 1e-6 / len(runs))
    return float(np.mean(per_chip)) if per_chip else None


def scope_of_ops(reduced, extra, top: int = 12) -> List[list]:
    """The longest operations (by label, summed over the window) on the
    first chip with their scope paths: ``[label, seconds, [paths]]``."""
    if not reduced.devices:
        return []
    d = reduced.devices[0]
    sc = extra["scopes"].get(d)
    if sc is None:
        return []
    ops = np.asarray(reduced.rec["devices"][d]["ops"], np.int64).reshape(-1, 3)
    facts = reduced.rec["devices"][d]["facts"]
    tot: Dict[str, float] = {}
    where: Dict[str, set] = {}
    for (_s, dur, i), p in zip(ops, sc["op_path"]):
        if facts[i]["kind"] in trace_reduce.CONTAINERS:
            continue
        lab = reduced.label(d, int(i))
        tot[lab] = tot.get(lab, 0.0) + dur * 1e-9
        where.setdefault(lab, set()).add(sc["paths"][p] if p >= 0 else "")
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v, sorted(where[k])] for k, v in best]


def queue_wait_ms(stamps: Sequence[Sequence[Optional[float]]], t0: float,
                  t_end: float, q: float = 50) -> Optional[float]:
    """The q-th percentile, in milliseconds, of admission less submission
    (``Request.admitted_s - submitted_s``) over the requests submitted in
    ``[t0, t_end)``; one never admitted enters at its age at ``t_end``.
    ``stamps`` are ``(submitted_s, admitted_s)`` pairs."""
    waits = [((a if a is not None else t_end) - s) * 1e3
             for s, a in stamps if s is not None and t0 <= s < t_end]
    return float(np.percentile(waits, q)) if waits else None


def record_rows(results, kind: str = "mixed") -> List[tuple]:
    """``(start, n)`` of every row with fresh tokens in the step records
    (``StepResult``-like: ``kind``, ``starts``, ``n_new``) of ``kind``."""
    return [(int(r["starts"][s]), int(r["n_new"][s])) for r in results
            if r["kind"] == kind for s in range(len(r["n_new"]))
            if r["n_new"][s] > 0]
