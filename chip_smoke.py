#!/usr/bin/env python3
"""On-chip smoke test of the serving engine's main path.

  python chip_smoke.py              # one TPU: phases "dense", "compressed"
  python chip_smoke.py --chips 4    # four TPUs: phase "sharded" only

Each phase builds a model at its published widths, its seeded random
parameters and its traffic through the launcher's builders
(``repro.launch.serve``), serves a dozen requests through ``Engine``, and
checks:

* every request ends with status ``ok``;
* the compiled mixed and decode steps hold the phase's Pallas kernels
  (``tpu_custom_call``): TDA, on one chip or per rank on four, plus DMM
  and SMM when the weights are compressed;
* teacher forcing: each request's prompt + output is re-run through
  ``Model.hidden`` on the jnp reference path (no paging, no kernels, and
  for compressed weights the explicitly decompressed factors), with the
  head applied at the generated positions only. At every generated
  position the reference logit of the engine's token must lie within
  ``LOGIT_TOL`` of the reference maximum.

Times printed here are set-up times (compilation included), not device
metrics. The last line of standard output is one JSON object naming the
device; it is printed only when every phase passed. Without a TPU the
script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.factorized import (FactorizationConfig,  # noqa: E402
                                   decompress_model_params,
                                   project_wd_leaves)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.launch.serve import (build_model, init_params,  # noqa: E402
                                make_requests)
from repro.models import layers as L  # noqa: E402
from repro.serve import Engine, EngineConfig  # noqa: E402

# Largest allowed gap between the reference maximum logit and the
# reference logit of the token the engine chose. Logits here have a
# standard deviation near 1; the engine and the reference differ in
# attention kernels and summation order over bf16 layers. Measured on TPU
# v5e, seeds 0 and 1: sound runs peaked at 0.040 and 0.035 (dense), 0.027
# and 0.015 (compressed), 0.070 and 0.072 (sharded over four chips, where
# the row-parallel psums add rounding). Planted faults in the dense phase
# peaked at 1.21 (weights rounded to int8 per output channel), 1.55 (one
# resident KV page zeroed mid-run) and 6.27 (a whole lane zeroed).
LOGIT_TOL = 0.1

KERNELS = ("tda_paged_decode_attention", "tda_mixed_attention",
           "tda_decode_attention", "dmm_matmul", "smm_matmul")


@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    arch: str
    engine: EngineConfig
    requests: int
    prompt_len: tuple       # (lowest, highest) prompt tokens
    max_new: tuple          # (lowest, highest) output budget
    shared_prefix: int      # tokens the first and last prompt share
    n_layers: int = None    # depth cut (None: as published)
    compressed: bool = False
    kernels: dict = dataclasses.field(default_factory=dict)


PHASES = {
    # qwen1.5-4b at full width and depth, bf16 weights (7.9 GB). cache_len
    # = max_prompt_len + max_new = 1024 tokens = 8 pages of 128; the page
    # pool (8 slots) holds 64 pages x 128 tokens x 409,600 B = 3.4 GB.
    # Prompts past max_len are chunked through the 512-wide mixed step.
    "dense": Phase(
        "dense", "qwen1.5-4b",
        EngineConfig(max_len=512, max_new_tokens=64, num_slots=8,
                     max_prompt_len=960),
        requests=12, prompt_len=(100, 960), max_new=(32, 64),
        shared_prefix=256,
        kernels={"mixed": ("tda_mixed_attention",),
                 "decode": ("tda_paged_decode_attention",)}),
    # The same model with factorized weights served from the compressed
    # streams. Depth cut to 8 of 40 layers: compression runs on the host
    # and takes about a second per layer.
    "compressed": Phase(
        "compressed", "qwen1.5-4b",
        EngineConfig(max_len=512, max_new_tokens=64, num_slots=8,
                     max_prompt_len=960),
        requests=12, prompt_len=(100, 960), max_new=(32, 64),
        shared_prefix=256, n_layers=8, compressed=True,
        kernels={"mixed": ("tda_mixed_attention", "dmm_matmul",
                           "smm_matmul"),
                 "decode": ("tda_paged_decode_attention", "dmm_matmul",
                            "smm_matmul")}),
    # starcoder2-15b at full width and depth (32 GB of bf16 weights) over
    # a 1x4 mesh: KV heads and weights split four ways. The TDA kernels
    # run once per rank on the rank's heads (no merge across ranks).
    "sharded": Phase(
        "sharded", "starcoder2-15b",
        EngineConfig(max_len=256, max_new_tokens=32, num_slots=8,
                     max_prompt_len=480),
        requests=8, prompt_len=(100, 480), max_new=(16, 32),
        shared_prefix=128,
        kernels={"mixed": ("tda_mixed_attention",),
                 "decode": ("tda_paged_decode_attention",)}),
}


def log(*a):
    print(*a, flush=True)


def kernel_calls(hlo: str) -> dict:
    """Pallas kernel calls in compiled HLO text, by kernel."""
    counts = dict.fromkeys(KERNELS, 0)
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            for k in KERNELS:
                if k in line:
                    counts[k] += 1
    return {k: n for k, n in counts.items() if n}


def peak_bytes(devices) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devices]


def teacher_forced_gaps(model, params, done, *, seq_len, max_new,
                        mesh=None):
    """Reference logit gap at every generated position: max logit minus
    the logit of the engine's token, from one forward of prompt + output
    (padded to ``seq_len``; causal, so padding never reaches earlier
    positions) with the head applied at the generated positions only."""
    cfg = model.cfg

    @jax.jit
    def gaps(params, toks, pos, chosen):
        h, _, _ = model.hidden(params, {"inputs": toks}, mesh=mesh)
        logits = L.lm_logits(params["lm_head"], params["embed"],
                             jnp.take(h[0], pos, axis=0), cfg)
        pick = jnp.take_along_axis(logits, chosen[:, None], axis=1)[:, 0]
        return logits.max(-1) - pick

    out = []
    for r in done:
        seq = np.concatenate([r.prompt, np.asarray(r.output, np.int32)])
        n = len(r.output)
        toks = np.zeros((1, seq_len), np.int32)
        toks[0, :len(seq)] = seq
        pos = np.full(max_new, len(r.prompt) - 1, np.int32)
        pos[:n] += np.arange(n, dtype=np.int32)
        chosen = np.zeros(max_new, np.int32)
        chosen[:n] = r.output
        out.append(np.asarray(gaps(params, toks, pos, chosen))[:n])
    return np.concatenate(out)


def run_phase(ph: Phase, seed: int = 0, mesh=None):
    """Serve one phase and check it; raises on any failed check."""
    t_start = time.perf_counter()
    log(f"== phase {ph.name}: {ph.arch}")
    overrides = {}
    if ph.n_layers:
        overrides["n_layers"] = ph.n_layers
    if ph.compressed:
        overrides["factorization"] = FactorizationConfig(enabled=True)
    model = build_model(ph.arch, **overrides)
    cfg = model.cfg
    log(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.kv_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} params={cfg.param_dtype} "
        f"compute={cfg.dtype}"
        + (f" | depth cut to {ph.n_layers} layers" if ph.n_layers else ""))

    t = time.perf_counter()
    params = init_params(model, seed, mesh)
    jax.block_until_ready(params)
    stats = {}
    ref_model, ref_params = model, params
    if ph.compressed:
        fcfg = cfg.factorization
        params = jax.jit(lambda p: project_wd_leaves(p, fcfg))(params)
        shapes = jax.eval_shape(lambda: params)
        tc = time.perf_counter()
        model, params, stats = model.compress_params(params)
        log(f"compressed on the host in {time.perf_counter() - tc:.1f} s "
            f"(set-up): rank {fcfg.rank_for(cfg.d_model)}, nnz "
            f"{fcfg.nnz_for(fcfg.rank_for(cfg.d_model))}/column, weight "
            f"stream {stats['weight_stream_bits'] / 8:.0f} B vs "
            f"{stats['weight_stream_bits_dense'] / 8:.0f} B dense-factorized")
        ref_params = jax.jit(
            lambda c: decompress_model_params(c, shapes))(params)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"param bytes {param_bytes} ({param_bytes / 1e9:.2f} GB); init "
        f"{time.perf_counter() - t:.1f} s (set-up, compile included)")
    devices = list(mesh.devices.flat) if mesh is not None \
        else [jax.devices()[0]]
    if mesh is not None:
        per_dev = dict.fromkeys(devices, 0)
        for x in jax.tree.leaves(params):
            for sh in x.addressable_shards:
                per_dev[sh.device] += sh.data.nbytes
        log(f"param bytes per device: {list(per_dev.values())}")

    ecfg = ph.engine
    if stats:
        ecfg = dataclasses.replace(
            ecfg, weight_stream_bits=stats["weight_stream_bits"])
    engine = Engine(model, params, config=ecfg, mesh=mesh)
    if engine.decode_attn != "tda":
        raise RuntimeError(f"decode_attn={engine.decode_attn}, want tda")
    pool_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.slots.caches))
    log(f"engine: decode_attn={engine.decode_attn} mixed={engine.mixed} "
        f"chunk width {engine._chunk_width} cache_len {engine.cache_len} "
        f"page_size {engine.page_size} slots {engine.num_slots} | KV pool "
        f"bytes {pool_bytes} ({pool_bytes / 1e9:.2f} GB)")

    rng = np.random.default_rng(seed + 1)
    lengths = rng.integers(ph.prompt_len[0], ph.prompt_len[1] + 1,
                           size=ph.requests)
    lengths[0] = lengths[-1] = max(lengths[0], lengths[-1],
                                   2 * ph.shared_prefix)
    budgets = rng.integers(ph.max_new[0], ph.max_new[1] + 1,
                           size=ph.requests)
    reqs = make_requests(lengths, cfg.vocab_size, max_new=budgets,
                         seed=seed, shared_prefix=ph.shared_prefix)
    log(f"requests: {len(reqs)}, prompt tokens {lengths.tolist()}, budgets "
        f"{budgets.tolist()}; requests 0 and {len(reqs) - 1} share "
        f"{ph.shared_prefix} prompt tokens")
    t = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    done = engine.run()
    ds = engine.decode_stats
    log(f"served in {time.perf_counter() - t:.1f} s (set-up, compile "
        f"included): {ds['steps']} steps ({ds['mixed_steps']} mixed), "
        f"{ds['decoded_tokens']} decoded tokens, prefix hit ratio "
        f"{ds.get('prefix_hit_ratio', 0.0):.3f}")
    statuses = {r.rid: r.status for r in done}
    log(f"request statuses: {statuses}")
    bad = {rid: s for rid, s in statuses.items() if s != "ok"}
    if bad or len(done) != len(reqs):
        raise RuntimeError(f"requests not ok: {bad} "
                           f"({len(done)} of {len(reqs)} returned)")

    calls = {step: kernel_calls(hlo)
             for step, hlo in engine.compiled_steps().items()}
    log(f"kernel calls in the compiled steps: {calls}")
    for step, want in ph.kernels.items():
        missing = [k for k in want if not calls.get(step, {}).get(k)]
        if missing:
            raise RuntimeError(f"{step} step lacks kernels {missing}")
    # The engine's jitted steps close over it, so free the page pool now
    # rather than when the garbage collector breaks that cycle.
    for x in jax.tree.leaves(engine.slots.caches):
        x.delete()
    del engine
    gc.collect()

    gaps = teacher_forced_gaps(
        ref_model, ref_params, done,
        seq_len=ecfg.max_prompt_len + ecfg.max_new_tokens,
        max_new=ecfg.max_new_tokens, mesh=mesh)
    worst = float(gaps.max())
    log(f"teacher-forced logit gap over {gaps.size} generated positions: "
        f"largest {worst:.4f}, mean {float(gaps.mean()):.4f} "
        f"(tolerance {LOGIT_TOL})")
    if not np.isfinite(worst) or worst > LOGIT_TOL:
        raise RuntimeError(f"teacher-forced gap {worst} > {LOGIT_TOL}")
    peaks = peak_bytes(devices)
    log(f"peak bytes in use per device since start: {peaks}")
    log(f"phase {ph.name} passed in {time.perf_counter() - t_start:.1f} s "
        "(set-up)")
    return peaks


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX backend "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPUs, found {len(devices)}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile cache "
        f"{enable_compile_cache()}")
    if args.chips == 4:
        peaks = run_phase(PHASES["sharded"], args.seed,
                          mesh=make_local_mesh(1, 4))
        if max(peaks) > 1.25 * min(peaks):
            raise RuntimeError(f"peak bytes unbalanced across devices: "
                               f"{peaks}")
    else:
        for name in ("dense", "compressed"):
            run_phase(PHASES[name], args.seed)
            gc.collect()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
