"""HLO analyzer correctness + multi-device sharding machinery, run in
subprocesses so the main test session keeps exactly 1 device."""
import json
import subprocess
import sys
import textwrap

import pytest

SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.launch.hlo_analysis import analyze_hlo
{body}
"""


def _run(body: str) -> dict:
    code = SUB.format(body=textwrap.dedent(body))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_analyzer_matches_xla_on_loop_free():
    r = _run("""
        d = 128
        def f(x, w):
            return jnp.tanh(x @ w) @ w
        x = jax.ShapeDtypeStruct((64, d), jnp.float32)
        w = jax.ShapeDtypeStruct((d, d), jnp.float32)
        c = jax.jit(f).lower(x, w).compile()
        a = analyze_hlo(c.as_text())
        ca = c.cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax: one dict per device
            ca = ca[0]
        print(json.dumps({"flops": a.flops, "xla_flops": ca["flops"],
                          "bytes": a.bytes, "xla_bytes": ca["bytes accessed"]}))
    """)
    assert abs(r["flops"] - r["xla_flops"]) / r["xla_flops"] < 0.05
    assert abs(r["bytes"] - r["xla_bytes"]) / r["xla_bytes"] < 0.25


def test_analyzer_multiplies_scan_bodies():
    r = _run("""
        d, L = 128, 12
        def body(x, w):
            return jnp.tanh(x @ w), None
        def scanned(x, ws):
            return jax.lax.scan(body, x, ws)[0]
        def unrolled(x, ws):
            for i in range(L):
                x, _ = body(x, ws[i])
            return x
        x = jax.ShapeDtypeStruct((64, d), jnp.float32)
        ws = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
        cs = jax.jit(scanned).lower(x, ws).compile()
        cu = jax.jit(unrolled).lower(x, ws).compile()
        a, b = analyze_hlo(cs.as_text()), analyze_hlo(cu.as_text())
        print(json.dumps({"scan": a.flops, "unrolled": b.flops,
                          "warn": len(a.warnings)}))
    """)
    assert abs(r["scan"] - r["unrolled"]) / r["unrolled"] < 0.05
    assert r["warn"] == 0


def test_analyzer_collectives_and_pod_split():
    r = _run("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("pod", "model"))
        def f(x, w):
            return (x @ w).sum()
        xs = jax.ShapeDtypeStruct((16, 32), jnp.float32)
        ws = jax.ShapeDtypeStruct((32, 64), jnp.float32)
        jf = jax.jit(f, in_shardings=(NamedSharding(mesh, P("pod", None)),
                                      NamedSharding(mesh, P(None, "model"))),
                     out_shardings=NamedSharding(mesh, P()))
        c = jf.lower(xs, ws).compile()
        a = analyze_hlo(c.as_text(), devices_per_pod=4)
        print(json.dumps({"kinds": sorted(a.collective_bytes),
                          "ici": a.ici_bytes, "dci": a.dci_bytes}))
    """)
    assert "all-reduce" in r["kinds"]
    assert r["ici"] > 0 and r["dci"] > 0


def test_moe_shard_map_matches_local_oracle():
    """EP shard_map on a 4x2 mesh == local oracle (generous capacity)."""
    r = _run("""
        import dataclasses
        from repro.configs import get_config
        from repro.models import moe as M
        from repro.models.transformer import Model
        cfg = get_config("dbrx-132b", "smoke")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=4.0))
        key = jax.random.key(0)
        m = Model(cfg)
        params = m.init(key)
        lp = jax.tree.map(lambda x: x[0], params["layers"])  # layer 0
        x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
        y_local, aux_l = M.moe_ffn(lp["moe"], x, cfg=cfg, dicts=None,
                                   mesh=None)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        y_ep, aux_e = jax.jit(lambda p, xx: M.moe_ffn(
            p, xx, cfg=cfg, dicts=None, mesh=mesh))(lp["moe"], x)
        rel = float(jnp.abs(y_ep.astype(jnp.float32)
                            - y_local.astype(jnp.float32)).max()
                    / (jnp.abs(y_local.astype(jnp.float32)).max() + 1e-9))
        print(json.dumps({"rel": rel, "aux_l": float(aux_l),
                          "aux_e": float(aux_e)}))
    """)
    assert r["rel"] < 0.05, f"EP diverges from oracle: {r}"
    assert abs(r["aux_l"] - r["aux_e"]) < 0.2


def test_sharded_train_step_runs_and_matches_single_device():
    """One real sharded train step on an 8-device host mesh: loss finite and
    close to the unsharded loss on the same batch."""
    r = _run("""
        from repro.configs import get_config
        from repro.launch.mesh import make_local_mesh
        from repro.launch import sharding as shd
        from repro.launch.steps import build_bundle, make_train_step
        from repro.models.transformer import Model
        from repro.optim import OptConfig, init_opt_state
        cfg = get_config("qwen2.5-32b", "smoke")
        mesh = make_local_mesh(4, 2)
        m = Model(cfg)
        params = m.init(jax.random.key(0))
        opt_cfg = OptConfig(lr=1e-3, warmup_steps=1)
        state = {"params": params, "opt": init_opt_state(params, opt_cfg),
                 "step": jnp.zeros((), jnp.int32)}
        batch = {"inputs": jax.random.randint(jax.random.key(1), (8, 32), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.key(2), (8, 32), 0,
                                              cfg.vocab_size)}
        # single-device reference loss
        ref_loss = float(m.loss(params, batch)[0])
        pspecs = shd.param_specs(jax.eval_shape(lambda: params), mesh)
        psh = shd.named(pspecs, mesh)
        step = make_train_step(m, opt_cfg, mesh=mesh)
        with mesh:
            new_state, metrics = jax.jit(step)(state, batch)
        print(json.dumps({"loss": float(metrics["loss"]), "ref": ref_loss}))
    """)
    assert abs(r["loss"] - r["ref"]) / r["ref"] < 0.02


def test_tp4_serving_specs_match_the_per_rank_call():
    """starcoder2-15b's placement on the 1x4 serving mesh is what the
    per-rank TDA region (``models/layers.py``, ``per_rank``) expects: the
    stacked page pool is split on its KV-head axis (axis 2, one head a
    rank), q/k/v and the feed-forward up projection are column-parallel
    with their biases split alike, and the row-parallel output and down
    projections are split on their input rows with their biases
    replicated, so each is added once, after the all-reduce."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh
    from jax.sharding import PartitionSpec as P

    from repro.launch import sharding as shd
    from repro.launch.serve import build_model
    from repro.models.layers import _pool_head_axis

    mesh = AbstractMesh((1, 4), ("data", "model"))
    model = build_model("starcoder2-15b", "full")
    specs = shd.param_specs(jax.eval_shape(model.init, jax.random.key(0)),
                            mesh)
    layer = specs["layers"]
    for lin in ("wq", "wk", "wv"):
        assert layer["attn"][lin]["w"] == P(None, "data", "model")
        assert layer["attn"][lin]["b"] == P(None, "model")
    assert layer["ffn"]["w_up"]["w"] == P(None, "data", "model")
    assert layer["ffn"]["w_up"]["b"] == P(None, "model")
    assert layer["attn"]["wo"]["w"] == P(None, "model", "data")
    assert layer["ffn"]["w_down"]["w"] == P(None, "model", "data")
    assert layer["attn"]["wo"]["b"] == P(None, None)
    assert layer["ffn"]["w_down"]["b"] == P(None, None)
    pool = jax.ShapeDtypeStruct((40, 289, 4, 128, 128), jnp.bfloat16)
    got = shd.slot_cache_specs({"k": pool, "v": pool}, mesh, paged=True)
    head = _pool_head_axis(layer_idx=0)
    want = P(*[("model" if i == head else None) for i in range(5)])
    assert got == {"k": want, "v": want}
