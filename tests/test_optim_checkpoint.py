"""Optimizer + checkpoint + train-loop fault tolerance."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import (latest_step, restore_checkpoint,
                              save_checkpoint, wait_pending)
from repro.optim import OptConfig, apply_updates, init_opt_state, lr_at


def _quadratic_params(key):
    return {"a": jax.random.normal(key, (8, 8)), "b": jnp.ones((8,))}


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_minimizes_quadratic(name):
    cfg = OptConfig(name=name, lr=0.1, warmup_steps=1, weight_decay=0.0,
                    schedule="constant", factored_min_dim=4)
    params = _quadratic_params(jax.random.key(0))
    state = init_opt_state(params, cfg)

    def loss_fn(p):
        return jnp.sum(p["a"] ** 2) + jnp.sum((p["b"] - 3.0) ** 2)

    l0 = float(loss_fn(params))
    for i in range(60):
        grads = jax.grad(loss_fn)(params)
        params, state, stats = apply_updates(params, grads, state,
                                             jnp.int32(i), cfg)
    assert float(loss_fn(params)) < 0.05 * l0
    assert np.isfinite(float(stats["grad_norm"]))


def test_grad_clip_caps_update_norm():
    cfg = OptConfig(lr=1.0, grad_clip=1e-3, warmup_steps=1,
                    schedule="constant", weight_decay=0.0)
    params = {"a": jnp.zeros((4,))}
    state = init_opt_state(params, cfg)
    huge = {"a": jnp.full((4,), 1e6)}
    new_params, _, stats = apply_updates(params, huge, state, jnp.int32(0),
                                         cfg)
    assert float(stats["grad_norm"]) > 1e5  # pre-clip norm reported
    assert float(jnp.abs(new_params["a"]).max()) < 10.0


def test_lr_schedule_warmup_and_cosine():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
    assert float(lr_at(cfg, jnp.int32(0))) < 0.2
    # warmup complete at step 9, cosine already decaying slightly
    assert float(lr_at(cfg, jnp.int32(9))) == pytest.approx(0.98, rel=0.02)
    assert float(lr_at(cfg, jnp.int32(99))) < 0.01


def test_bf16_state_option():
    cfg = OptConfig(state_dtype="bfloat16")
    params = _quadratic_params(jax.random.key(0))
    state = init_opt_state(params, cfg)
    assert state["m"]["a"].dtype == jnp.bfloat16


def test_checkpoint_roundtrip_and_keep_k(tmp_path):
    tree = {"w": np.arange(12.0).reshape(3, 4), "s": np.int32(7),
            "nested": {"x": np.ones((2,), np.float32)}}
    for step in (10, 20, 30, 40):
        save_checkpoint(tmp_path, step, tree, keep=2)
    assert latest_step(tmp_path) == 40
    kept = sorted(p.name for p in tmp_path.iterdir())
    assert kept == ["step_00000030", "step_00000040"]
    like = jax.tree.map(lambda x: jnp.zeros_like(jnp.asarray(x)), tree)
    restored, step = restore_checkpoint(tmp_path, like)
    assert step == 40
    np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
    assert int(restored["s"]) == 7


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save_checkpoint(tmp_path, 1, {"w": np.ones((2, 2))})
    with pytest.raises(ValueError):
        restore_checkpoint(tmp_path, {"w": jnp.zeros((3, 3))})


def test_train_loop_fault_injection_and_restart(tmp_path):
    """NaN batches are skipped; repeated faults trigger checkpoint restore;
    a killed-and-restarted loop resumes from the saved step."""
    from repro.configs import get_config
    from repro.data import lm_batches
    from repro.models.transformer import Model
    from repro.train import TrainLoopConfig, train

    cfg = get_config("qwen2.5-32b", "smoke")
    m = Model(cfg)
    data = lm_batches(cfg.vocab_size, batch=2, seq=16, seed=0)
    opt = OptConfig(lr=1e-3, warmup_steps=1, schedule="constant")

    def inject(step, batch):
        if step == 7:  # poison one batch -> NaN loss
            bad = dict(batch)
            bad["inputs"] = np.full_like(batch["inputs"], -1)
            return bad
        return batch

    loop = TrainLoopConfig(total_steps=12, ckpt_dir=str(tmp_path),
                           ckpt_every=5, log_every=100)
    out = train(m, data, opt, loop, hooks={"inject_fault": inject})
    hist_steps = [h["step"] for h in out["history"]]
    assert 7 not in hist_steps or all(
        np.isfinite(h["loss"]) for h in out["history"])
    wait_pending()
    assert latest_step(tmp_path) is not None

    # restart: resumes from checkpoint, runs to a later total
    loop2 = TrainLoopConfig(total_steps=15, ckpt_dir=str(tmp_path),
                            ckpt_every=5, log_every=100)
    out2 = train(m, data, opt, loop2)
    assert int(out2["state"]["step"]) == 15


def test_grad_compression_error_feedback():
    """Quantize-allreduce with EF: single-step error bounded, EF carries the
    residual so the *running sum* converges to the true mean."""
    import os
    # use the local 1-device mesh: n_pods=1 path must be identity
    from repro.launch.mesh import make_mesh
    from repro.optim import compress_pod_allreduce, init_ef_state
    mesh = make_mesh((1, 1), ("data", "model"))
    g = {"w": jnp.ones((4, 4))}
    ef = init_ef_state(g)
    out, ef2 = compress_pod_allreduce(g, ef, mesh, n_pods=1)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones((4, 4)))
