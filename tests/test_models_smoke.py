"""Per-arch smoke tests (assignment requirement): reduced config of the same
family, one forward + one train step on CPU, asserting shapes + no NaNs; plus
decode-vs-full consistency and factorized-variant gradients."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.core.factorized import FactorizationConfig
from repro.models.transformer import Model
from repro.optim import OptConfig, apply_updates, init_opt_state

ARCHS = list_archs()


def _batch(cfg, key, B=2, S=32):
    if cfg.external_embeddings:
        b = {"embeds": jax.random.normal(key, (B, S, cfg.d_model))}
    else:
        b = {"inputs": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    lbl = (B, S, cfg.n_codebooks) if cfg.n_codebooks > 1 else (B, S)
    b["labels"] = jax.random.randint(key, lbl, 0, cfg.vocab_size)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nan(arch):
    cfg = get_config(arch, "smoke")
    m = Model(cfg)
    key = jax.random.key(0)
    params = m.init(key)
    b = _batch(cfg, key)
    logits, _, aux = m.apply(params, b)
    expect = (2, 32, cfg.n_codebooks, cfg.vocab_size) if cfg.n_codebooks > 1 \
        else (2, 32, cfg.vocab_size)
    assert logits.shape == expect
    assert not bool(jnp.isnan(logits).any())
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_decreases_loss_no_nan(arch):
    cfg = get_config(arch, "smoke")
    m = Model(cfg)
    key = jax.random.key(0)
    params = m.init(key)
    opt_cfg = OptConfig(lr=5e-3, warmup_steps=1, weight_decay=0.0,
                        schedule="constant")
    opt = init_opt_state(params, opt_cfg)
    b = _batch(cfg, key)

    @jax.jit
    def step(params, opt, i):
        (loss, _), grads = jax.value_and_grad(
            lambda p: m.loss(p, b), has_aux=True)(params)
        params, opt, _ = apply_updates(params, grads, opt, i, opt_cfg)
        return params, opt, loss

    losses = []
    for i in range(5):
        params, opt, loss = step(params, opt, jnp.int32(i))
        assert np.isfinite(float(loss)), f"step {i} NaN"
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # same-batch overfit must reduce loss


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "starcoder2-15b",
                                  "mamba2-370m", "recurrentgemma-2b",
                                  "musicgen-large", "dbrx-132b",
                                  "arctic-480b", "yi-34b", "qwen1.5-4b",
                                  "llava-next-mistral-7b"])
def test_decode_matches_full_forward(arch):
    cfg = get_config(arch, "smoke")
    m = Model(cfg)
    key = jax.random.key(0)
    params = m.init(key)
    B, S = 2, 16
    if cfg.external_embeddings:
        x = jax.random.normal(key, (B, S, cfg.d_model))
        full_b, pre_b, dec_b = ({"embeds": x}, {"embeds": x[:, :S - 1]},
                                {"embeds": x[:, S - 1:]})
    else:
        t = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        full_b, pre_b, dec_b = ({"inputs": t}, {"inputs": t[:, :S - 1]},
                                {"inputs": t[:, S - 1:]})
    logits_full, _, _ = m.apply(params, full_b)
    _, caches = m.prefill(params, pre_b, max_len=S + 4)
    logits_dec, _ = m.decode_step(params, dec_b, caches, jnp.int32(S - 1))
    a = np.asarray(logits_full[:, -1], np.float32)
    b = np.asarray(logits_dec[:, 0], np.float32)
    rel = np.abs(a - b).max() / np.abs(a).max()
    assert rel < 0.02, f"{arch}: decode diverges from full forward ({rel})"


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "dbrx-132b", "mamba2-370m",
                                  "recurrentgemma-2b"])
def test_factorized_variant_grads(arch):
    cfg = get_config(arch, "smoke")
    cfg = dataclasses.replace(
        cfg, factorization=FactorizationConfig(enabled=True, min_dim=32))
    m = Model(cfg)
    key = jax.random.key(0)
    params = m.init(key)
    assert "dicts" in params
    b = _batch(cfg, key)
    loss, grads = jax.value_and_grad(
        lambda p: m.loss(p, b, sparse_train=True)[0])(params)
    assert np.isfinite(float(loss))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    # dictionaries receive gradient (they are shared across layers)
    gd = jax.tree.leaves(grads["dicts"])
    assert all(float(jnp.abs(g).max()) > 0 for g in gd)


def test_packed_forward_matches_separate():
    """Dynamic batching fidelity: two packed requests produce the same
    logits as running them separately (block-diagonal masking)."""
    from repro.core.packing import PackingPolicy, pack_requests
    cfg = get_config("qwen2.5-32b", "smoke")
    m = Model(cfg)
    key = jax.random.key(0)
    params = m.init(key)
    r1 = np.arange(10) % cfg.vocab_size
    r2 = (np.arange(6) + 3) % cfg.vocab_size
    packed = pack_requests([r1, r2], PackingPolicy(max_len=16))
    logits_packed, _, _ = m.apply(params, {
        "inputs": jnp.asarray(packed.tokens),
        "positions": jnp.asarray(packed.positions),
        "seg_ids": jnp.asarray(packed.segment_ids)})
    for i, r in enumerate([r1, r2]):
        row, start, L = packed.request_slots[i]
        solo, _, _ = m.apply(params, {
            "inputs": jnp.asarray(r, jnp.int32)[None]})
        a = np.asarray(solo[0, :L], np.float32)
        b = np.asarray(logits_packed[row, start:start + L], np.float32)
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
        assert rel < 0.02, f"request {i} packed != solo ({rel})"


@pytest.mark.parametrize("factorized", [False, True])
def test_bf16_param_dtype_holds_for_weight_leaves(factorized):
    """A bfloat16 ``param_dtype`` gives bfloat16 weights: the init scale
    (a numpy float) must not promote them to float32, which would double
    a full-width model's footprint on the chip."""
    for arch in ("qwen1.5-4b", "dbrx-132b", "recurrentgemma-2b"):
        cfg = get_config(arch, "smoke", param_dtype="bfloat16",
                         factorized=factorized)
        shapes = jax.eval_shape(Model(cfg).init, jax.random.key(0))
        wide = [jax.tree_util.keystr(p) for p, leaf
                in jax.tree_util.tree_flatten_with_path(shapes)[0]
                if leaf.dtype != jnp.bfloat16 and leaf.ndim >= 2]
        assert not wide, (arch, wide)


def _leaves(arch):
    m = Model(get_config(arch, "full"))
    flat = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(m.init, jax.random.key(0)))[0]
    return m.cfg, {jax.tree_util.keystr(p): x.shape for p, x in flat}


def test_starcoder2_full_is_the_published_block():
    """starcoder2-15b as published (``use_bias``: a bias on every attention
    and feed-forward linear; LayerNorm with bias, eps 1e-5; tanh GELU, not
    gated), and its smoke variant the same block."""
    cfg, leaves = _leaves("starcoder2-15b")
    assert (cfg.norm, cfg.norm_eps, cfg.act) == ("layernorm", 1e-5, "gelu")
    assert get_config("starcoder2-15b", "smoke").norm_eps == 1e-5
    for lin, width in (("attn']['wq", 48 * 128), ("attn']['wk", 4 * 128),
                       ("attn']['wv", 4 * 128), ("attn']['wo", 6144),
                       ("ffn']['w_up", 24576), ("ffn']['w_down", 6144)):
        assert leaves[f"['layers']['{lin}']['b']"] == (40, width), lin
    assert "['layers']['ffn']['w_gate']['w']" not in leaves
    for norm in ("['layers']['norm1']", "['layers']['norm2']",
                 "['final_norm']"):
        assert f"{norm}['bias']" in leaves
    assert sum(int(np.prod(s)) for s in leaves.values()) == 15_957_889_024


def test_qwen1_5_4b_parameter_tree_is_unchanged():
    """The published-bias fields default off: qwen1.5-4b keeps its leaves
    (biases on q, k and v only), its size and its RMSNorm eps 1e-6."""
    cfg, leaves = _leaves("qwen1.5-4b")
    assert (cfg.linear_bias, cfg.norm_eps) == (False, 1e-6)
    assert sorted(leaves) == sorted(
        ["['embed']['tok']", "['final_norm']['scale']", "['lm_head']['w']",
         "['layers']['norm1']['scale']", "['layers']['norm2']['scale']"]
        + [f"['layers']['attn']['{n}']['{k}']" for n in ("wq", "wk", "wv")
           for k in ("w", "b")]
        + ["['layers']['attn']['wo']['w']"]
        + [f"['layers']['ffn']['{n}']['w']"
           for n in ("w_up", "w_gate", "w_down")])
    assert sum(int(np.prod(s)) for s in leaves.values()) == 3_950_369_280
