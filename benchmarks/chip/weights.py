"""Seeded weights, made by the benchmark on the device in one jitted call.

The program gives the layout (leaf names and shapes, from
``jax.eval_shape`` of its init, and where each leaf lives on a mesh); the
values are the benchmark's own, drawn from the seed, so the reference
never uses numbers the program made. Scales are those of the usual
initialisation, which keep the logits near unit size so that gaps read
on one scale across seeds:

* token embedding rows ~ N(0, 0.02^2), so the residual stream is the
  layers' sum and rounding in any layer reaches the logits (with rows of
  unit size the embedding dominates the stream, and an int8 rounding of
  every weight moved 40-layer logits barely more than bf16 rounding did);
* a linear map ``w`` of input width n ~ N(0, 1/n), its bias ~ N(0, 0.1^2);
* norm scales ~ 1 + N(0, 0.1^2), layer-norm biases ~ N(0, 0.1^2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["seed_key", "make_weights"]


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A key that depends on every bit of a seed of up to 64 bits (the
    key constructor alone keeps only the low 32)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def _leaf(path, sd, key):
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    name = names[-1]
    if name == "tok":
        x = 0.02 * jax.random.normal(key, sd.shape, jnp.float32)
    elif name == "w":
        x = jax.random.normal(key, sd.shape, jnp.float32) \
            * sd.shape[-2] ** -0.5
    elif name in ("b", "bias"):
        x = 0.1 * jax.random.normal(key, sd.shape, jnp.float32)
    elif name == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, sd.shape, jnp.float32)
    else:
        raise ValueError(f"no rule for weight leaf {'/'.join(names)}")
    return x.astype(sd.dtype)


def make_weights(model, seed: int, shardings=None):
    """Weights in the program's layout, drawn from ``seed``. ``shardings``
    (a pytree of ``NamedSharding`` matching the layout) builds each leaf
    straight into its shards."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(path, sd, jax.random.fold_in(key, i))
            for i, (path, sd) in enumerate(flat)])

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
