"""Weights and data from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights, not the program's ``model.init``: the plain
reference is given the same arrays and takes nothing the program has made.
The program only tells the shapes (``jax.eval_shape`` of its ``init``).
"""

from __future__ import annotations

import numpy as np


def key_from_seed(seed: int, stream: int = 0):
    """A typed ``rbg`` key (quick to compile and to run on the TPU) from any
    whole number: ``--seed`` may pass 2**31, which a 32-bit seed cannot hold."""
    import jax

    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        4, np.uint32)
    return jax.random.wrap_key_data(words, impl="rbg")


def numpy_rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), int(stream)]))


def _leaf(path: str, shape, key):
    """One leaf by what its name says it is. Kernels: normal with standard
    deviation 1/sqrt(fan_in). Embeddings and biases: 0.02. Norm scales: 1
    +- 0.1, so that a path which dropped them would show. Batch statistics:
    mean 0, variance 1."""
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    noise = jax.random.normal(key, shape, jnp.float32)
    if name == "kernel":
        return noise / np.sqrt(int(np.prod(shape[:-1])))
    if name == "scale":
        return 1.0 + 0.1 * noise
    if name == "var":
        return jnp.ones(shape, jnp.float32)
    if name == "mean":
        return jnp.zeros(shape, jnp.float32)
    return 0.02 * noise              # embedding, bias


def tree_builder(shapes, dtype):
    """``build(key)`` -> a tree of arrays like ``shapes`` (any pytree of
    things with ``.shape``) in ``dtype``, every leaf from its own stream of
    the key. To be called under ``jit``."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    dims = [tuple(leaf.shape) for _, leaf in flat]

    def build(key):
        keys = jax.random.split(key, len(dims))
        leaves = [_leaf(p, s, k).astype(dtype)
                  for p, s, k in zip(paths, dims, keys)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build


def weights_key(seed: int):
    return key_from_seed(seed, stream=1)


def make_tree(shapes, seed: int, dtype):
    """The weights of a run: one jitted call on the device."""
    import jax

    return jax.jit(tree_builder(shapes, dtype))(weights_key(seed))


def leaf_paths(tree) -> list:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
