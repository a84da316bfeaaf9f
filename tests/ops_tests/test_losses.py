"""Chunked softmax CE vs the materialized-logits oracle (values + grads)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu.ops.losses import chunked_softmax_cross_entropy


def _setup(key, n=24, d=8, v=40, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    hidden = jax.random.normal(k1, (n, d), dtype)
    kernel = jax.random.normal(k2, (d, v), dtype) * 0.3
    bias = jax.random.normal(k3, (v,), dtype) * 0.1
    targets = jax.random.randint(k4, (n,), 0, v)
    return hidden, kernel, bias, targets


def _oracle(hidden, kernel, bias, targets):
    lg = (hidden.astype(jnp.float32) @ kernel.astype(jnp.float32))
    if bias is not None:
        lg = lg + bias.astype(jnp.float32)
    return optax.softmax_cross_entropy_with_integer_labels(lg, targets)


@pytest.mark.parametrize("chunk", [8, 7, 24, 100])
def test_values_match_oracle(chunk):
    """Chunk sizes that divide N, don't divide N (padding), equal N, and
    exceed N must all reproduce the materialized-logits CE."""
    hidden, kernel, bias, targets = _setup(jax.random.PRNGKey(0))
    got = chunked_softmax_cross_entropy(hidden, kernel, bias, targets,
                                        chunk_size=chunk)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_oracle(hidden, kernel, bias,
                                                  targets)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("with_bias", [True, False])
def test_grads_match_oracle(with_bias):
    hidden, kernel, bias, targets = _setup(jax.random.PRNGKey(1))
    if not with_bias:
        bias = None

    def loss_chunked(h, k, b):
        return chunked_softmax_cross_entropy(h, k, b, targets,
                                             chunk_size=7).mean()

    def loss_oracle(h, k, b):
        return _oracle(h, k, b, targets).mean()

    args = (hidden, kernel, bias)
    wrt = (0, 1) if bias is None else (0, 1, 2)
    g_c = jax.grad(loss_chunked, argnums=wrt)(*args)
    g_o = jax.grad(loss_oracle, argnums=wrt)(*args)
    for a, b_, name in zip(g_c, g_o, ["hidden", "kernel", "bias"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


def test_leading_shape_and_bf16():
    """[B, T] leading shape round-trips; bf16 hidden/kernel accumulate the
    tile in f32 (no bf16 logsumexp)."""
    hidden, kernel, bias, targets = _setup(jax.random.PRNGKey(2), n=32,
                                           dtype=jnp.bfloat16)
    h2 = hidden.reshape(4, 8, -1)
    t2 = targets.reshape(4, 8)
    got = chunked_softmax_cross_entropy(h2, kernel, bias, t2, chunk_size=8)
    assert got.shape == (4, 8)
    assert got.dtype == jnp.float32
    want = _oracle(hidden, kernel, bias, targets).reshape(4, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_weighted_cotangent():
    """Non-uniform per-token cotangents (e.g. masked means) flow exactly."""
    hidden, kernel, bias, targets = _setup(jax.random.PRNGKey(3))
    w = jnp.linspace(0.0, 1.0, targets.shape[0])

    def loss_chunked(h):
        return jnp.sum(chunked_softmax_cross_entropy(
            h, kernel, bias, targets, chunk_size=7) * w)

    def loss_oracle(h):
        return jnp.sum(_oracle(h, kernel, bias, targets) * w)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_chunked)(hidden)),
        np.asarray(jax.grad(loss_oracle)(hidden)),
        rtol=2e-5, atol=2e-5)


def test_pad_rows_stay_out_of_the_gradients_under_a_large_bias():
    """``n % chunk != 0``: a pad row's logits are the bias alone, so a
    bias entry past ~88 made ``exp(bias - 0)`` inf on it and ``inf * 0``
    poisoned ``dkernel``/``dbias``/``dhidden``. The gradients are finite
    and the unchunked reference's."""
    chunk = 8
    hidden, kernel, bias, targets = _setup(jax.random.PRNGKey(4),
                                           n=chunk + 3)
    bias = bias.at[5].set(100.0)

    def loss_chunked(h, k, b):
        return chunked_softmax_cross_entropy(h, k, b, targets,
                                             chunk_size=chunk).mean()

    def loss_oracle(h, k, b):
        return _oracle(h, k, b, targets).mean()

    g_c = jax.grad(loss_chunked, argnums=(0, 1, 2))(hidden, kernel, bias)
    g_o = jax.grad(loss_oracle, argnums=(0, 1, 2))(hidden, kernel, bias)
    for a, b_, name in zip(g_c, g_o, ["hidden", "kernel", "bias"]):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
