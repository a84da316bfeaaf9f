"""Autodiff-through-communication: collectives.

Mirrors ``[U] tests/chainermn_tests/functions_tests/test_collective_
communication.py`` (SURVEY.md S4): forward values and the transposed-backward
property of each differentiable collective, plus a finite-difference check.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu import create_communicator
from chainermn_tpu import functions as F


@pytest.fixture(scope="module")
def comm():
    return create_communicator("naive")


def _grad_of(comm, step, x):
    """Gradient of sum(step(x)) with step running under shard_map."""

    def loss(xx):
        f = comm.shard_map(step, in_specs=P(comm.axis_name), out_specs=P(comm.axis_name))
        return jnp.sum(f(xx))

    return loss, jax.grad(loss)(jnp.asarray(x))


def test_allgather_backward_is_reduce_scatter(comm):
    """loss = sum over every rank's gathered copy => each x_i receives a
    cotangent from all n copies: grad = n * 1."""
    n = comm.size

    def step(x):
        return F.allgather(x, comm)

    _, g = _grad_of(comm, step, np.random.RandomState(0).randn(n, 2).astype(np.float32))
    np.testing.assert_allclose(np.asarray(g), np.full((n, 2), float(n)), rtol=1e-6)


def test_alltoall_backward_is_alltoall(comm):
    n = comm.size

    def step(x):
        # x is the local [1, n, 2] block: squeeze the rank axis for the
        # per-rank alltoall convention, restore it for the out_spec.
        return F.alltoall(x[0], comm)[None]

    x = np.random.RandomState(1).randn(n, n, 2).astype(np.float32)

    def loss(xx):
        f = comm.shard_map(step, in_specs=P(comm.axis_name), out_specs=P(comm.axis_name))
        y = f(xx)
        w = jnp.arange(y.size, dtype=y.dtype).reshape(y.shape)  # distinct weights
        return jnp.sum(y * w)

    g = np.asarray(jax.grad(loss)(jnp.asarray(x)))
    # analytic: dL/dx[i,j] = w[j,i]  (alltoall transposes rank/slice indices)
    w = np.arange(x.size, dtype=np.float32).reshape(x.shape)
    expected = np.swapaxes(w, 0, 1)
    np.testing.assert_allclose(g, expected, rtol=1e-6)


@pytest.mark.parametrize("root", [0, 2])
def test_bcast_backward_sums_at_root(comm, root):
    n = comm.size

    def step(x):
        return F.bcast(x, comm, root=root)

    x = np.random.RandomState(2).randn(n, 3).astype(np.float32)
    _, g = _grad_of(comm, step, x)
    g = np.asarray(g)
    np.testing.assert_allclose(g[root], np.full((3,), float(n)), rtol=1e-6)
    mask = np.ones(n, bool)
    mask[root] = False
    np.testing.assert_allclose(g[mask], 0.0)


def test_scatter_gather_roundtrip_and_grad(comm):
    n = comm.size

    def roundtrip(x):
        y = F.scatter(x, comm, root=0)      # each rank gets its row: [2]
        return F.gather(y, comm, root=0)    # stack them back: [n, 2]

    x = np.arange(n * 2, dtype=np.float32).reshape(n, 2)
    # out_specs stacks every rank's copy (VMA can't statically infer the
    # gather output as replicated): [n*n, 2], each block must equal x
    f = jax.jit(comm.shard_map(roundtrip, in_specs=P(), out_specs=P(comm.axis_name)))
    y = np.asarray(f(x)).reshape(n, n, 2)
    for r in range(n):
        np.testing.assert_allclose(y[r], x)

    # backward of scatter gathers cotangents onto root: with a summed square
    # loss every rank's row lands back at its slot of root's input
    def loss(x):
        y = F.scatter(x, comm, root=0)
        return comm.allreduce((y * y).sum(), "sum")

    g = jax.jit(
        comm.shard_map(jax.grad(loss), in_specs=P(), out_specs=P(comm.axis_name))
    )(x)
    g = np.asarray(g).reshape(n, n, 2)
    for r in range(n):
        np.testing.assert_allclose(g[r], 2 * x, rtol=1e-6)


def test_allreduce_function_grad(comm):
    n = comm.size

    def step(x):
        return F.allreduce(x, comm, "sum")

    x = np.random.RandomState(3).randn(n, 2).astype(np.float32)
    _, g = _grad_of(comm, step, x)
    # every rank's output includes every x_i once; n outputs => grad = n
    np.testing.assert_allclose(np.asarray(g), np.full((n, 2), float(n)), rtol=1e-6)


def test_finite_difference_through_collectives(comm):
    """End-to-end numerical check: composite program mixing compute and
    communication, jax.grad vs central differences."""
    n = comm.size

    def step(x):
        h = jnp.tanh(x)
        g = F.allgather(h, comm)          # [n, d]
        s = jnp.sum(g, axis=0)            # mix all ranks
        return s * h                      # per-rank output

    def loss(xx):
        f = comm.shard_map(step, in_specs=P(comm.axis_name), out_specs=P(comm.axis_name))
        return jnp.sum(f(xx) ** 2)

    rng = np.random.RandomState(4)
    x = rng.randn(n, 3)
    with jax.enable_x64(True):
        g = np.asarray(jax.grad(loss)(jnp.asarray(x, dtype=jnp.float64)))
        eps = 1e-5
        for idx in [(0, 0), (2, 1), (n - 1, 2)]:
            xp = x.copy(); xp[idx] += eps
            xm = x.copy(); xm[idx] -= eps
            fd = (
                float(loss(jnp.asarray(xp, dtype=jnp.float64)))
                - float(loss(jnp.asarray(xm, dtype=jnp.float64)))
            ) / (2 * eps)
            np.testing.assert_allclose(g[idx], fd, rtol=1e-5, atol=1e-8)
