"""Autodiff-through-communication: p2p.

Mirrors ``[U] tests/chainermn_tests/functions_tests/test_point_to_point_
communication.py`` (SURVEY.md S4): forward values AND gradients of send/recv
across ranks — the backward must be the transposed communication.
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


def test_send_recv_forward(comm):
    n = comm.size

    def step(x):
        with F.rank_context(0):
            phi = F.send(x, comm, rank=1)
        with F.rank_context(1):
            y = F.recv(comm, rank=0, delegate_variable=phi)
        return y

    f = jax.jit(comm.shard_map(step, in_specs=P(comm.axis_name), out_specs=P(comm.axis_name)))
    x = np.stack([np.full((2,), float(r), np.float32) for r in range(n)])
    y = np.asarray(f(x))
    np.testing.assert_allclose(y[1], x[0])        # rank 1 received rank 0's data
    np.testing.assert_allclose(y[2], np.zeros(2))  # everyone else: zeros


def test_send_recv_gradient_is_transposed_comm(comm):
    """Loss lives on rank 1 (the receiver); its gradient must land on rank
    0's input — i.e. backward communication is the reverse ppermute."""
    n = comm.size

    def loss_fn(x):
        def step(xl):
            with F.rank_context(0):
                phi = F.send(xl, comm, rank=1)
            with F.rank_context(1):
                y = F.recv(comm, rank=0, delegate_variable=phi)
            rank = comm.axis_index()
            contrib = jnp.where(rank == 1, jnp.sum(y**2), 0.0)
            return comm.allreduce(contrib, "sum")[None]  # [1] so P(axis) stacks

        f = comm.shard_map(step, in_specs=P(comm.axis_name), out_specs=P(comm.axis_name))
        return jnp.sum(f(x)) / n  # every rank returns the same total

    x = np.stack([np.full((3,), float(r + 1), np.float32) for r in range(n)])
    g = np.asarray(jax.grad(loss_fn)(jnp.asarray(x)))
    np.testing.assert_allclose(g[0], 2.0 * x[0], rtol=1e-6)  # d/dx0 of sum(x0^2)
    np.testing.assert_allclose(g[1:], np.zeros_like(g[1:]))


def test_send_requires_rank_context(comm):
    with pytest.raises(RuntimeError, match="rank_context"):
        F.send(jnp.ones(2), comm, rank=1)


def test_send_self_rejected(comm):
    with F.rank_context(1):
        with pytest.raises(ValueError, match="self-send"):
            F.send(jnp.ones(2), comm, rank=1)


def test_recv_endpoint_mismatch(comm):
    def step(x):
        with F.rank_context(0):
            phi = F.send(x, comm, rank=1)
        with F.rank_context(2):
            return F.recv(comm, rank=0, delegate_variable=phi)

    with pytest.raises(ValueError, match="mismatch"):
        jax.jit(comm.shard_map(step, in_specs=P(comm.axis_name), out_specs=P(comm.axis_name)))(
            np.ones((comm.size, 2), np.float32)
        )


def test_recv_requires_delegate(comm):
    with F.rank_context(1):
        with pytest.raises(ValueError, match="delegate_variable"):
            F.recv(comm, rank=0)


def test_pseudo_connect_preserves_value_and_gradient(comm):
    n = comm.size

    def loss_fn(x):
        def step(xl):
            with F.rank_context(0):
                phi = F.send(xl * 2.0, comm, rank=1)
            z = xl * 3.0
            z = F.pseudo_connect(phi, z)
            return z

        f = comm.shard_map(step, in_specs=P(comm.axis_name), out_specs=P(comm.axis_name))
        return jnp.sum(f(x))

    x = jnp.ones((n, 2), jnp.float32)
    val = loss_fn(x)
    np.testing.assert_allclose(float(val), 3.0 * n * 2)
    g = np.asarray(jax.grad(loss_fn)(x))
    np.testing.assert_allclose(g, np.full((n, 2), 3.0))


def test_delegate_chain_two_hops(comm):
    """0 -> 1 -> 2 relay, the MultiNodeChainList pattern."""
    n = comm.size

    def step(x):
        with F.rank_context(0):
            phi1 = F.send(x, comm, rank=1)
        with F.rank_context(1):
            h = F.recv(comm, rank=0, delegate_variable=phi1)
            phi2 = F.send(h + 10.0, comm, rank=2)
        with F.rank_context(2):
            return F.recv(comm, rank=1, delegate_variable=phi2)

    f = jax.jit(comm.shard_map(step, in_specs=P(comm.axis_name), out_specs=P(comm.axis_name)))
    x = np.stack([np.full((2,), float(r), np.float32) for r in range(n)])
    y = np.asarray(f(x))
    np.testing.assert_allclose(y[2], x[0] + 10.0)
