#!/usr/bin/env python
"""MNIST model-parallel training — MLP split across two ranks.

Parity target: ``[U] examples/mnist/train_mnist_model_parallel.py``
(SURVEY.md S2.15 — unverified cite): the reference builds a
``MultiNodeChainList`` whose first half runs on rank 0 and second half on
rank 1, wired by differentiable send/recv. Here the chain is declared once
by the single controller; boundary tensors move device-to-device (ICI) and
autodiff produces the transposed backward transfers (S3.3).

Run (2+ emulated devices)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        python examples/mnist/train_mnist_model_parallel.py --epoch 3
"""

from __future__ import annotations

import argparse
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu.utils import enable_compilation_cache

from train_mnist import ArrayDataset, collate, load_mnist  # noqa: E402 (sibling)


class MLPHalf0(nn.Module):
    """Stage 0: input -> hidden (runs on rank 0)."""

    n_units: int

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(self.n_units)(x))
        return nn.relu(nn.Dense(self.n_units)(x))


class MLPHalf1(nn.Module):
    """Stage 1: hidden -> logits (runs on rank 1)."""

    n_units: int
    n_out: int = 10

    @nn.compact
    def __call__(self, h):
        h = nn.relu(nn.Dense(self.n_units)(h))
        return nn.Dense(self.n_out)(h)


def main() -> None:
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU example: MNIST model-parallel"
    )
    parser.add_argument("--batchsize", "-b", type=int, default=100)
    parser.add_argument("--epoch", "-e", type=int, default=10)
    parser.add_argument("--unit", "-u", type=int, default=500)
    parser.add_argument("--data", type=str, default=None)
    parser.add_argument("--n-train", type=int, default=8000)
    parser.add_argument("--n-test", type=int, default=1000)
    parser.add_argument(
        "--fused", action="store_true",
        help="one jitted program over the whole chain (replicated variables) "
             "instead of a jit per stage",
    )
    args = parser.parse_args()
    enable_compilation_cache()

    chainermn_tpu.add_global_except_hook()
    comm = chainermn_tpu.create_communicator("naive")
    if comm.size < 2:
        raise SystemExit("model-parallel example needs >= 2 devices")
    r0, r1 = 0, 1  # the two stage-owning ranks (reference: MPI ranks 0/1)

    model = chainermn_tpu.MultiNodeChainList(comm)
    model.add_link(MLPHalf0(args.unit), rank=r0, rank_in=None, rank_out=r1)
    model.add_link(MLPHalf1(args.unit), rank=r1, rank_in=r0, rank_out=None)

    (x_train, y_train), (x_test, y_test) = load_mnist(
        args.data, args.n_train, args.n_test
    )
    train = ArrayDataset(x_train, y_train)
    test = ArrayDataset(x_test, y_test)
    it = chainermn_tpu.SerialIterator(train, args.batchsize, shuffle=True, seed=1)

    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))
    if args.fused:
        # Fused mode trades per-rank placement for a single compiled
        # program: variables are replicated over the mesh and the whole
        # chain (and its backward) is one XLA program.
        variables = model.replicate(variables)
    # One optimizer per stage, exactly like the reference. In the default
    # mode each stage's optimizer state is co-located with its parameters on
    # the owning rank; under --fused it follows the replicated placement.
    optimizer = optax.adam(1e-3)
    opt_states = [optimizer.init(v) for v in variables]

    def loss_fn(variables, images, labels):
        logits = model.apply(variables, images, fused=args.fused)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    grad_fn = jax.value_and_grad(loss_fn)

    def train_step(variables, opt_states, images, labels):
        # The chain's stages are separately jitted (placement is per-stage);
        # the outer autodiff stitches their VJPs with reversed transfers.
        loss, grads = grad_fn(variables, images, labels)
        new_vars, new_states = [], []
        for v, g, s in zip(variables, grads, opt_states):
            updates, s = optimizer.update(g, s, v)
            new_vars.append(optax.apply_updates(v, updates))
            new_states.append(s)
        return new_vars, new_states, loss

    def evaluate() -> dict:
        correct = n = 0
        for batch in chainermn_tpu.SerialIterator(
            test, args.batchsize, repeat=False, shuffle=False
        ):
            images, labels = collate(batch)
            logits = model.apply(variables, images, fused=args.fused)
            correct += int((np.argmax(np.asarray(logits), -1) == labels).sum())
            n += len(labels)
        return {"validation/main/accuracy": correct / max(n, 1)}

    t0 = time.time()
    while it.epoch < args.epoch:
        images, labels = collate(next(it))
        variables, opt_states, loss = train_step(variables, opt_states, images, labels)
        if it.is_new_epoch and comm.rank == 0:
            metrics = evaluate()
            print(f"epoch {it.epoch:3d}  train/loss {float(loss):.4f}  "
                  f"val/acc {metrics['validation/main/accuracy']:.4f}")
    if comm.rank == 0:
        print(f"done in {time.time() - t0:.1f}s  "
              f"(stage devices: {[str(d) for d in list(comm.mesh.devices.flat)[:2]]})")


if __name__ == "__main__":
    main()
