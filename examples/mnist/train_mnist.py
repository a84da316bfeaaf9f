#!/usr/bin/env python
"""MNIST data-parallel training — the reference's minimum end-to-end slice.

Parity target: ``[U] examples/mnist/train_mnist.py`` (SURVEY.md S2.15 —
unverified cite). Exercises: communicator factory, ``scatter_dataset``,
multi-node optimizer, multi-node evaluator, root-only reporting.

Where the reference runs ``mpiexec -n N python train_mnist.py``, this runs as
ONE controller over all local devices (SPMD over a Mesh). To emulate N
"ranks" without a TPU pod slice::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/mnist/train_mnist.py --epoch 2

MNIST itself needs a download; without ``--data mnist.npz`` a deterministic
synthetic stand-in with class structure is used (the training dynamics are
real, the digits are not).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu.models import MLP
from chainermn_tpu.training import jit_train_step
from chainermn_tpu.utils import enable_compilation_cache, ensure_batch_fits


def load_mnist(path: str | None, n_train: int, n_test: int, seed: int = 0):
    """``mnist.npz`` (keras layout: x_train/y_train/x_test/y_test) or a
    synthetic, learnable stand-in: each class has a fixed random template,
    samples are template + noise."""
    if path:
        with np.load(path) as z:
            return (
                (z["x_train"][:n_train].astype(np.float32) / 255.0,
                 z["y_train"][:n_train].astype(np.int32)),
                (z["x_test"][:n_test].astype(np.float32) / 255.0,
                 z["y_test"][:n_test].astype(np.int32)),
            )
    rng = np.random.RandomState(seed)
    templates = rng.rand(10, 28, 28).astype(np.float32)

    def draw(n):
        y = rng.randint(0, 10, size=n).astype(np.int32)
        x = templates[y] + 0.3 * rng.randn(n, 28, 28).astype(np.float32)
        return np.clip(x, 0.0, 1.0), y

    return draw(n_train), draw(n_test)


class ArrayDataset:
    """(x, y) record view over parallel arrays (chainer's TupleDataset shape)."""

    def __init__(self, x: np.ndarray, y: np.ndarray) -> None:
        assert len(x) == len(y)
        self.x, self.y = x, y

    def __len__(self) -> int:
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def collate(batch) -> tuple[np.ndarray, np.ndarray]:
    xs, ys = zip(*batch)
    return np.stack(xs), np.asarray(ys, np.int32)


def main() -> None:
    parser = argparse.ArgumentParser(description="ChainerMN-TPU example: MNIST")
    parser.add_argument("--batchsize", "-b", type=int, default=100,
                        help="per-participant batch size (reference default)")
    parser.add_argument("--epoch", "-e", type=int, default=20)
    parser.add_argument("--unit", "-u", type=int, default=1000)
    parser.add_argument("--communicator", type=str, default="tpu",
                        help="naive | flat | tpu | pure_nccl | hierarchical | "
                             "two_dimensional | single_node")
    parser.add_argument("--data", type=str, default=None,
                        help="path to mnist.npz (keras layout); synthetic if absent")
    parser.add_argument("--n-train", type=int, default=10000)
    parser.add_argument("--n-test", type=int, default=2000)
    args = parser.parse_args()
    enable_compilation_cache()

    chainermn_tpu.add_global_except_hook()
    comm = chainermn_tpu.create_communicator(args.communicator)
    if comm.rank == 0:
        print(f"communicator: {args.communicator}  size: {comm.size} "
              f"(intra {comm.intra_size} x inter {comm.inter_size})")

    (x_train, y_train), (x_test, y_test) = load_mnist(
        args.data, args.n_train, args.n_test
    )
    # Process-space scatter (multi-host); within a process the global batch is
    # sharded over devices by the train step itself.
    train = chainermn_tpu.scatter_dataset(
        ArrayDataset(x_train, y_train), comm, shuffle=True, seed=0
    )
    test = chainermn_tpu.scatter_dataset(ArrayDataset(x_test, y_test), comm)

    model = MLP(n_units=args.unit)
    global_batch = args.batchsize * comm.size
    ensure_batch_fits(train, global_batch, comm.size)
    it = chainermn_tpu.SerialIterator(train, global_batch, shuffle=True, seed=1)

    variables = comm.bcast_data(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))
    )
    optimizer = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-3), comm)
    opt_state = jax.device_put(
        optimizer.init(variables["params"]), comm.named_sharding()
    )
    step = jit_train_step(model, optimizer, comm)

    @jax.jit
    def eval_batch(variables, images, labels):
        logits = model.apply(variables, images)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        acc = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        return loss.sum(), acc.sum()

    def evaluate() -> dict:
        tot_loss = tot_acc = n = 0.0
        ev_it = chainermn_tpu.SerialIterator(
            test, global_batch, repeat=False, shuffle=False
        )
        for batch in ev_it:
            images, labels = collate(batch)
            loss, acc = eval_batch(variables, images, labels)
            tot_loss += float(loss)
            tot_acc += float(acc)
            n += len(labels)
        n = max(n, 1.0)
        return {"validation/main/loss": tot_loss / n,
                "validation/main/accuracy": tot_acc / n}

    evaluator = chainermn_tpu.create_multi_node_evaluator(evaluate, comm)

    steps_per_epoch = max(1, len(train) // global_batch)
    t0 = time.time()
    loss = jnp.float32(0)
    while it.epoch < args.epoch:
        images, labels = collate(next(it))
        if len(labels) == global_batch:  # ragged tail: skip (reference drops too)
            variables, opt_state, loss = step(variables, opt_state, images, labels)
        if it.is_new_epoch:
            metrics = evaluator.evaluate()
            if comm.rank == 0:
                print(f"epoch {it.epoch:3d}  train/loss {float(loss):.4f}  "
                      f"val/loss {metrics['validation/main/loss']:.4f}  "
                      f"val/acc {metrics['validation/main/accuracy']:.4f}  "
                      f"({(time.time() - t0) / it.epoch:.2f}s/epoch, "
                      f"{steps_per_epoch} steps)")
    if comm.rank == 0:
        print(f"done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
