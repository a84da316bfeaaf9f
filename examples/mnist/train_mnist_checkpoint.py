#!/usr/bin/env python
"""MNIST data-parallel training with fault-tolerant checkpoint/resume.

Parity target: ``[U] examples/mnist/train_mnist_checkpoint.py`` (SURVEY.md
S2.15 — unverified cite): the reference attaches
``create_multi_node_checkpointer`` to the trainer so a killed job resumes
from the newest snapshot every rank still has. Here the checkpointer
snapshots {variables, opt_state, iterator state} every ``--frequency``
iterations; rerunning the same command resumes automatically.

Try it: run with ``--stop-at 12`` (simulated crash), then run again without
it and watch training resume from the snapshot instead of iteration 0.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import optax

import chainermn_tpu
from chainermn_tpu.models import MLP
from chainermn_tpu.training import jit_train_step
from chainermn_tpu.utils import enable_compilation_cache, ensure_batch_fits

from train_mnist import ArrayDataset, collate, load_mnist  # noqa: E402 (sibling)


def main() -> None:
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU example: MNIST with checkpointing"
    )
    parser.add_argument("--batchsize", "-b", type=int, default=100)
    parser.add_argument("--epoch", "-e", type=int, default=5)
    parser.add_argument("--unit", "-u", type=int, default=200)
    parser.add_argument("--communicator", type=str, default="tpu")
    parser.add_argument("--out", type=str, default="/tmp/chainermn_tpu_ckpt")
    parser.add_argument("--frequency", type=int, default=5,
                        help="snapshot every N iterations")
    parser.add_argument("--stop-at", type=int, default=None,
                        help="simulate a crash after N iterations")
    parser.add_argument("--data", type=str, default=None)
    parser.add_argument("--n-train", type=int, default=4000)
    args = parser.parse_args()
    enable_compilation_cache()

    chainermn_tpu.add_global_except_hook()
    comm = chainermn_tpu.create_communicator(args.communicator)

    (x_train, y_train), _ = load_mnist(args.data, args.n_train, 1)
    train = chainermn_tpu.scatter_dataset(
        ArrayDataset(x_train, y_train), comm, shuffle=True, seed=0
    )
    global_batch = args.batchsize * comm.size
    ensure_batch_fits(train, global_batch, comm.size)
    it = chainermn_tpu.SerialIterator(train, global_batch, shuffle=True, seed=1)

    model = MLP(n_units=args.unit)
    variables = comm.bcast_data(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))
    )
    optimizer = chainermn_tpu.create_multi_node_optimizer(optax.adam(1e-3), comm)
    opt_state = jax.device_put(
        optimizer.init(variables["params"]), comm.named_sharding()
    )
    step = jit_train_step(model, optimizer, comm)

    checkpointer = chainermn_tpu.create_multi_node_checkpointer(
        name="mnist_example", comm=comm, path=args.out
    )
    state, iteration = checkpointer.maybe_load(
        {"variables": variables, "opt_state": opt_state, "iterator": it.state_dict()}
    )
    if iteration > 0:
        sharding = comm.named_sharding()
        variables = jax.device_put(state["variables"], sharding)
        opt_state = jax.device_put(state["opt_state"], sharding)
        it.load_state_dict(state["iterator"])
        if comm.rank == 0:
            print(f"resumed from iteration {iteration}")
    elif comm.rank == 0:
        print("fresh start (no common snapshot)")

    while it.epoch < args.epoch:
        images, labels = collate(next(it))
        if len(labels) < global_batch:
            continue
        variables, opt_state, loss = step(variables, opt_state, images, labels)
        iteration += 1
        if iteration % args.frequency == 0:
            checkpointer.save(
                {"variables": variables, "opt_state": opt_state,
                 "iterator": it.state_dict()},
                iteration,
            )
            if comm.rank == 0:
                print(f"iter {iteration:4d}  loss {float(loss):.4f}  [snapshot]")
        if args.stop_at is not None and iteration >= args.stop_at:
            if comm.rank == 0:
                print(f"simulated crash at iteration {iteration}")
            raise SystemExit(1)
    if comm.rank == 0:
        print(f"finished at iteration {iteration}; "
              f"checkpoint stats: {checkpointer.get_stats()}")


if __name__ == "__main__":
    main()
