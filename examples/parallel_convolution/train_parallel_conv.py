#!/usr/bin/env python
"""Channel-parallel convolution — tensor parallelism from differentiable
collectives.

Parity target: ``[U] examples/parallel_convolution/`` (SURVEY.md S2.15/S2.16 —
unverified cite): the reference's only tensor-parallel construct, a CIFAR CNN
whose conv layers' channels are split across ranks and stitched with the
differentiable ``alltoall``/``allgather`` function nodes; the backward runs
the transposed collectives.

TPU re-design (one SPMD program over the mesh):

- the batch enters **batch-sharded** (how data arrives in practice);
- an ``alltoall`` re-shards activations batch->channel (split the channel
  axis, concatenate the batch axis — the Ulysses collective shape applied to
  channels) so the parallel section sees the FULL batch with ``C/n`` channels
  per rank;
- each parallel conv holds only its ``F/n`` out-channel slice of the kernel
  (the global kernel array is sharded over the mesh on its out-feature axis);
  the full input is assembled per layer with a tiled ``allgather`` whose
  autodiff transpose routes every rank's cotangents back to the owning
  channel shard — the reference's hand-written backward, derived;
- a closing ``alltoall`` returns to batch-sharded for the replicated head and
  the per-shard loss.

Gradients: channel-sharded kernels get their full cross-rank gradient through
the collective transposes; replicated (conv1/head) parameters need an explicit
``psum`` of the per-shard contributions — the example does both and documents
which is which.

Run (2+ emulated devices)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/parallel_convolution/train_parallel_conv.py --check
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import chainermn_tpu
from chainermn_tpu.utils import enable_compilation_cache, ensure_batch_fits


# --------------------------------------------------------------------------- #
# Model: conv1 (replicated) -> pconv2 -> pconv3 (channel-parallel) -> head    #
# --------------------------------------------------------------------------- #

CH1, CH2, CH3 = 32, 64, 64


def init_params(key, image_size: int, classes: int):
    """Full (unsharded) parameters; the pconv kernels' out-feature axis is
    what gets sharded over the mesh at train time."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    he = jax.nn.initializers.he_normal()
    feat = (image_size // 4) * (image_size // 4) * CH3
    return {
        "conv1": {"w": he(k1, (3, 3, 3, CH1)), "b": jnp.zeros((CH1,))},
        "pconv2": {"w": he(k2, (3, 3, CH1, CH2)), "b": jnp.zeros((CH2,))},
        "pconv3": {"w": he(k3, (3, 3, CH2, CH3)), "b": jnp.zeros((CH3,))},
        "head": {
            "w": he(k4, (feat, classes)),
            "b": jnp.zeros((classes,)),
        },
    }


def param_specs(axis: str):
    """Sharding: pconv kernels/biases split on the out-channel axis; the rest
    replicated (the reference's 'every rank holds a channel slice' layout)."""
    return {
        "conv1": {"w": P(), "b": P()},
        "pconv2": {"w": P(None, None, None, axis), "b": P(axis)},
        "pconv3": {"w": P(None, None, None, axis), "b": P(axis)},
        "head": {"w": P(), "b": P()},
    }


def _conv(x, p, stride: int = 1):
    y = lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + p["b"]


def _pool(x):
    return lax.reduce_window(
        x, 0.0, lax.add, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    ) / 4.0


def serial_forward(params, x):
    """Single-device reference semantics: what the parallel program must
    reproduce bit-for-bit-ish (fp tolerance) with the same weights."""
    h = jax.nn.relu(_conv(x, params["conv1"]))
    h = _pool(h)
    h = jax.nn.relu(_conv(h, params["pconv2"]))
    h = jax.nn.relu(_conv(h, params["pconv3"]))
    h = _pool(h)
    h = h.reshape((h.shape[0], -1))
    return h @ params["head"]["w"] + params["head"]["b"]


# --------------------------------------------------------------------------- #
# Parallel program (runs inside comm.shard_map)                               #
# --------------------------------------------------------------------------- #

def _batch_to_channel(h, comm):
    """[N/n, H, W, C] batch-sharded -> [N, H, W, C/n] channel-sharded."""
    n = comm.size
    nl, hh, ww, c = h.shape
    h = h.reshape(nl, hh, ww, n, c // n).transpose(3, 0, 1, 2, 4)
    h = chainermn_tpu.functions.alltoall(h, comm)  # leading axis: peers
    return h.reshape(n * nl, hh, ww, c // n)


def _channel_to_batch(h, comm):
    """[N, H, W, C/n] channel-sharded -> [N/n, H, W, C] batch-sharded."""
    n = comm.size
    nn_, hh, ww, cl = h.shape
    h = h.reshape(n, nn_ // n, hh, ww, cl)
    h = chainermn_tpu.functions.alltoall(h, comm)
    return h.transpose(1, 2, 3, 0, 4).reshape(nn_ // n, hh, ww, n * cl)


def parallel_forward(params, x, comm):
    """Per-rank body: ``params`` are the LOCAL views (pconv slices), ``x`` is
    the local batch shard."""
    h = jax.nn.relu(_conv(x, params["conv1"]))  # batch-sharded, replicated w
    h = _pool(h)
    h = _batch_to_channel(h, comm)              # full batch, C/n channels
    # each parallel conv: assemble full input channels, compute local slice
    full = chainermn_tpu.functions.allgather(h, comm)  # [n, N, H, W, C/n]
    full = jnp.moveaxis(full, 0, -2).reshape(h.shape[:3] + (-1,))
    h = jax.nn.relu(_conv(full, params["pconv2"]))     # -> [N, H, W, CH2/n]
    full = chainermn_tpu.functions.allgather(h, comm)
    full = jnp.moveaxis(full, 0, -2).reshape(h.shape[:3] + (-1,))
    h = jax.nn.relu(_conv(full, params["pconv3"]))     # -> [N, H, W, CH3/n]
    h = _pool(h)
    h = _channel_to_batch(h, comm)              # back to batch shards, full C
    h = h.reshape((h.shape[0], -1))
    return h @ params["head"]["w"] + params["head"]["b"]


def make_train_step(comm, optimizer):
    axis = comm.axis_name

    def body(params, opt_state, images, labels):
        def loss_fn(p):
            logits = parallel_forward(p, images, comm)
            local = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
            return comm.allreduce(local, "mean")  # global mean loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # channel-sharded kernels already carry their full cross-rank gradient
        # (collective transposes); replicated params hold only the local
        # shard's contribution scaled 1/n -> sum across ranks.
        for name in ("conv1", "head"):
            grads[name] = jax.tree_util.tree_map(
                lambda g: comm.allreduce(g, "sum"), grads[name]
            )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # optimizer state: plain SGD is stateless (EmptyState), so a replicated
    # P() prefix-spec covers it; a param-shaped state (adam moments) would
    # need the same sharding tree as the params.
    specs = param_specs(axis)
    sm = comm.shard_map(
        body,
        in_specs=(specs, P(), comm.data_spec, comm.data_spec),
        out_specs=(specs, P(), P()),
    )
    return jax.jit(sm, donate_argnums=(0, 1))


# --------------------------------------------------------------------------- #

def synthetic_cifar(n: int, image_size: int, classes: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    templates = rng.rand(classes, image_size, image_size, 3).astype(np.float32)
    y = rng.randint(0, classes, size=n).astype(np.int32)
    x = templates[y] + 0.25 * rng.randn(n, image_size, image_size, 3).astype(np.float32)
    return np.clip(x, 0.0, 1.0), y


def main() -> None:
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU example: channel-parallel convolution"
    )
    parser.add_argument("--batchsize", "-b", type=int, default=64)
    parser.add_argument("--epoch", "-e", type=int, default=5)
    parser.add_argument("--image-size", type=int, default=32)
    parser.add_argument("--classes", type=int, default=10)
    parser.add_argument("--n-train", type=int, default=2048)
    parser.add_argument("--check", action="store_true",
                        help="assert parallel forward == serial forward "
                             "with the same weights before training")
    args = parser.parse_args()
    enable_compilation_cache()

    chainermn_tpu.add_global_except_hook()
    comm = chainermn_tpu.create_communicator("tpu")
    n = comm.size
    for ch in (CH1, CH2, CH3):
        if ch % n:
            raise SystemExit(f"channel counts {CH1}/{CH2}/{CH3} must divide "
                             f"the device count ({n})")

    params = init_params(jax.random.PRNGKey(0), args.image_size, args.classes)
    specs = param_specs(comm.axis_name)
    params = jax.device_put(
        params, jax.tree_util.tree_map(
            lambda s: comm.named_sharding(*s), specs,
            is_leaf=lambda s: isinstance(s, P),
        )
    )

    x, y = synthetic_cifar(args.n_train, args.image_size, args.classes)
    ensure_batch_fits(x, args.batchsize)

    if args.check:
        xb = jnp.asarray(x[: args.batchsize])
        want = serial_forward(jax.device_get(params), xb)
        got = jax.jit(comm.shard_map(
            lambda p, xs: parallel_forward(p, xs, comm),
            in_specs=(specs, comm.data_spec), out_specs=comm.data_spec,
        ))(params, xb)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        if comm.rank == 0:
            print(f"parity check OK: parallel({n} ranks) == serial forward")

    optimizer = optax.sgd(5e-2)  # stateless: see make_train_step spec note
    opt_state = jax.device_put(
        optimizer.init(jax.device_get(params)), comm.named_sharding()
    )
    step = make_train_step(comm, optimizer)

    steps_per_epoch = max(1, args.n_train // args.batchsize)
    t0 = time.time()
    first = last = None
    for epoch in range(1, args.epoch + 1):
        perm = np.random.RandomState(epoch).permutation(args.n_train)
        losses = []
        for it in range(steps_per_epoch):
            idx = perm[it * args.batchsize:(it + 1) * args.batchsize]
            if len(idx) < args.batchsize:
                continue
            params, opt_state, loss = step(
                params, opt_state, jnp.asarray(x[idx]), jnp.asarray(y[idx])
            )
            losses.append(float(loss))
        mean_loss = float(np.mean(losses))
        first = first if first is not None else mean_loss
        last = mean_loss
        if comm.rank == 0:
            print(f"epoch {epoch:3d}  train/loss {mean_loss:.4f}")
    if comm.rank == 0:
        print(f"done in {time.time() - t0:.1f}s  "
              f"(ranks={n}, loss {first:.3f} -> {last:.3f})")


if __name__ == "__main__":
    main()
