#!/usr/bin/env python
"""ImageNet training — the reference's benchmark workload.

Parity target: ``[U] examples/imagenet/train_imagenet.py`` (SURVEY.md S2.15
— unverified cite): ResNet-50 (plus alex/googlenet model zoo) under the
pure_nccl communicator with fp16 allreduce and double buffering — the
configuration behind the 15-minute ImageNet run (BASELINE.md). TPU rebuild:
same flag surface, bf16 wire dtype, one fused SPMD step.

Data: ``--train-npz`` with arrays ``x`` (N,H,W,3 uint8) and ``y`` (N,) —
or synthetic ImageNet-shaped data (default) for throughput work.

Run (throughput mode, single host)::

    python examples/imagenet/train_imagenet.py --arch resnet50 \
        --batchsize 128 --iterations 50 --dtype bfloat16 --double-buffering

Run (the "15-minute ImageNet" TRAINING RECIPE, arXiv:1711.04325 — linearly
scaled LR ``0.1 x global_batch/256`` with warmup, label smoothing 0.1, top-1
eval on a held-out shard through the multi-node evaluator)::

    python examples/imagenet/train_imagenet.py --arch resnet50 \
        --batchsize 128 --epoch 90 --dtype bfloat16 --double-buffering \
        --recipe --train-npz /data/imagenet_train.npz
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu import models
from chainermn_tpu.training import jit_train_step
from chainermn_tpu.utils import enable_compilation_cache, ensure_batch_fits

ARCHS = {
    "resnet18": lambda n: models.ResNet18(num_classes=n),
    "resnet34": lambda n: models.ResNet34(num_classes=n),
    "resnet50": lambda n: models.ResNet50(num_classes=n),
    "resnet101": lambda n: models.ResNet101(num_classes=n),
    "resnet152": lambda n: models.ResNet152(num_classes=n),
    "alex": lambda n: models.AlexNet(num_classes=n),
    "googlenet": lambda n: models.GoogLeNet(num_classes=n),
    "vgg16": lambda n: models.VGG16(num_classes=n),
}


class SyntheticImageNet:
    """ImageNet-shaped synthetic records (uint8 images, int labels)."""

    def __init__(self, n: int, size: int = 224, classes: int = 1000, seed: int = 0):
        self._rng = np.random.RandomState(seed)
        self.n, self.size, self.classes = n, size, classes
        # small pool of random images, resampled by index (cheap, no 150GB)
        self._pool = self._rng.randint(0, 256, (64, size, size, 3), np.uint8)
        self._labels = self._rng.randint(0, classes, n).astype(np.int32)

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return self._pool[i % len(self._pool)], self._labels[i]


class NpzImageNet:
    def __init__(self, path: str):
        z = np.load(path)
        self.x, self.y = z["x"], z["y"].astype(np.int32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def collate(batch, dtype):
    from chainermn_tpu.native.dataloader import IMAGENET_MEAN, IMAGENET_STD

    xs, ys = zip(*batch)
    x = np.stack(xs).astype(np.float32) / 255.0
    # per-channel ImageNet normalization (reference subtracts a mean image);
    # constants shared with NativeBatchLoader so both input paths normalize
    # identically
    x = (x - np.array(IMAGENET_MEAN)) / np.array(IMAGENET_STD)
    return x.astype(dtype), np.asarray(ys, np.int32)


def record_source(ds):
    """(base_u8, rows, labels) view of a dataset for zero-copy native
    loading: ``rows[i]`` is sample i's row in ``base_u8`` (SyntheticImageNet
    aliases its small pool; SubDataset shards compose indices)."""
    from chainermn_tpu.datasets import SubDataset

    if isinstance(ds, SubDataset):
        base, rows, labels = record_source(ds._dataset)
        idx = np.asarray(ds.indices)
        return base, rows[idx], labels[idx]
    if isinstance(ds, SyntheticImageNet):
        rows = np.arange(len(ds), dtype=np.int64) % len(ds._pool)
        return ds._pool, rows, ds._labels
    if isinstance(ds, NpzImageNet):
        return ds.x, np.arange(len(ds), dtype=np.int64), ds.y
    raise TypeError(
        f"--native-loader supports the synthetic/npz datasets, got "
        f"{type(ds).__name__}"
    )


def main() -> None:
    parser = argparse.ArgumentParser(description="ChainerMN-TPU example: ImageNet")
    parser.add_argument("--arch", "-a", default="resnet50", choices=sorted(ARCHS))
    parser.add_argument("--batchsize", "-B", type=int, default=32,
                        help="per-participant batch size (reference default 32)")
    parser.add_argument("--epoch", "-E", type=int, default=1)
    parser.add_argument("--iterations", type=int, default=None,
                        help="stop after N iterations (throughput mode)")
    parser.add_argument("--communicator", default="tpu",
                        help="reference 'pure_nccl' maps to 'tpu'")
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["float32", "bfloat16", "float16"],
                        help="allreduce wire dtype (reference allreduce_grad_dtype)")
    parser.add_argument("--double-buffering", action="store_true",
                        help="1-step-stale overlapped gradient averaging")
    parser.add_argument("--mnbn", action="store_true",
                        help="multi-node BatchNorm (cross-replica statistics)")
    parser.add_argument("--train-npz", default=None)
    parser.add_argument("--train-dir", default=None,
                        help="directory of JPEGs in class subfolders "
                             "(root/<class>/*.jpg): decoded by the native "
                             "libjpeg pipeline (PIL fallback), classes "
                             "inferred from the tree")
    parser.add_argument("--n-synthetic", type=int, default=100000)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--classes", type=int, default=1000)
    parser.add_argument("--lr", type=float, default=0.1,
                        help="base LR; under --recipe this is the per-256 "
                             "base of the linear scaling rule")
    parser.add_argument(
        "--recipe", action="store_true",
        help="the 15-minute-run training recipe (arXiv:1711.04325): "
             "LR = lr x global_batch/256 with linear warmup then cosine "
             "decay, label smoothing 0.1, per-epoch top-1 eval on a "
             "held-out shard via the multi-node evaluator",
    )
    parser.add_argument("--warmup-epochs", type=float, default=None,
                        help="LR warmup span (recipe default: 5)")
    parser.add_argument("--label-smoothing", type=float, default=None,
                        help="(recipe default: 0.1)")
    parser.add_argument("--val-frac", type=float, default=None,
                        help="held-out fraction for top-1 eval "
                             "(recipe default: 0.02)")
    parser.add_argument("--native-loader", action=argparse.BooleanOptionalAction,
                        default=None,
                        help="C++ batch assembly (gather + fused uint8->f32 "
                             "normalize, GIL-free threads) with one-batch "
                             "prefetch — the MultiprocessIterator slot. "
                             "Defaults ON under --recipe, where a failed "
                             "extension build degrades (all ranks together) "
                             "to numpy; an EXPLICIT --native-loader fails "
                             "hard instead")
    parser.add_argument("--device-prefetch", type=int, default=0,
                        help="wrap the pre-normalized input stream (native "
                             "C++ or JPEG loader) in a dataflow."
                             "DevicePrefetcher: a producer thread "
                             "device_puts N batches ahead with the step's "
                             "data sharding, so H2D overlaps the step "
                             "(0: feed synchronously)")
    parser.add_argument("--fsdp", action="store_true",
                        help="ZeRO-3 layout: params/grads/moments scattered "
                             "over the data axis, XLA-partitioner-inserted "
                             "gather/scatter (parallel.fsdp); BN statistics "
                             "become global-batch (sync-BN) by construction")
    args = parser.parse_args()
    enable_compilation_cache()

    if args.fsdp and (args.mnbn or args.double_buffering):
        # MNBN's explicit collectives need shard_map axis names, which the
        # FSDP global program doesn't have (its BN is already global-batch);
        # double buffering configures the explicit gradient collective the
        # FSDP step doesn't own.
        raise SystemExit("--fsdp is incompatible with --mnbn/--double-buffering")

    if args.recipe:
        if args.warmup_epochs is None:
            args.warmup_epochs = 5.0
        if args.label_smoothing is None:
            args.label_smoothing = 0.1
        if args.val_frac is None and not args.train_dir:
            args.val_frac = 0.02  # --train-dir has no array split to hold out
    # None = unspecified: the recipe defaults the native loader ON (the
    # measured ~3x assembly win, PERF.md); an explicit True keeps hard
    # errors, an explicit False (--no-native-loader) forces numpy
    native_explicit = args.native_loader is True
    if args.native_loader is None:
        args.native_loader = bool(args.recipe)
    args.warmup_epochs = args.warmup_epochs or 0.0
    args.label_smoothing = args.label_smoothing or 0.0
    args.val_frac = args.val_frac or 0.0

    chainermn_tpu.add_global_except_hook()
    # a non-float32 wire dtype is only meaningful for the tpu/pure_nccl
    # strategy; create_communicator raises on unsupported combinations
    # rather than silently running f32 (reference: pure_nccl-only flag)
    comm = chainermn_tpu.create_communicator(
        args.communicator,
        # FSDP has no explicit gradient collective to configure a wire dtype
        # on (the partitioner reduces in the gradient's own dtype)
        allreduce_grad_dtype=None if (args.dtype == "float32" or args.fsdp)
        else args.dtype,
    )
    if comm.rank == 0:
        wire = "n/a (fsdp: partitioner reduces in the gradient dtype)" \
            if args.fsdp else args.dtype
        print(f"arch={args.arch} communicator={args.communicator} "
              f"wire-dtype={wire} double_buffering={args.double_buffering} "
              f"devices={comm.size}")

    jpeg_it = None
    if args.train_dir:
        # JPEG-directory input: the loader shards the FILE LIST per process
        # and decodes via the native libjpeg pipeline (chainermn_tpu.native
        # .jpeg), so the array-dataset scatter machinery is bypassed.
        if args.train_npz:
            raise SystemExit("--train-dir and --train-npz are exclusive")
        if args.val_frac:
            raise SystemExit("--val-frac needs an array dataset and was "
                             "passed explicitly; with --train-dir hold out "
                             "a separate val/ tree instead")
        from chainermn_tpu.native import jpeg as jpeg_mod

        jpeg_it = jpeg_mod.JpegDirectoryLoader(
            args.train_dir, args.batchsize * comm.size,
            image_size=args.image_size, shuffle=True, seed=1,
            rank=jax.process_index(), size=comm.process_size,
        )
        args.classes = len(jpeg_it.class_names)  # labels come from the tree
        if comm.rank == 0:
            print(f"input pipeline: JPEG directory, "
                  f"{'native libjpeg' if jpeg_mod.native_available() else 'PIL fallback'}"
                  f", {args.classes} classes, "
                  f"{len(jpeg_it) * args.batchsize * comm.size} imgs/shard-epoch")
        dataset = val = train = val_shard = None
    else:
        dataset = (NpzImageNet(args.train_npz) if args.train_npz
                   else SyntheticImageNet(args.n_synthetic, args.image_size,
                                          args.classes))
        val = None
    if dataset is not None and args.val_frac:
        # hold out the tail as the eval shard (deterministic split so every
        # process agrees before scattering)
        from chainermn_tpu.datasets import SubDataset

        n_val = max(1, int(len(dataset) * args.val_frac))
        val = SubDataset(dataset, range(len(dataset) - n_val, len(dataset)))
        dataset = SubDataset(dataset, range(len(dataset) - n_val))
    if dataset is not None:
        train = chainermn_tpu.scatter_dataset(dataset, comm, shuffle=True,
                                              seed=0)
        val_shard = (chainermn_tpu.scatter_dataset(val, comm, shuffle=False)
                     if val is not None else None)

    model_fn = ARCHS[args.arch]
    model = model_fn(args.classes)
    if args.mnbn:
        import dataclasses
        import functools
        from chainermn_tpu.links import MultiNodeBatchNormalization
        if hasattr(model, "norm"):
            # ResNet takes a norm factory directly — inject sync-BN with the
            # baseline BN hyperparameters so --mnbn isolates the statistics
            # change (not a changed epsilon/dtype)
            model = dataclasses.replace(model, norm=functools.partial(
                MultiNodeBatchNormalization, communicator=comm,
                momentum=0.9, epsilon=1e-5, dtype=model.compute_dtype))
        else:
            model = chainermn_tpu.create_mnbn_model(model, comm)

    global_batch = args.batchsize * comm.size
    if jpeg_it is not None:
        # the JPEG loader yields ready float32 batches just like
        # NativeBatchLoader -> the loop's pre-normalized branch
        it = jpeg_it
        batches = iter(it)
        pre_normalized = True
    else:
        ensure_batch_fits(train, global_batch, comm.size)
        if args.native_loader:
            try:
                from chainermn_tpu.native.dataloader import NativeBatchLoader

                # zero-copy view of the shard: the C++ path gathers rows from
                # the base array, fuses the normalize, prefetches a batch ahead
                base, rows, ys = record_source(train)
                native_it = NativeBatchLoader(base, ys, global_batch, rows=rows,
                                              shuffle=True, seed=1)
            except Exception as e:  # toolchain/build failure on THIS rank
                # per-rank diagnostic: rank 0's banner can't see this failure
                print(f"[rank {comm.rank}] native loader unavailable "
                      f"({type(e).__name__}: {e})", flush=True)
                native_it = None
            # the step/evaluate cadence is collective — every rank must take
            # the SAME input path, so agree before choosing (one rank's build
            # failure would otherwise desync step counts and hang the job).
            # ALWAYS agree first, even on the explicit-flag failure path: a
            # lone rank raising before the collective would strand the others
            # inside it — fail hard on every rank together instead.
            args.native_loader = comm.allreduce_obj(
                native_it is not None, lambda a, b: a and b)
            if native_explicit and not args.native_loader:
                raise SystemExit(
                    "--native-loader was explicitly requested but the native "
                    "extension is unavailable on at least one rank (see the "
                    "per-rank diagnostics above); an explicit opt-in must not "
                    "silently measure the numpy path")
            if args.native_loader:
                it = native_it
                batches = iter(it)
        if not args.native_loader:
            it = chainermn_tpu.SerialIterator(train, global_batch, shuffle=True, seed=1)
        pre_normalized = args.native_loader
        if comm.rank == 0:
            print(f"input pipeline: "
                  f"{'native C++ prefetch' if args.native_loader else 'numpy'}")

    sample = jnp.zeros((2, args.image_size, args.image_size, 3), jnp.bfloat16)
    variables = comm.bcast_data(
        model.init(jax.random.PRNGKey(0), sample, train=True)
    )
    steps_per_epoch = (max(1, len(it)) if jpeg_it is not None else
                       max(1, (len(train) * comm.process_size) // global_batch))
    if args.warmup_epochs:
        # linear scaling rule + warmup (arXiv:1711.04325): ramp to
        # lr x global_batch/256 over the warmup span, cosine-decay to 0.
        # The x global_batch/256 multiplier applies only under --recipe —
        # a bare --warmup-epochs must not silently rescale the user's --lr.
        scaled_lr = (args.lr * global_batch / 256.0 if args.recipe
                     else args.lr)
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=scaled_lr,
            warmup_steps=max(1, int(args.warmup_epochs * steps_per_epoch)),
            decay_steps=max(2, args.epoch * steps_per_epoch),
        )
    else:
        lr = args.lr
    if args.fsdp:
        from chainermn_tpu.parallel import fsdp_shard, jit_fsdp_train_step

        optimizer = optax.sgd(lr, momentum=0.9)  # no multi-node wrapper:
        # the gradient mean falls out of the global-batch loss (fsdp.py)
        variables = fsdp_shard(variables, comm)
        opt_state = fsdp_shard(jax.jit(optimizer.init)(variables["params"]), comm)
        step = jit_fsdp_train_step(
            model, optimizer, comm, train_kwargs={"train": True},
            label_smoothing=args.label_smoothing,
        )
    else:
        optimizer = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(lr, momentum=0.9), comm,
            double_buffering=args.double_buffering,
        )
        opt_state = jax.device_put(
            optimizer.init(variables["params"]), comm.named_sharding()
        )
        step = jit_train_step(
            model, optimizer, comm, train_kwargs={"train": True},
            label_smoothing=args.label_smoothing,
        )

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(variables["params"]))
    if comm.rank == 0:
        print(f"{n_params / 1e6:.1f}M params, global batch {global_batch}")

    evaluate = None
    if val_shard is not None:
        from jax.sharding import PartitionSpec as P

        if args.fsdp:
            # variables live scattered; a global program gathers them at use
            eval_forward = jax.jit(lambda v, x: model.apply(v, x, train=False))
        else:
            eval_forward = jax.jit(comm.shard_map(
                lambda v, x: model.apply(v, x, train=False),
                in_specs=(P(), comm.data_spec), out_specs=comm.data_spec,
            ))

        def _local_eval():
            # top-1 over this process's held-out shard; the multi-node
            # evaluator averages the dicts across processes (SURVEY.md S2.14)
            correct = n = 0
            for batch in chainermn_tpu.SerialIterator(
                val_shard, global_batch, repeat=False, shuffle=False
            ):
                x, y = collate(batch, np.float32)
                if len(y) < global_batch:  # pad ragged tail to jitted shape
                    pad = global_batch - len(y)
                    x = np.concatenate(
                        [x, np.zeros((pad,) + x.shape[1:], x.dtype)]
                    )
                logits = np.asarray(eval_forward(variables, x))
                pred = logits[: len(y)].argmax(-1)
                correct += int((pred == y).sum())
                n += len(y)
            return {"validation/main/accuracy": correct / max(n, 1)}

        evaluate = chainermn_tpu.create_multi_node_evaluator(_local_eval, comm)

    if args.device_prefetch:
        if not pre_normalized:
            raise SystemExit(
                "--device-prefetch wraps the pre-normalized input stream "
                "(native C++ or JPEG loader); the numpy SerialIterator "
                "path collates inside the loop — use --native-loader or "
                "--train-dir")
        from chainermn_tpu.dataflow import DevicePrefetcher

        # epoch/is_new_epoch on the wrapper track DELIVERED batches, so
        # the epoch-cadenced eval below keys off the wrapper, not the
        # producer-paced loader
        batches = it = DevicePrefetcher(
            it, depth=args.device_prefetch,
            sharding=comm.named_sharding(*comm.data_spec),
            name="imagenet")
        if comm.rank == 0:
            print(f"device prefetch: depth {args.device_prefetch} "
                  "(H2D on a producer thread)")

    iteration = 0
    t0 = time.time()
    imgs = 0
    loss = jnp.float32(0)  # stays 0 if every batch is a ragged tail
    while it.epoch < args.epoch:
        if pre_normalized:
            images, labels = next(batches)  # pre-normalized, never ragged
        else:
            images, labels = collate(next(it), np.float32)
        if len(labels) == global_batch:  # ragged tails skip the jitted step
            variables, opt_state, loss = step(variables, opt_state, images, labels)
            iteration += 1
            imgs += global_batch
            if iteration == 1:
                jax.block_until_ready(loss)
                t0, imgs = time.time(), 0  # exclude compile from throughput
                if comm.rank == 0:
                    print(f"compiled; first loss {float(loss):.3f}")
            elif iteration % 20 == 0 and comm.rank == 0:
                dt = time.time() - t0
                print(f"iter {iteration:5d}  loss {float(loss):.3f}  "
                      f"{imgs / dt:.1f} img/s ({imgs / dt / comm.size:.1f}/chip)")
        if it.is_new_epoch and evaluate is not None:
            metrics = evaluate()
            if comm.rank == 0:
                print(f"epoch {it.epoch:3d}  "
                      f"top-1 {metrics['validation/main/accuracy']:.4f}")
        if args.iterations and iteration >= args.iterations:
            break
    jax.block_until_ready(loss)
    if args.device_prefetch:
        it.close()  # stop + join the producer thread
    if evaluate is not None and not it.is_new_epoch:
        # exited mid-epoch (--iterations): still report a final top-1
        metrics = evaluate()
        if comm.rank == 0:
            print(f"final top-1 {metrics['validation/main/accuracy']:.4f}")
    if comm.rank == 0 and imgs:
        dt = time.time() - t0
        print(f"done: {iteration} iterations, {imgs / dt:.1f} img/s "
              f"({imgs / dt / comm.size:.2f} img/s/chip)")


if __name__ == "__main__":
    main()
