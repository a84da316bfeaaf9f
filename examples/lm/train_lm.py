#!/usr/bin/env python
"""Language-model training — the long-context / MoE extension workload.

No counterpart in the reference (it predates attention; SURVEY.md S2.16
marks SP/CP/EP absent) — this script is the user-facing entry to the
TPU-first extensions: sequence-parallel ring/Ulysses attention
(``--seq-parallel``), Pallas flash attention (``--attention flash``), and
expert-parallel MoE blocks (``--moe-experts N``).

Synthetic data: a deterministic k-th order Markov character stream — real
next-token structure (loss can drop well below uniform) with zero I/O.

Run (2+ emulated devices)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/train_lm.py --iterations 30 --moe-experts 8
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/lm/train_lm.py --iterations 30 --seq-parallel \
        --attention ring --seq-len 256
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu import monitor
from chainermn_tpu.models import TransformerLM
from chainermn_tpu.training import jit_lm_train_step
from chainermn_tpu.utils import enable_compilation_cache


def _dump_traces(args) -> None:
    """``--trace-out``: export whatever span trees the run retained
    (training.fit and the resilient trainer trace every step through the
    default tracer) as a Perfetto-loadable Chrome trace file."""
    if not getattr(args, "trace_out", ""):
        return
    tracer = monitor.get_tracer()
    n = len(tracer.finished())
    tracer.export_chrome(args.trace_out)
    print(f"wrote {n} trace(s) to {args.trace_out} "
          "(load in chrome://tracing or ui.perfetto.dev)")


def markov_stream(n_tokens: int, vocab: int, order: int = 2, seed: int = 0):
    """Deterministic k-th order Markov chain over ``vocab`` symbols."""
    rng = np.random.RandomState(seed)
    table = rng.randint(0, vocab, (vocab,) * order)
    out = np.zeros(n_tokens, np.int32)
    out[:order] = rng.randint(0, vocab, order)
    for i in range(order, n_tokens):
        ctx = tuple(out[i - order : i])
        # mostly-deterministic transitions with a little noise
        out[i] = table[ctx] if rng.rand() < 0.9 else rng.randint(0, vocab)
    return out


def _stream_data(args):
    """(tokens, targets, n_seq) arrays from the Markov stream — shared by
    every mode's data prep."""
    stream = markov_stream(args.n_tokens, args.vocab)
    n_seq = (len(stream) - 1) // args.seq_len
    toks = stream[: n_seq * args.seq_len].reshape(n_seq, args.seq_len)
    tgts = stream[1 : n_seq * args.seq_len + 1].reshape(n_seq, args.seq_len)
    return toks, tgts, n_seq


def _serve_samples(args, comm, model, params, tokens_all):
    """Training-to-serving in one script: push ``--serve-samples``
    continuations of the trained model through the serving fast path
    (bucketed batched prefill + ref-counted prefix KV reuse,
    :mod:`chainermn_tpu.serving`). All prompts share the stream's opening
    context, so after the first admission every later one hits the prefix
    cache and prefills only its ragged tail — the shared-system-prompt
    traffic shape the cache exists for. Skipped for sharded-model modes
    (rebuild without sequence/tensor sharding to serve; see
    ``serve_lm.py``)."""
    if comm.rank != 0:
        return
    if args.seq_parallel or args.tensor_parallel:
        print("serve-samples: skipped (sequence/tensor-sharded training "
              "model; rebuild dense for inference — see serve_lm.py)")
        return
    from chainermn_tpu.serving import ServingClient, ServingEngine

    infer = (model.clone(moe_impl="gshard") if model.moe_experts
             else model)
    params = jax.device_get(params)           # host copy: plain-jit serve
    ctx_len = min(args.seq_len // 2, 24)
    ctx = np.asarray(tokens_all[0][:ctx_len], np.int32)
    tail_src = np.asarray(tokens_all[1], np.int32)
    bucket_small = 8
    prefill_len = ctx_len + bucket_small
    engine = ServingEngine(
        infer, params, n_slots=4,
        prefill_buckets=(bucket_small, prefill_len), prefill_batch=4,
        prefix_cache_blocks=32, prefix_block_size=4,
        cache_len=prefill_len + 16)
    engine.warmup()
    n = args.serve_samples
    print(f"serving {n} shared-context continuations "
          f"(ctx={ctx_len} tokens, prefix-cached, bucketed prefill):")
    with ServingClient(engine) as client:
        reqs = [client.submit(
            np.concatenate([ctx, tail_src[: 1 + i % bucket_small]]), 12,
            rng=jax.random.PRNGKey(i)) for i in range(n)]
        for i, req in enumerate(reqs):
            req.wait(timeout=300)
            print(f"  sample {i}: ...{[int(t) for t in req.output[-8:]]}")
    stats = engine.prefix_stats()
    print(f"prefix cache: hit_rate={stats['hit_rate']} "
          f"hits={stats['hits']} inserted_blocks="
          f"{stats['inserted_blocks']}; executables="
          f"{engine.compile_counts_detailed()} (zero recompiles)")


class _OnlinePublisher:
    """``--publish-to engine``: the online train→serve loop (ISSUE 10).

    A live serving engine (initial weights) plus its background client
    thread come up BEFORE training starts; every ``--publish-every``
    iterations the freshly trained params hot-swap in through the deploy
    version fence — the client thread drains the fence, which is what
    makes the blocking ``publish`` from the training loop safe — a
    continuation samples at the new version, and training continues.
    The jit cache is asserted unchanged across every swap at close."""

    def __init__(self, args, model, params, tokens_all) -> None:
        from chainermn_tpu.deploy import WeightPublisher
        from chainermn_tpu.serving import ServingClient, ServingEngine

        infer = (model.clone(moe_impl="gshard") if model.moe_experts
                 else model)
        ctx_len = min(args.seq_len // 2, 16)
        self._ctx = np.asarray(tokens_all[0][:ctx_len], np.int32)
        self.every = args.publish_every or max(1, args.iterations // 2)
        self._engine = ServingEngine(
            infer, jax.device_get(params), n_slots=2,
            prefill_len=ctx_len, cache_len=ctx_len + 16)
        self._engine.warmup()
        self._client = ServingClient(self._engine)
        self._pub = WeightPublisher(self._engine, self._client.scheduler)
        self._counts = dict(self._engine.compile_counts_detailed())
        self._sample("serving v0 (initial weights)")

    def _sample(self, label: str) -> None:
        out = self._client.generate(
            self._ctx, 12,
            rng=jax.random.PRNGKey(self._engine.weight_version),
            timeout=300)
        print(f"{label}: ...{[int(t) for t in out[-8:]]}")

    def publish(self, it: int, params) -> None:
        # host copy, like --serve-samples: the engine runs plain-jit
        # uncommitted leaves and the publisher re-places to match them
        v = self._pub.publish(jax.device_get(params), step=it,
                              timeout=120.0)
        self._sample(f"published v{v} at iter {it}")

    def close(self) -> None:
        assert dict(self._engine.compile_counts_detailed()) == self._counts
        self._client.close()
        print(f"publish-to engine: weight_version="
              f"{self._engine.weight_version}, zero recompiles across "
              "swaps")


def _save_snapshot(args, comm, model, params) -> None:
    """``--snapshot-to``: step-stamped sharded snapshot of the trained
    params with the resharding manifest (mesh shape, TP degree, head
    geometry) — what ``serve_lm.py --reshard-from`` consumes, on any
    mesh shape or TP degree."""
    from chainermn_tpu.deploy import snapshot_meta
    from chainermn_tpu.extensions.sharded_checkpoint import (
        ShardedCheckpointer,
    )

    meta = snapshot_meta(comm=comm, model=model)
    with ShardedCheckpointer(args.snapshot_to) as cp:
        cp.save(args.iterations, {"params": params}, meta=meta)
    if comm.rank == 0:
        print(f"snapshot -> {args.snapshot_to} (step {args.iterations}, "
              f"tp_degree={meta.get('tp_degree', 1)})")


def _drop_suffix(acc) -> str:
    """Footer fragment for the aggregated MoE drop telemetry ('' when the
    run had no MoE steps) — shared by every mode's final log line."""
    s = acc.summary()
    if not s["steps"]:
        return ""
    return (f"  moe_drop mean {s['moe_drop_frac_mean']:.1%} "
            f"max {s['moe_drop_frac_max']:.1%}")


def _sequential_train_loop(args, comm, step, params, opt_state,
                           toks, tgts, n_seq, batch):
    """The shared strided train/telemetry loop for the pipeline and gspmd
    modes (no shuffling): one place for the compile-time exclusion, tok/s
    logging, MoE drop aggregation, and the final footer. Steps may return
    3-tuples (pipeline) or the uniform 4-tuple (gspmd)."""
    from chainermn_tpu.parallel import MoeStatsAccumulator

    t0, seen, first, loss = time.time(), 0, None, None
    acc = MoeStatsAccumulator()
    for it in range(1, args.iterations + 1):
        i = (it * batch) % max(1, n_seq - batch)
        out = step(
            params, opt_state, jnp.asarray(toks[i : i + batch]),
            jnp.asarray(tgts[i : i + batch]))
        params, opt_state, loss = out[:3]
        acc.update(out[3] if len(out) > 3 else {})
        if it == 1:
            jax.block_until_ready(loss)
            first = float(loss)
            t0, seen = time.time(), 0
            if comm.rank == 0:
                print(f"compiled; first loss {first:.3f}")
        seen += batch * args.seq_len
        if it % 20 == 0 and comm.rank == 0:
            print(f"iter {it:4d}  loss {float(loss):.3f}  "
                  f"{seen / (time.time() - t0):.0f} tok/s")
    if comm.rank == 0 and loss is not None:
        print(f"done: loss {first:.3f} -> {float(loss):.3f}"
              f"{_drop_suffix(acc)}")
    return params, opt_state


def run_gspmd(args, comm) -> None:
    """Megatron weights-at-rest: the DENSE TransformerLM under plain jit,
    params + optimizer state sharded ~1/n per device (parallel.gspmd);
    MoE uses the gshard einsum-dispatch twin."""
    from chainermn_tpu.parallel import (
        gspmd_lm_train_step,
        megatron_opt_shard,
        megatron_shard,
    )

    model = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers,
        max_len=args.max_len or max(args.seq_len, 512),
        attention=args.attention,  # 'full' or 'flash' (guarded in main)
        moe_experts=args.moe_experts, moe_impl="gshard",
        moe_top_k=args.moe_top_k,
        remat=args.remat,
        compute_dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
    )
    toks, tgts, n_seq = _stream_data(args)
    batch = args.batchsize
    if n_seq < batch:
        raise SystemExit(f"need >= {batch} sequences, have {n_seq}")

    params = megatron_shard(
        model.init(jax.random.PRNGKey(0), jnp.asarray(toks[:1])), comm)
    optimizer = optax.adam(args.lr)
    opt_state = megatron_opt_shard(
        optimizer, jax.jit(optimizer.init)(params), params, comm)
    step = gspmd_lm_train_step(model, optimizer, comm)

    def frac(tree):
        tot = loc = 0
        for _, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if hasattr(leaf, "sharding") and leaf.shape:
                tot += leaf.size
                loc += int(np.prod(leaf.sharding.shard_shape(leaf.shape)))
        return loc / max(tot, 1)

    if comm.rank == 0:
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        print(f"{n_params / 1e6:.2f}M params  gspmd megatron layout  "
              f"per-device fraction: params {frac(params):.3f}, "
              f"opt {frac(opt_state):.3f} (1/n = {1 / comm.size:.3f})")
    _sequential_train_loop(args, comm, step, params, opt_state,
                           toks, tgts, n_seq, batch)


def run_pipeline(args, comm) -> None:
    """Pipeline-parallel LM: n_stages = mesh size, one causal transformer
    block resident per rank, stage params stacked P(axis); the GPipe
    fill-drain schedule microbatches each step (ops.pipeline)."""
    from chainermn_tpu.ops import (
        init_pipeline_lm,
        jit_pp_lm_train_step,
        make_pipeline_lm,
        pp_lm_opt_init,
    )

    n_stages = comm.size
    mods = make_pipeline_lm(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_stages=n_stages, max_len=args.max_len or max(args.seq_len, 512),
        compute_dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
    )
    toks, tgts, n_seq = _stream_data(args)
    batch = args.batchsize * args.microbatches
    if n_seq < batch:
        raise SystemExit(f"need >= {batch} sequences, have {n_seq}")

    params = init_pipeline_lm(
        mods, jax.random.PRNGKey(0), jnp.asarray(toks[:1]), n_stages)
    optimizer = optax.adam(args.lr)
    opt_state = pp_lm_opt_init(optimizer, params)
    step = jit_pp_lm_train_step(mods, optimizer, comm,
                                n_microbatches=args.microbatches)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    bubble = (n_stages - 1) / (args.microbatches + n_stages - 1)
    if comm.rank == 0:
        print(f"{n_params / 1e6:.2f}M params  pipeline stages={n_stages} "
              f"microbatches={args.microbatches} "
              f"(bubble fraction {bubble:.1%})")
    _sequential_train_loop(args, comm, step, params, opt_state,
                           toks, tgts, n_seq, batch)


def run_resilient(args, comm, step, params, opt_state,
                  tokens_all, targets_all, n_seq, batch) -> None:
    """``--resume``: the same jitted step driven by
    :func:`chainermn_tpu.resilience.resilient_fit` — periodic snapshots of
    (params, optimizer state, iterator position), a step-level exception
    boundary that restores the newest intact snapshot on failure, and
    cross-launch resume: rerunning the command continues from where the
    last launch stopped, on the same loss trajectory."""
    import chainermn_tpu.resilience as resilience

    ckpt = chainermn_tpu.create_multi_node_checkpointer(
        "train_lm", comm, path=args.checkpoint_dir)
    # drop the ragged tail (as the non-resume loop's generator does): the
    # sharded step needs every batch exactly `batch` rows
    it = chainermn_tpu.SerialIterator(
        list(range(n_seq - n_seq % batch)), batch_size=batch,
        shuffle=True, seed=1)

    def step_fn(state, sel):
        sel = np.asarray(sel)
        p, o, loss, _ = step(state["params"], state["opt_state"],
                             jnp.asarray(tokens_all[sel]),
                             jnp.asarray(targets_all[sel]))
        return {"params": p, "opt_state": o, "loss": float(loss)}

    def restore_hook(state):
        # snapshots hold host arrays; put them back with the step's
        # (replicated) shardings so the resumed trajectory is bit-exact
        return {
            "params": jax.device_put(state["params"],
                                     comm.named_sharding()),
            "opt_state": jax.device_put(state["opt_state"],
                                        comm.named_sharding()),
            "loss": state["loss"],
        }

    def on_step(i, state):
        if (i + 1) % 20 == 0 and comm.rank == 0:
            print(f"iter {i + 1:4d}  loss {state['loss']:.3f}")

    injector = None
    if args.inject_fault:
        injector = resilience.FaultInjector(seed=0)
        injector.arm("trainer.step", kind="raise",
                     after=args.inject_fault, times=1)
        injector.install()
    try:
        state, report = resilience.resilient_fit(
            step_fn, {"params": params, "opt_state": opt_state,
                      "loss": None},
            it, args.iterations, ckpt, save_every=args.save_every,
            restore_hook=restore_hook, on_step=on_step,
            async_save=args.async_save)
    finally:
        if injector is not None:
            injector.uninstall()
    if comm.rank == 0:
        mttr = (f"  mttr {report['mttr_s'][0] * 1e3:.0f}ms"
                if report["mttr_s"] else "")
        print(f"done: loss {state['loss']:.3f}  resumed_from "
              f"{report['resumed_from']}  failures {report['failures']}  "
              f"restores {report['restores']}{mttr}")


def main() -> None:
    parser = argparse.ArgumentParser(description="ChainerMN-TPU example: LM")
    parser.add_argument("--vocab", type=int, default=64)
    parser.add_argument("--d-model", type=int, default=64)
    parser.add_argument("--n-heads", type=int, default=8)
    parser.add_argument("--n-layers", type=int, default=2)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--batchsize", "-b", type=int, default=4,
                        help="per-rank batch (DP mode) / global batch (SP mode)")
    parser.add_argument("--iterations", type=int, default=100)
    parser.add_argument("--attention", default="full",
                        choices=["full", "ring", "ring_flash", "zigzag",
                                 "zigzag_flash", "ulysses", "ulysses_flash",
                                 "flash"])
    parser.add_argument("--seq-parallel", action="store_true",
                        help="shard the SEQUENCE axis over the mesh "
                             "(context parallelism); needs ring/zigzag/"
                             "ulysses (zigzag data is host-permuted "
                             "automatically)")
    parser.add_argument("--moe-experts", type=int, default=0,
                        help="expert-parallel MoE FFN every 2nd block")
    parser.add_argument("--fused-ce", action="store_true",
                        help="fused chunked head+loss: never materializes "
                             "the [B,T,vocab] f32 logits (the step's "
                             "largest tensor pair; ops/losses.py)")
    parser.add_argument("--remat", action="store_true",
                        help="rematerialize block forwards in the backward "
                             "(jax.checkpoint): ~1/3 more forward FLOPs for "
                             "O(n_layers*B*T*d) less activation HBM — the "
                             "lever for long context / large token batches")
    parser.add_argument("--moe-top-k", type=int, default=1, choices=[1, 2],
                        help="1 = Switch routing, 2 = GShard top-2")
    parser.add_argument("--tensor-parallel", action="store_true",
                        help="Megatron-style TP: heads + FFN width sharded "
                             "over the mesh axis, batch replicated "
                             "(parallel.tensor; global-objective grads)")
    parser.add_argument("--gspmd", action="store_true",
                        help="GSPMD weights-at-rest: the dense model under "
                             "plain jit with Megatron param layouts (params"
                             "+opt ~1/n per device; parallel.gspmd). "
                             "Combines with --moe-experts via the gshard "
                             "einsum-dispatch MoE")
    parser.add_argument("--pipeline", action="store_true",
                        help="pipeline parallelism: one transformer block "
                             "per mesh rank (GPipe fill-drain microbatch "
                             "schedule; ops.pipeline)")
    parser.add_argument("--microbatches", type=int, default=8,
                        help="with --pipeline: microbatches per step "
                             "(bubble fraction = (S-1)/(M+S-1))")
    parser.add_argument("--vocab-parallel-head", action="store_true",
                        help="with --tensor-parallel: shard the LM head "
                             "over the vocab; full logits are never "
                             "materialized (sharded-vocab cross entropy)")
    parser.add_argument("--resume", action="store_true",
                        help="run the (plain-DP) training loop through "
                             "resilience.resilient_fit: periodic snapshots "
                             "(params + optimizer + iterator + loop "
                             "index), auto-restore on a step failure, and "
                             "cross-launch resume — rerun the same "
                             "command after a crash and it continues from "
                             "the newest intact snapshot")
    parser.add_argument("--checkpoint-dir", default="./lm_checkpoints",
                        help="with --resume: snapshot directory")
    parser.add_argument("--save-every", type=int, default=20,
                        help="with --resume: snapshot cadence in steps")
    parser.add_argument("--async-save", action="store_true",
                        help="with --resume: background checkpointing — "
                             "the loop blocks only on the device_get; "
                             "serialize + write + GC run on the "
                             "checkpointer's writer thread "
                             "(dataflow async hot loop)")
    parser.add_argument("--prefetch-depth", type=int, default=0,
                        help="device-prefetch the batch stream this many "
                             "batches ahead on a producer thread (H2D "
                             "overlaps the step; dataflow."
                             "DevicePrefetcher). 0: synchronous feeding")
    parser.add_argument("--fetch-every", type=int, default=1,
                        help="dispatch-ahead loss cadence: keep losses on "
                             "device and fetch them batched every K steps "
                             "(bounded in-flight window; loss prints lag "
                             "up to K-1 steps). 1: per-step fetch. With "
                             "either this >1 or --prefetch-depth the loop "
                             "runs through training.fit (per-step MoE "
                             "drop-fraction prints are skipped there)")
    parser.add_argument("--inject-fault", type=int, default=0,
                        help="with --resume: crash training at this step "
                             "(a seeded resilience.FaultInjector raise) "
                             "to demo the restore loop end to end "
                             "(0: off)")
    parser.add_argument("--serve-samples", type=int, default=0,
                        help="after training, serve this many shared-"
                             "context continuations through the serving "
                             "fast path (bucketed batched prefill + "
                             "prefix KV reuse) — training-to-serving in "
                             "one script (plain/MoE modes; 0: off)")
    parser.add_argument("--publish-to", default="",
                        help="online train->serve (ISSUE 10): 'engine' "
                             "stands up a live in-process serving engine "
                             "BEFORE training and hot-swaps the params "
                             "into it every --publish-every iterations "
                             "through the deploy version fence (zero "
                             "recompiles, traffic keeps flowing), "
                             "sampling a continuation at each version "
                             "(address-shaped targets are reserved for a "
                             "network front)")
    parser.add_argument("--publish-every", type=int, default=0,
                        help="with --publish-to: publish cadence in "
                             "iterations (default: half the run)")
    parser.add_argument("--snapshot-to", default="",
                        help="save a sharded snapshot of the trained "
                             "params (with the resharding manifest: mesh "
                             "shape, TP degree, head geometry) to this "
                             "directory — serve it on a DIFFERENT mesh/"
                             "TP degree via serve_lm.py --reshard-from")
    parser.add_argument("--trace-out", default="",
                        help="write the run's train-step span trees "
                             "(prefetch-wait / dispatch / loss-fetch / "
                             "checkpoint-enqueue) as Chrome trace-event "
                             "JSON to this path — load in "
                             "chrome://tracing or ui.perfetto.dev")
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--n-tokens", type=int, default=200_000)
    parser.add_argument("--max-len", type=int, default=None,
                        help="positional-embedding table size "
                             "(default: just enough for --seq-len)")
    args = parser.parse_args()
    enable_compilation_cache()

    chainermn_tpu.add_global_except_hook()
    comm = chainermn_tpu.create_communicator("tpu")
    if args.pipeline and (args.seq_parallel or args.moe_experts
                          or args.tensor_parallel):
        raise SystemExit("--pipeline uses the whole mesh axis for stages; "
                         "it does not combine with the other parallel "
                         "flags in this example")
    if args.pipeline and args.remat:
        raise SystemExit("--pipeline builds its blocks via make_pipeline_lm, "
                         "which does not thread --remat; the flag would be "
                         "silently ignored (pipeline microbatching already "
                         "bounds live activations to one microbatch per "
                         "stage)")
    if args.fused_ce and (args.pipeline or args.gspmd
                          or args.tensor_parallel):
        raise SystemExit("--fused-ce is the plain/sequence-parallel step's "
                         "fused head+loss; the pipeline/gspmd/TP paths "
                         "build their own steps and would silently ignore "
                         "it (TP's vocab-parallel head already avoids full "
                         "logits)")
    if args.gspmd and (args.seq_parallel or args.tensor_parallel
                       or args.pipeline):
        raise SystemExit("--gspmd is its own layout (plain jit, partitioner "
                         "collectives); it does not combine with "
                         "--seq-parallel/--tensor-parallel/--pipeline")
    if args.gspmd and args.attention not in ("full", "flash"):
        raise SystemExit("--gspmd runs the dense model; --attention must be "
                         "full or flash (sequence-sharded kinds need the "
                         "shard_map step)")
    if args.resume and (args.gspmd or args.pipeline):
        raise SystemExit("--resume wraps the plain/SP/TP/MoE train loop in "
                         "resilient_fit; the gspmd/pipeline modes build "
                         "their own loops and would silently ignore it")
    if (args.prefetch_depth or args.fetch_every > 1) and (
            args.gspmd or args.pipeline or args.resume):
        raise SystemExit("--prefetch-depth/--fetch-every drive the plain "
                         "loop through training.fit; the gspmd/pipeline/"
                         "resume modes build their own loops and would "
                         "silently ignore them")
    if args.publish_to and args.publish_to != "engine":
        raise SystemExit("--publish-to: only the in-process 'engine' "
                         "target exists (a network front would take an "
                         "address here)")
    if args.publish_to and (
            args.gspmd or args.pipeline or args.seq_parallel
            or args.tensor_parallel or args.resume
            or args.prefetch_depth or args.fetch_every > 1):
        raise SystemExit("--publish-to rides the plain synchronous train "
                         "loop (like --serve-samples): it does not "
                         "combine with the sharded-model, resume, or "
                         "async-loop flags")
    if args.snapshot_to and (args.gspmd or args.pipeline or args.resume):
        raise SystemExit("--snapshot-to snapshots the plain/SP/TP loop's "
                         "params; the gspmd/pipeline/resume modes own "
                         "their state layouts and would silently ignore "
                         "it")
    if args.gspmd:
        return run_gspmd(args, comm)
    if args.pipeline:
        if args.n_layers != parser.get_default("n_layers") and (
                args.n_layers != comm.size):
            raise SystemExit(
                f"--pipeline pins the layer count to one block per rank "
                f"({comm.size} here); --n-layers {args.n_layers} would be "
                "silently ignored")
        return run_pipeline(args, comm)
    if args.seq_parallel and args.attention not in (
            "ring", "ring_flash", "zigzag", "zigzag_flash", "ulysses",
            "ulysses_flash"):
        raise SystemExit("--seq-parallel needs --attention "
                         "ring|zigzag|ulysses (or a _flash variant)")
    if args.tensor_parallel and (args.seq_parallel or args.moe_experts):
        raise SystemExit("--tensor-parallel uses the whole flat mesh axis; "
                         "it does not combine with --seq-parallel or "
                         "--moe-experts in this example")
    if args.tensor_parallel and args.n_heads % comm.size:
        raise SystemExit(f"--tensor-parallel needs n_heads divisible by the "
                         f"{comm.size}-way mesh axis")
    if args.vocab_parallel_head and not args.tensor_parallel:
        raise SystemExit("--vocab-parallel-head needs --tensor-parallel")

    model = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers,
        max_len=args.max_len or max(args.seq_len, 512),
        attention=args.attention,
        sequence_axis=comm.axis_name if args.seq_parallel else None,
        moe_experts=args.moe_experts,
        moe_axis=comm.axis_name if args.moe_experts else None,
        moe_top_k=args.moe_top_k,
        tensor_axis=comm.axis_name if args.tensor_parallel else None,
        vocab_parallel_head=args.vocab_parallel_head,
        remat=args.remat,
        compute_dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
    )

    tokens_all, targets_all, n_seq = _stream_data(args)
    if args.seq_parallel and args.attention.startswith("zigzag"):
        # zigzag shards hold (early, late) chunk pairs: permute the data
        # once on the host; the mean loss is permutation-invariant
        from chainermn_tpu.parallel.sequence import zigzag_permutation

        perm = np.asarray(zigzag_permutation(args.seq_len, comm.size))
        tokens_all = tokens_all[:, perm]
        targets_all = targets_all[:, perm]

    if args.seq_parallel or args.tensor_parallel:
        # SP: the sequence axis shards over the mesh. TP: the WEIGHTS shard
        # over the mesh and the batch is replicated. Either way --batchsize
        # is already the global batch.
        batch = args.batchsize
    else:
        batch = args.batchsize * comm.size
    if n_seq < batch:
        raise SystemExit(
            f"only {n_seq} sequences of length {args.seq_len} in "
            f"{args.n_tokens} tokens but the global batch is {batch}; "
            "raise --n-tokens or lower --batchsize/--seq-len"
        )

    def batches():
        epoch = 0
        while True:
            order = np.random.RandomState(1 + epoch).permutation(n_seq)
            epoch += 1
            for i in range(0, n_seq - batch + 1, batch):
                sel = order[i : i + batch]
                yield tokens_all[sel], targets_all[sel]

    sample = jnp.asarray(tokens_all[:1])
    if args.moe_experts or args.seq_parallel or args.tensor_parallel:
        # collectives inside the model: init under the mesh
        from jax.sharding import PartitionSpec as P

        spec = (P(None, comm.axis_name) if args.seq_parallel
                else P() if args.tensor_parallel
                else comm.data_spec)
        init_tok = jnp.asarray(
            tokens_all[:batch]
            if not (args.seq_parallel or args.tensor_parallel)
            else tokens_all[:1]
        )
        params = jax.jit(comm.shard_map(
            lambda t: model.init(
                jax.random.PRNGKey(0), t[:1] if t.ndim > 1 else t),
            in_specs=spec, out_specs=P(),
        ))(init_tok)
    else:
        params = comm.bcast_data(model.init(jax.random.PRNGKey(0), sample))

    if args.tensor_parallel:
        # plain optax: the TP step's grads are already the exact global
        # gradient (global-objective pattern); a multi-node wrapper's extra
        # mean would shrink them by the axis size
        optimizer = optax.adam(args.lr)
    else:
        optimizer = chainermn_tpu.create_multi_node_optimizer(
            optax.adam(args.lr), comm
        )
    opt_state = jax.device_put(optimizer.init(params), comm.named_sharding())
    step = jit_lm_train_step(model, optimizer, comm,
                             shard_sequence=args.seq_parallel,
                             fused_ce=args.fused_ce)

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    if comm.rank == 0:
        print(f"{n_params / 1e6:.2f}M params  attention={args.attention} "
              f"seq_parallel={args.seq_parallel} moe={args.moe_experts} "
              f"tensor_parallel={args.tensor_parallel} devices={comm.size}")

    if args.resume:
        out = run_resilient(args, comm, step, params, opt_state,
                            tokens_all, targets_all, n_seq, batch)
        _dump_traces(args)
        return out

    if args.prefetch_depth or args.fetch_every > 1:
        # the async hot loop: batches device_put by a producer thread,
        # losses fetched batched — the host leaves the critical path
        from jax.sharding import PartitionSpec as P

        from chainermn_tpu.training import fit

        data_spec = (P(None, comm.axis_name) if args.seq_parallel
                     else P() if args.tensor_parallel
                     else comm.data_spec)

        def on_loss(i, v):
            if (i + 1) % 20 == 0 and comm.rank == 0:
                print(f"iter {i + 1:4d}  loss {v:.3f}")

        t0 = time.time()
        params, opt_state, losses = fit(
            step, params, opt_state, batches(), args.iterations,
            fetch_every=args.fetch_every,
            prefetch_depth=args.prefetch_depth,
            sharding=comm.named_sharding(*data_spec),
            transform=lambda b: (jnp.asarray(b[0]), jnp.asarray(b[1])),
            on_loss=on_loss, name="train_lm")
        if comm.rank == 0:
            tok_s = args.iterations * batch * args.seq_len / (
                time.time() - t0)
            print(f"done: {args.iterations} iterations (prefetch_depth="
                  f"{args.prefetch_depth}, fetch_every={args.fetch_every}),"
                  f" loss {losses[0]:.3f} -> {losses[-1]:.3f}  "
                  f"{tok_s:.0f} tok/s incl. compile")
        if args.snapshot_to:
            _save_snapshot(args, comm, model, params)
        _dump_traces(args)
        return

    from chainermn_tpu.parallel import MoeStatsAccumulator

    publisher = None
    if args.publish_to and comm.rank == 0:
        publisher = _OnlinePublisher(args, model, params, tokens_all)

    gen = batches()
    t0, toks = time.time(), 0
    first = last = None
    acc = MoeStatsAccumulator()
    tracer = monitor.get_tracer()
    for it in range(1, args.iterations + 1):
        # per-step span tree (same taxonomy as training.fit) so
        # --trace-out has causal data even from the synchronous loop
        with tracer.trace("train_step", kind="train", step=it):
            with tracer.span("prefetch_wait"):
                tok, tgt = next(gen)
            # uniform step arity: stats is {} for dense models
            with tracer.span("dispatch"):
                params, opt_state, loss, stats = step(
                    params, opt_state, jnp.asarray(tok), jnp.asarray(tgt))
        acc.update(stats)
        if it == 1:
            jax.block_until_ready(loss)
            first = float(loss)
            t0, toks = time.time(), 0
            if comm.rank == 0:
                print(f"compiled; first loss {first:.3f} "
                      f"(uniform = {np.log(args.vocab):.3f})")
        toks += tok.size
        if publisher is not None and it % publisher.every == 0:
            publisher.publish(it, params)
        if it % 20 == 0 and comm.rank == 0:
            last = float(loss)
            drop = (f"  moe_drop {float(stats['moe_drop_frac']):.1%}"
                    if stats else "")
            print(f"iter {it:4d}  loss {last:.3f}  "
                  f"{toks / (time.time() - t0):.0f} tok/s{drop}")
    last = float(loss)
    if comm.rank == 0:
        print(f"done: {args.iterations} iterations, "
              f"loss {first:.3f} -> {last:.3f}{_drop_suffix(acc)}")
    if publisher is not None:
        publisher.close()
    if args.snapshot_to:
        _save_snapshot(args, comm, model, params)
    if args.serve_samples:
        _serve_samples(args, comm, model, params, tokens_all)
    _dump_traces(args)


if __name__ == "__main__":
    main()
