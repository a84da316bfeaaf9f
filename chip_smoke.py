#!/usr/bin/env python3
"""The quickest proof that chainermn_tpu still starts on the chip.

One process drives the system's main paths once, through the entry points a
user calls (the README quickstart and ``examples/lm/{train,serve}_lm.py``),
on every chip JAX shows, at the full width of the models the repo trains and
serves. Weights are random (from a seed), data is synthetic, a few steps each:

  A. trainer  ResNet-50, 224x224 bf16, batch 128 per chip, data-parallel:
              ``create_communicator`` -> ``bcast_data`` ->
              ``create_multi_node_optimizer`` -> ``jit_train_step``.
  B. LM       the 220M ``TransformerLM`` (vocab 32768, d_model 1024, 12
              layers, 16 heads, bf16), ``attention="flash"``, T=2048, batch 8
              per chip, ``jit_lm_train_step``; the compiled step must hold
              the Mosaic flash kernels, and flash must agree with
              ``full_attention`` forward and backward.
  C. server   B's parameters behind ``ServingEngine(paged=True,
              kv_quant="int8", paged_kernel=True)`` + ``ServingClient``:
              warm-up, then ragged prompts, blocking and streaming; the
              decode program must hold the Mosaic paged-decode kernel.
  D. (several chips only) the server once more with tensor-parallel decode.

It refuses to run anywhere but on a TPU, any failed check raises, and the last
line of standard output of a run that passed is::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Times and memory figures printed on the way are set-up facts of this run
(compile seconds, peak HBM), not benchmark metrics.

``--rehearse`` runs the same phases at toy widths on the CPU, kernels
interpreted, to debug the script without a chip. It prints no result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from importlib import metadata

MOSAIC_CALL = "tpu_custom_call"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the CPU rehearsal."""

    # A: ResNet trainer
    resnet_stages: tuple
    resnet_width: int
    resnet_classes: int
    image_size: int
    resnet_batch: int          # per chip
    resnet_steps: int
    # B: LM trainer
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    max_len: int
    seq_len: int
    lm_batch: int              # per chip
    lm_steps: int
    parity_len: int            # flash vs full_attention, fwd+bwd
    # C: server
    n_slots: int
    cache_len: int
    kv_block: int
    prefill_buckets: tuple
    prefill_batch: int
    prompt_lens: tuple
    max_new: int


FULL = Sizes(
    resnet_stages=(3, 4, 6, 3), resnet_width=64, resnet_classes=1000,
    image_size=224, resnet_batch=128, resnet_steps=5,
    vocab=32768, d_model=1024, n_layers=12, n_heads=16, max_len=2048,
    seq_len=2048, lm_batch=8, lm_steps=3, parity_len=512,
    n_slots=8, cache_len=576, kv_block=16,
    prefill_buckets=(128, 256, 512), prefill_batch=4,
    prompt_lens=(64, 97, 128, 200, 256, 301, 400, 512), max_new=32,
)

TINY = Sizes(
    resnet_stages=(1,), resnet_width=8, resnet_classes=10,
    image_size=16, resnet_batch=2, resnet_steps=5,
    vocab=64, d_model=64, n_layers=1, n_heads=8, max_len=32,
    seq_len=16, lm_batch=1, lm_steps=3, parity_len=16,
    n_slots=2, cache_len=16, kv_block=8,
    prefill_buckets=(16,), prefill_batch=2,
    prompt_lens=(3, 6, 10), max_new=4,
)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def check(cond, msg: str) -> None:
    """A check that survives ``python -O`` and names what failed."""
    if not cond:
        raise AssertionError(msg)


def memory_facts() -> list:
    """Per device ``(bytes_in_use, peak_bytes_in_use, peak_bytes_reserved)``
    as the backend reports them; the peaks are high-water marks since the
    process started, so after phase B they are B's. On the v5e the first two
    count live arrays only (ResNet-50 at batch 128 reads 0.33 GB); a running
    program's temporaries show under ``reserved``. The train phases report
    the compiler's own figure beside them, :func:`compiled_hbm_bytes`.
    ``None`` where the backend keeps no statistics (the CPU)."""
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats()
        out.append(None if not st else tuple(
            int(st[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                 "peak_bytes_reserved")))
    return out


def compiled_hbm_bytes(compiled) -> int:
    """What one device needs to run a compiled step, by the compiler's
    memory analysis: arguments + outputs + temporaries (donated arguments
    come back as outputs and count twice)."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes)


def collectives(compiled, n_devices: int, what: str) -> dict:
    """Collective counts and bytes of a compiled step, read from its HLO;
    over several devices a data-parallel step must hold an all-reduce."""
    from chainermn_tpu.extensions import parse_hlo_collectives

    coll = parse_hlo_collectives(compiled.as_text())
    if n_devices > 1:
        check(coll.get("all-reduce", {}).get("count", 0) > 0
              and coll["total_bytes"] > 0,
              f"{what} over {n_devices} devices has no all-reduce: {coll}")
    return coll


def check_on_every_chip(where: str, tree) -> None:
    """Nothing sat on device 0 alone. Called while ``tree`` (a phase's
    parameters, state and batch) is alive: every array in it is laid out
    over every device JAX shows, and every device holds, right now, at
    least the bytes of its shards. ``bytes_in_use`` is read, not the peak:
    a peak is a high-water mark of the whole process, which an earlier
    phase would already have raised. (The CPU keeps no statistics; the
    rehearsal checks the layouts only.)"""
    import jax

    devices = jax.devices()
    held = dict.fromkeys(devices, 0)
    for leaf in jax.tree_util.tree_leaves(tree):
        check(leaf.sharding.device_set == set(devices),
              f"{where}: an array of shape {leaf.shape} lives on "
              f"{sorted(d.id for d in leaf.sharding.device_set)} only")
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for d, m in zip(devices, memory_facts()):
        if m is not None:
            check(m[0] >= held[d] > 0,
                  f"{where}: device {d.id} reports {m[0]} bytes in use, "
                  f"its shards add up to {held[d]}")


def release() -> None:
    """Drop what the last phase left, before the next allocates: A, B and C
    share one chip's 16 GB in this one process."""
    import jax

    jax.clear_caches()
    gc.collect()


# --------------------------------------------------------------------------- #
# A. ResNet trainer                                                            #
# --------------------------------------------------------------------------- #

def phase_resnet(comm, sz: Sizes) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu
    from chainermn_tpu.models import ResNet
    from chainermn_tpu.training import jit_train_step

    n = comm.size
    # the bootstrap collectives a user's script issues before training
    ranked = np.arange(n, dtype=np.float32)[:, None] + np.zeros((1, 4),
                                                                np.float32)
    summed = np.asarray(comm.allreduce(ranked, "sum"))
    np.testing.assert_allclose(
        summed, np.broadcast_to(ranked.sum(axis=0), ranked.shape))
    check(comm.allgather_obj(("smoke", comm.rank)) == [("smoke", 0)],
          "allgather_obj did not return this process's object")

    model = ResNet(stage_sizes=list(sz.resnet_stages), width=sz.resnet_width,
                   num_classes=sz.resnet_classes)
    batch = sz.resnet_batch * n
    key = jax.random.PRNGKey(0)
    shape = (batch, sz.image_size, sz.image_size, 3)
    # a fixed synthetic batch, made where the step wants it (set-up work is
    # jitted throughout: op-by-op it is one small compile per shape)
    on_mesh = comm.named_sharding(*comm.data_spec)
    images = jax.jit(lambda k: jax.random.normal(k, shape, jnp.bfloat16),
                     out_shardings=on_mesh)(key)
    labels = jax.device_put(
        np.arange(batch, dtype=np.int32) % sz.resnet_classes, on_mesh)

    variables = comm.bcast_data(jax.jit(lambda k: model.init(
        k, jnp.zeros((2,) + shape[1:], jnp.bfloat16), train=True))(key))
    opt = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.05, momentum=0.9), comm)
    opt_state = jax.device_put(jax.jit(opt.init)(variables["params"]),
                               comm.named_sharding())

    # one AOT compile serves the run and the look at the compiled program
    t0 = time.perf_counter()
    step = jit_train_step(model, opt, comm).lower(
        variables, opt_state, images, labels).compile()
    compile_s = time.perf_counter() - t0
    coll = collectives(step, n, "ResNet step")

    t0 = time.perf_counter()
    losses = []
    for _ in range(sz.resnet_steps):
        variables, opt_state, loss = step(variables, opt_state, images,
                                          labels)
        losses.append(loss)
    jax.block_until_ready((variables, opt_state, losses))
    steps_s = time.perf_counter() - t0
    losses = [float(x) for x in losses]
    check(all(np.isfinite(losses)), f"ResNet loss not finite: {losses}")
    check(losses[-1] < losses[0],
          f"ResNet loss did not fall on a fixed batch: {losses}")
    check_on_every_chip("ResNet trainer",
                        (variables, opt_state, images, labels))
    return {
        "global_batch": batch, "compile_s": round(compile_s, 1),
        "steps_s": round(steps_s, 2), "losses": [round(x, 4) for x in losses],
        "collective_bytes_per_step": int(coll["total_bytes"]),
        "all_reduce_count": int(coll.get("all-reduce", {}).get("count", 0)),
        "compiled_hbm_bytes": compiled_hbm_bytes(step),
        "memory": memory_facts(),
    }


# --------------------------------------------------------------------------- #
# B. LM trainer with the Pallas kernels                                        #
# --------------------------------------------------------------------------- #

def lm_model(sz: Sizes, **kw):
    import jax.numpy as jnp

    from chainermn_tpu.models import TransformerLM

    return TransformerLM(
        vocab_size=sz.vocab, d_model=sz.d_model, n_heads=sz.n_heads,
        n_layers=sz.n_layers, max_len=sz.max_len,
        compute_dtype=jnp.bfloat16, **kw)


def check_flash_parity(sz: Sizes, on_tpu: bool) -> dict:
    """``flash_attention`` against ``full_attention``, forward and backward,
    bf16 causal at the model's head shape, to bf16's tolerance."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops import flash_attention
    from chainermn_tpu.parallel.sequence import full_attention

    t, h, d = sz.parity_len, sz.n_heads, sz.d_model // sz.n_heads
    q, k, v = (jax.random.normal(kk, (2, t, h, d), jnp.bfloat16)
               for kk in jax.random.split(jax.random.PRNGKey(1), 3))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def full(q, k, v):
        return full_attention(q, k, v, causal=True, precision="highest")

    def sq_loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    flash_fwd = jax.jit(flash)
    if on_tpu:
        check(MOSAIC_CALL in flash_fwd.lower(q, k, v).as_text(),
              "flash_attention did not lower to a Mosaic kernel on the TPU")
    f32 = lambda x: x.astype(jnp.float32)
    err_out = float(jnp.max(jnp.abs(
        f32(flash_fwd(q, k, v)) - f32(jax.jit(full)(q, k, v)))))
    g_flash = jax.jit(jax.grad(sq_loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.jit(jax.grad(sq_loss(full), argnums=(0, 1, 2)))(q, k, v)
    # gradients grow with T: compare relative to the reference's magnitude
    err_grad = max(
        float(jnp.max(jnp.abs(f32(a) - f32(b))) / jnp.max(jnp.abs(f32(b))))
        for a, b in zip(g_flash, g_full))
    check(err_out < 2e-2 * t ** 0.5 and err_grad < 8e-2,
          f"flash vs full attention: out abs err {err_out}, "
          f"grad rel err {err_grad}")
    return {"flash_out_abs_err": round(err_out, 5),
            "flash_grad_rel_err": round(err_grad, 5)}


def phase_lm(comm, sz: Sizes, on_tpu: bool) -> tuple:
    """Returns ``(facts, params)``; the parameters go on to the server."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import chainermn_tpu
    from chainermn_tpu.training import jit_lm_train_step

    facts = check_flash_parity(sz, on_tpu)

    n = comm.size
    model = lm_model(sz, attention="flash")
    batch = sz.lm_batch * n
    on_mesh = comm.named_sharding(*comm.data_spec)
    toks = np.random.RandomState(0).randint(
        0, sz.vocab, (batch, sz.seq_len)).astype(np.int32)
    tokens = jax.device_put(toks, on_mesh)
    targets = jax.device_put(np.roll(toks, -1, axis=1), on_mesh)  # next token

    params = comm.bcast_data(jax.jit(lambda k: model.init(
        k, jnp.zeros((1, sz.seq_len), jnp.int32)))(jax.random.PRNGKey(0)))
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adamw(3e-4), comm)
    opt_state = jax.device_put(jax.jit(opt.init)(params),
                               comm.named_sharding())

    t0 = time.perf_counter()
    step = jit_lm_train_step(model, opt, comm).lower(
        params, opt_state, tokens, targets).compile()
    compile_s = time.perf_counter() - t0
    n_mosaic = step.as_text().count(f'custom_call_target="{MOSAIC_CALL}"')
    if on_tpu:
        # forward, dq and dk/dv kernels in every layer: neither interpret
        # mode nor flash_attention's full_attention fallback can pass
        check(n_mosaic >= 3 * sz.n_layers,
              f"compiled LM step holds {n_mosaic} Mosaic calls, expected "
              f">= {3 * sz.n_layers}")
    coll = collectives(step, n, "LM step")

    t0 = time.perf_counter()
    losses = []
    for _ in range(sz.lm_steps):
        params, opt_state, loss, _ = step(params, opt_state, tokens, targets)
        losses.append(loss)
    jax.block_until_ready((params, opt_state, losses))
    steps_s = time.perf_counter() - t0
    losses = [float(x) for x in losses]
    check(all(np.isfinite(losses)), f"LM loss not finite: {losses}")
    check(losses[-1] < losses[0],
          f"LM loss did not fall on a fixed batch: {losses}")
    check_on_every_chip("LM trainer", (params, opt_state, tokens, targets))
    facts.update({
        "global_batch": batch, "seq_len": sz.seq_len,
        "n_params": int(sum(x.size for x in jax.tree_util.tree_leaves(params))),
        "compile_s": round(compile_s, 1), "steps_s": round(steps_s, 2),
        "losses": [round(x, 4) for x in losses], "mosaic_calls": n_mosaic,
        "collective_bytes_per_step": int(coll["total_bytes"]),
        "compiled_hbm_bytes": compiled_hbm_bytes(step),
        "memory": memory_facts(),
    })
    return facts, params


# --------------------------------------------------------------------------- #
# C / D. server                                                                #
# --------------------------------------------------------------------------- #

def check_paged_read_paths(sz: Sizes, n_heads: int) -> dict:
    """One decode step's attention through the fused kernel and through the
    XLA gather, on the same int8 block store at the engine's shapes.

    This, not token equality of two engines, is the agreement bar here:
    random-init bf16 logits over a 32768-word vocabulary sit in near-ties
    that any change of summation order flips, while the attention output
    itself is comparable to a tolerance — a few bf16 roundings (the XLA
    path's default-precision PV product rounds its probabilities to bf16),
    so 2e-2 of the output's largest magnitude."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.parallel.sequence import (
        fold_block_scales,
        paged_update_cache_and_attend,
    )

    b, bs = sz.n_slots, sz.kv_block
    d = sz.d_model // sz.n_heads
    n_max = -(-sz.cache_len // bs)
    n_blocks = b * n_max + 1
    rng = np.random.RandomState(2)
    rows, heads = (n_blocks, bs, n_heads, d), (n_blocks, bs, n_heads)

    def scales():
        """A scale for every row and head, as the store holds them: a
        block a row."""
        sc = rng.uniform(0.001, 0.02, heads).astype(np.float32)
        return fold_block_scales(jnp.asarray(sc))

    store = {
        "k": jnp.asarray(rng.randint(-127, 128, rows).astype(np.int8)),
        "v": jnp.asarray(rng.randint(-127, 128, rows).astype(np.int8)),
        "k_scale": scales(), "v_scale": scales(),
        # every row owns a shuffled span of the pool; block 0 is scratch
        "table": jnp.asarray(1 + rng.permutation(b * n_max).astype(
            np.int32).reshape(b, n_max)),
    }
    q, k, v = (jnp.asarray(rng.standard_normal((b, 1, n_heads, d)),
                           jnp.bfloat16) for _ in range(3))
    # ragged depths: youngest possible row, block edges, a full slot
    pos = jnp.asarray(np.linspace(0, sz.cache_len - 1, b).astype(np.int32))

    def read(use_kernel):
        cache = dict(store, use_kernel=True) if use_kernel else store
        out, _ = jax.jit(
            lambda q, k, v, pos: paged_update_cache_and_attend(
                cache, q, k, v, pos))(q, k, v, pos)
        return np.asarray(out.astype(jnp.float32))

    want, got = read(False), read(True)
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    check(err < 2e-2, f"paged kernel vs XLA read path: rel err {err}")
    return {"paged_read_rel_err": round(err, 5)}


def serve(engine, sz: Sizes, on_tpu: bool) -> dict:
    """Warm an engine up and put the ragged burst through a client."""
    import numpy as np

    from chainermn_tpu.serving import ServingClient
    from chainermn_tpu.serving.scheduler import RequestState

    check(engine.paged_kernel is True,
          "ServingEngine fell back from the paged kernel at construction")
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    if on_tpu:
        check(MOSAIC_CALL in engine.decode_program_text(),
              "the decode program holds no Mosaic call on the TPU")
    compiled = engine.compile_counts_detailed()
    check(set(compiled.values()) == {1},
          f"warm-up left a program uncompiled or compiled twice: {compiled}")

    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, sz.vocab, n).astype(np.int32)
               for n in sz.prompt_lens]
    t0 = time.perf_counter()
    with ServingClient(engine) as client:
        # one blocking call on an idle server, then the rest as one burst,
        # every other request streaming its tokens as they are decoded
        first = client.generate(prompts[0], sz.max_new, timeout=600)
        check(len(first) == len(prompts[0]) + sz.max_new,
              f"blocking request returned {len(first)} tokens")
        burst = []
        for i, p in enumerate(prompts[1:]):
            streamed = [] if i % 2 == 0 else None
            burst.append((client.submit(
                p, sz.max_new,
                stream_cb=streamed.append if streamed is not None else None),
                streamed))
        for req, streamed in burst:
            check(req.wait(timeout=600), f"request {req.id} did not finish")
            check(req.state is RequestState.DONE,
                  f"request {req.id} ended {req.state}")
            check(len(req.tokens) == sz.max_new,
                  f"request {req.id}: {len(req.tokens)} tokens, asked "
                  f"{sz.max_new}")
            check(all(0 <= t < sz.vocab for t in req.tokens),
                  f"request {req.id}: token outside the vocabulary")
            if streamed is not None:
                check(streamed == list(req.tokens),
                      f"request {req.id}: streamed tokens differ from the "
                      "request's own")
    serve_s = time.perf_counter() - t0
    check(engine.compile_counts_detailed() == compiled
          and engine.recompiles == {},
          f"a program compiled after warm-up: "
          f"{engine.compile_counts_detailed()} {engine.recompiles}")
    return {"warmup_s": round(warmup_s, 1), "programs": len(compiled),
            "serve_s": round(serve_s, 2), "requests": len(prompts),
            "tokens_out": len(prompts) * sz.max_new}


def engine_kwargs(sz: Sizes) -> dict:
    return dict(
        n_slots=sz.n_slots, cache_len=sz.cache_len,
        prefill_buckets=sz.prefill_buckets, prefill_batch=sz.prefill_batch,
        paged=True, kv_block_size=sz.kv_block, kv_quant="int8",
        paged_kernel=True)


def phase_server(params, sz: Sizes, on_tpu: bool) -> dict:
    """The LM trainer's parameters behind a one-device engine."""
    import jax

    from chainermn_tpu.serving import ServingEngine

    facts = check_paged_read_paths(sz, sz.n_heads)
    if len(jax.devices()) > 1:
        # one replica serves from one chip; on one chip the trainer's
        # parameters go in as ``bcast_data`` committed them to its mesh
        params = jax.device_put(params, jax.devices()[0])
    engine = ServingEngine(lm_model(sz), params, **engine_kwargs(sz))
    facts.update(serve(engine, sz, on_tpu))
    facts["memory"] = memory_facts()
    return facts


def phase_server_tp(comm, sz: Sizes, on_tpu: bool) -> dict:
    """Tensor-parallel decode through the same scheduler: heads and the
    block store sharded over every chip (``examples/lm/serve_lm.py
    --tensor-parallel``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.serving import ServingEngine

    model = lm_model(sz, tensor_axis=comm.axis_name)
    params = jax.jit(comm.shard_map(
        lambda t: model.init(jax.random.PRNGKey(0), t),
        in_specs=P(), out_specs=P()))(jnp.zeros((1, 8), jnp.int32))
    facts = check_paged_read_paths(sz, sz.n_heads // comm.size)
    engine = ServingEngine(model, params, comm=comm, **engine_kwargs(sz))
    facts.update(serve(engine, sz, on_tpu))
    check_on_every_chip("tensor-parallel server",
                        (engine.params, engine._store))
    facts["memory"] = memory_facts()
    return facts


# --------------------------------------------------------------------------- #

def run(sz: Sizes, on_tpu: bool) -> dict:
    import chainermn_tpu

    comm = chainermn_tpu.create_communicator(
        "tpu", allreduce_grad_dtype="bfloat16")
    facts = {}
    log(f"A: ResNet trainer over {comm.size} device(s)")
    facts["resnet"] = phase_resnet(comm, sz)
    log(f"A done: {facts['resnet']}")
    release()
    log("B: LM trainer, flash kernels")
    facts["lm"], params = phase_lm(comm, sz, on_tpu)
    log(f"B done: {facts['lm']}")
    release()
    log("C: server, paged int8 store + fused decode kernel")
    facts["server"] = phase_server(params, sz, on_tpu)
    log(f"C done: {facts['server']}")
    del params
    release()
    if comm.size > 1:
        log(f"D: server, tensor-parallel decode over {comm.size} devices")
        facts["server_tp"] = phase_server_tp(comm, sz, on_tpu)
        log(f"D done: {facts['server_tp']}")
    return facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on the CPU, kernels interpreted; "
                         "prints no result line")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rehearse:
        if device["platform"] != "cpu":
            print("chip_smoke --rehearse is for the CPU (JAX_PLATFORMS=cpu); "
                  f"JAX found {device}", file=sys.stderr)
            return 1
    elif device["platform"] != "tpu":
        print(f"chip_smoke needs a TPU; JAX found {device}. Nothing was run.",
              file=sys.stderr)
        return 1

    from chainermn_tpu.utils import enable_compilation_cache

    # the rehearsal compiles nothing for the chip, so it keeps no cache
    cache_dir = None if args.rehearse else enable_compilation_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    log(f"device {device}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {libtpu}; compile cache {cache_dir}")

    facts = run(TINY if args.rehearse else FULL, on_tpu=not args.rehearse)
    facts["wall_s"] = round(time.perf_counter() - _T0, 1)
    facts["versions"] = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                         "libtpu": libtpu}
    facts["compile_cache"] = cache_dir
    if args.rehearse:
        log(f"rehearsal passed: {json.dumps(facts)}")
        return 0
    print(json.dumps({"facts": facts}))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
