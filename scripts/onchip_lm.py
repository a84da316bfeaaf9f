#!/usr/bin/env python
"""On-chip transformer-LM training MFU — the second headline metric.

bench.py measures the reference's acceptance workload (ResNet-50 DP,
SURVEY.md S6). This measures the flagship LM path — ``jit_lm_train_step``
over :class:`TransformerLM` with the Pallas flash kernels — compiled and
executed on the real chip, at sizes where the MXU (not the input pipeline)
is the constraint:

  cells: (T=2048, B=8, flash) — throughput headline
         (T=2048, B=8, full)  — LM-level flash-vs-full ratio, short ctx
         (T=8192, B=2, flash) — long-context step
         (T=8192, B=2, full)  — the AOT table's 4.3x prediction, measured

FLOPs come from the compiled module's cost_analysis (post-optimization,
per-device — same convention as bench.py), with the analytic
``6 * params * tokens (+ attention term)`` estimate recorded beside it as a
cross-check. MFU is vs the chip's bf16 peak (197 TFLOP/s on v5e).

Appends one JSON record per cell to scripts/onchip_lm.jsonl the moment it
lands, so a run that is cut short keeps the cells it finished. Exits non-zero
if no TPU is attached (``ONCHIP_LM_TINY=1`` is the any-platform smoke).
"""

import json
import os
import signal
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root (run from anywhere)
OUT = os.path.join(_HERE, "onchip_lm.jsonl")

# one peak-FLOPs table for every script that reports a utilization
from bench import chip_peak


_PERSIST = [False]  # set true after the platform check confirms a real TPU


def emit(rec):
    """Real-chip records append to the evidence jsonl; CPU/tiny smoke runs
    print only (the file is committed TPU evidence)."""
    rec["t"] = round(time.time(), 1)
    if _PERSIST[0]:
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def main():
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    deadline = time.time() + float(os.environ.get("ONCHIP_LM_BUDGET", "1500"))

    import jax

    from chainermn_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    import jax.numpy as jnp
    import optax

    tiny_env = bool(os.environ.get("ONCHIP_LM_TINY"))  # CI smoke: any platform
    devs = jax.devices()
    if devs[0].platform != "tpu" and not tiny_env:
        raise SystemExit(f"onchip_lm measures the TPU; JAX found "
                         f"{devs[0].platform!r}. Nothing was run.")
    kind = devs[0].device_kind
    peak = chip_peak(kind) if devs[0].platform == "tpu" else None
    _PERSIST[0] = devs[0].platform == "tpu" and not tiny_env
    emit({"test": "platform", "device_kind": kind, "peak_flops": peak})

    import chainermn_tpu
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.training import jit_lm_train_step

    vocab = int(os.environ.get("ONCHIP_LM_VOCAB", "32768"))
    d_model = int(os.environ.get("ONCHIP_LM_DMODEL", "1024"))
    n_layers = int(os.environ.get("ONCHIP_LM_LAYERS", "12"))
    n_heads = d_model // 64
    tiny = tiny_env
    if tiny:
        vocab, d_model, n_layers, n_heads = 256, 64, 2, 2
    cells = [(2048, 8, "flash"), (2048, 8, "full"),
             (8192, 2, "flash"), (8192, 2, "full"),
             # token-batch lever: more tokens per step amortize the
             # weight/state HBM traffic. B=16 is the biggest cell that fits
             # with the f32 logits pair (~17 GB at B=32 by its shape):
             # B=16+remat needs 12.77 GB by the AOT memory analysis
             # (lm_roofline_aot.jsonl).
             (2048, 16, "flash+remat"),
             # chunked fused head+loss (ops/losses.py) never builds the f32
             # logits pair: B=32 then needs 9.38 GB (same record)
             (2048, 32, "flash+remat+fused")]
    if tiny:
        cells = [(128, 2, "full")]

    comm = chainermn_tpu.create_communicator("tpu")
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adamw(3e-4), comm)
    rng = jax.random.PRNGKey(0)

    this_run = []  # records from THIS process only (ratio pairing below)
    # Gate each cell on a pessimistic cost estimate, like bench.py's
    # ladder, so no compile starts that cannot fit the time budget: a warm
    # previous compile predicts warm neighbors (same earlier process, same
    # cell list); cold needs the full floor.
    cell_floor = float(os.environ.get("ONCHIP_LM_CELL_FLOOR", "700"))
    prev_wall = prev_compile = None
    for t_len, batch, attn in cells:
        remaining = deadline - time.time()
        if prev_wall is None:
            # first cell: the budget is the operator's statement that one
            # cell fits; no history to gate on
            need = 60.0
        elif prev_compile is not None and prev_compile < 60:
            need = max(3 * prev_wall, 120.0)
        else:
            need = cell_floor
        if remaining < need:
            emit({"cell": [t_len, batch, attn], "skipped": "budget",
                  "remaining_s": round(remaining, 1), "need_s": need})
            continue
        flags = attn.split("+")
        attn_kind, use_remat, use_fused = (
            flags[0], "remat" in flags[1:], "fused" in flags[1:])
        rec = {"cell": [t_len, batch, attn], "seq_len": t_len,
               "batch": batch, "attention": attn_kind, "remat": use_remat,
               "fused_ce": use_fused,
               "d_model": d_model, "n_layers": n_layers, "vocab": vocab}
        t_start = time.time()
        try:
            model = TransformerLM(
                vocab_size=vocab, d_model=d_model, n_heads=n_heads,
                n_layers=n_layers, max_len=max(t_len, 2048),
                attention=attn_kind, compute_dtype=jnp.bfloat16,
                remat=use_remat)
            tokens = jax.random.randint(rng, (batch, t_len), 0, vocab)
            # real next-token objective (same key would make targets ==
            # tokens: a trivial copy task whose loss collapses)
            targets = jnp.roll(tokens, -1, axis=1)
            params = comm.bcast_data(model.init(rng, tokens))
            opt_state = jax.jit(opt.init)(params)
            n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
            rec["n_params"] = n_params

            step_fn = jit_lm_train_step(model, opt, comm,
                                        fused_ce=use_fused)
            t0 = time.time()
            # first call compiles (jit_lm_train_step caches per-shape)
            params, opt_state, loss, _ = step_fn(
                params, opt_state, tokens, targets)
            float(loss)
            rec["compile_plus_first_step_s"] = round(time.time() - t0, 1)

            n_steps = 3 if tiny else int(os.environ.get(
                "ONCHIP_LM_STEPS", "20"))
            # warm, enqueue n, close with a device->host fetch
            params, opt_state, loss, _ = step_fn(
                params, opt_state, tokens, targets)
            float(loss)
            t0 = time.time()
            for _ in range(n_steps):
                params, opt_state, loss, _ = step_fn(
                    params, opt_state, tokens, targets)
            rec["loss"] = float(loss)
            dt = time.time() - t0
            step_s = dt / n_steps
            rec["step_time_ms"] = round(step_s * 1e3, 2)
            rec["tokens_per_sec"] = round(batch * t_len / step_s, 1)

            # Analytic fwd+bwd FLOPs: 6 * non-embedding-params * tokens for
            # the matmul tower + 12 * B * H * T^2 * d_head / 2 (causal) for
            # attention scores/values, fwd+bwd. Recorded as the cross-check;
            # cost_analysis is unavailable here because jit_lm_train_step
            # manages its own jit cache (no AOT handle) — the bench keeps
            # both conventions side by side where it can.
            embed_params = vocab * d_model + model.max_len * d_model
            d_head = d_model // n_heads
            flops = (6.0 * (n_params - embed_params) * batch * t_len
                     + 12.0 * batch * n_heads * t_len * t_len * d_head / 2)
            rec["analytic_tflops"] = round(flops / step_s / 1e12, 2)
            if peak:
                rec["mfu_analytic"] = round(flops / step_s / peak, 4)
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:400]
        rec["wall_s"] = round(time.time() - t_start, 1)
        prev_wall = rec["wall_s"]
        prev_compile = rec.get("compile_plus_first_step_s")  # None => cold
        this_run.append(rec)
        emit(rec)

    # LM-level flash-vs-full ratios, paired within THIS run only (an
    # append-only OUT can hold records from earlier runs / other configs)
    by = {tuple(r["cell"]): r for r in this_run if "step_time_ms" in r}
    for t_len in (2048, 8192):
        b = {2048: 8, 8192: 2}[t_len]
        fl, fu = by.get((t_len, b, "flash")), by.get((t_len, b, "full"))
        if fl and fu:
            emit({"test": "full_over_flash", "seq_len": t_len,
                  "ratio": round(fu["step_time_ms"]
                                 / fl["step_time_ms"], 3)})
    emit({"test": "done"})


if __name__ == "__main__":
    main()
