#!/usr/bin/env python
"""On-chip block-size sweep for the flash kernels (round 5 tuning).

The round-5 battery measured the kernels at ~6.5 TFLOP/s with the original
f32-precast MXU inputs and 128x128 blocks. After the storage-dtype MXU fix
(ops/flash_attention.py), this sweeps (block_q, block_k) on the real chip at
the onchip_flash timing shapes so the default can be set from data rather
than guessed: fwd+bwd ms/step and achieved TFLOP/s per cell, flash-vs-full
ratio recomputed at the winning block size.

Appends one JSON record per cell to scripts/flash_tune.jsonl as it lands.
Exits non-zero if no TPU is attached.
"""

import functools
import json
import os
import signal
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
OUT = os.path.join(_HERE, "flash_tune.jsonl")

from onchip_flash import time_grad_step  # the one shared timing idiom


def emit(rec):
    rec["t"] = round(time.time(), 1)
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def main():
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    deadline = time.time() + float(os.environ.get("FLASH_TUNE_BUDGET", "900"))

    import jax

    from chainermn_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    import jax.numpy as jnp

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"flash_tune times the compiled kernels on the TPU; "
                         f"JAX found {devs[0].platform!r}. Nothing was run.")
    emit({"test": "platform", "device_kind": devs[0].device_kind})

    from chainermn_tpu.ops.flash_attention import flash_attention
    from chainermn_tpu.parallel.sequence import full_attention

    rng = jax.random.PRNGKey(0)

    def mk(b, t, h, d):
        ks = jax.random.split(rng, 3)
        return tuple(
            jax.random.normal(k, (b, t, h, d), jnp.bfloat16) for k in ks
        )

    b, h, d = 1, 8, 64
    for t_len in (4096, 8192):
        q, k, v = mk(b, t_len, h, d)
        # full-attention reference under the same harness/process
        if time.time() < deadline:
            try:
                full_ms = time_grad_step(
                    functools.partial(full_attention, causal=True), q, k, v, 10)
                emit({"test": "full_ref", "seq_len": t_len, "full_ms": full_ms})
            except Exception as e:
                emit({"test": "full_ref", "seq_len": t_len,
                      "error": f"{type(e).__name__}: {e}"[:200]})
        for blk in (128, 256, 512, 1024, 2048):
            if time.time() > deadline:
                emit({"test": "tune", "seq_len": t_len, "block": blk,
                      "skipped": "budget"})
                continue
            rec = {"test": "tune", "seq_len": t_len, "block": blk}
            try:
                fn = functools.partial(flash_attention, causal=True,
                                       interpret=False, block_q=blk,
                                       block_k=blk)
                rec["flash_ms"] = time_grad_step(fn, q, k, v, 10)
                flops = 7.0 * b * h * t_len * t_len * d  # causal fwd+bwd
                rec["achieved_tflops"] = round(
                    flops / (rec["flash_ms"] / 1e3) / 1e12, 2)
            except Exception as e:
                rec["error"] = f"{type(e).__name__}: {e}"[:200]
            emit(rec)
    emit({"test": "done"})


if __name__ == "__main__":
    main()
