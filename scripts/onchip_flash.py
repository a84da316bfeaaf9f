#!/usr/bin/env python
"""On-chip proof of the Pallas flash kernel stack.

Every CPU test runs the kernels in interpret mode; this script runs them
COMPILED on the real TPU and records:

  1. fwd parity:  flash_attention vs full_attention (causal + non-causal,
     bf16 and f32), max abs error;
  2. bwd parity:  grads of a scalar loss through both paths (dq/dk/dv);
  3. offset-causal parity: traced q_offset/k_offset path (the ring's
     contract) vs a sliced full-attention oracle;
  4. ring_flash + zigzag_flash composition: one shard_map step on a
     1-device mesh (ppermute is identity at world 1, but the kernels and
     the ring-level custom VJP lower and execute compiled);
  5. flash-vs-full wall-clock at T in {2048, 4096, 8192} fwd+bwd — the
     measured counterpart of the AOT 4.3x prediction (PERF.md round 4);
  6. flash-only long-context cells at T in {16384, 32768} — sizes where
     full attention cannot materialize scores and which only compile at
     all after the round-5 kernel grid restructure (context ceiling
     8k -> 128k, PERF.md).

Appends one JSON record per result to scripts/onchip_flash.jsonl the moment
it lands, so a run that is cut short keeps what it finished.
Exits non-zero if no TPU is attached.
"""

import functools
import json
import os
import signal
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root (run from anywhere)
OUT = os.path.join(_HERE, "onchip_flash.jsonl")


def emit(rec):
    rec["t"] = round(time.time(), 1)
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def time_grad_step(fn, q, k, v, n):
    """ms/step for jit(grad(sum fn^2)) — warm, enqueue n, close with a
    device->host fetch. One home for the timing idiom so
    every cell measures identically (flash_tune.py imports it for exactly
    that reason — the cross-file ratios only mean something if both files
    time the same way)."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    g = step(q, k, v)  # compile + warm
    float(jnp.sum(g[0].astype(jnp.float32)))
    t0 = time.time()
    for _ in range(n):
        g = step(q, k, v)
    float(jnp.sum(g[0].astype(jnp.float32)))
    return round((time.time() - t0) / n * 1e3, 3)


def main():
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    deadline = time.time() + float(os.environ.get("ONCHIP_FLASH_BUDGET", "780"))

    import jax

    from chainermn_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"onchip_flash runs the compiled kernels on the TPU; "
                         f"JAX found {devs[0].platform!r}. Nothing was run.")
    emit({"test": "platform", "device_kind": devs[0].device_kind})

    from chainermn_tpu.ops.flash_attention import flash_attention
    from chainermn_tpu.parallel.sequence import full_attention

    rng = jax.random.PRNGKey(0)

    def mk(b, t, h, d, dtype):
        ks = jax.random.split(rng, 3)
        return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)

    # ---- 1+2: fwd + bwd parity, compiled ------------------------------- #
    # The oracle einsums run at precision="highest": at the TPU's DEFAULT
    # precision an "f32" einsum rounds its operands through bf16 passes
    # (~1e-3 abs error), which in the first round-5 window dominated the
    # comparison and flagged the f32 cells ok=false against a 4.5e-4 bar —
    # the error was the oracle's, not the kernel's. f32 tolerances assume a
    # BF16_3X-or-better kernel dot (true f32 inputs are never pre-rounded
    # in the kernel; only the Mosaic dot decomposition contributes).
    for dtype, tol_o, tol_g in ((jnp.float32, 1e-4, 1e-3),
                                (jnp.bfloat16, 2e-2, 8e-2)):
        for causal in (False, True):
            if time.time() > deadline:
                emit({"test": "parity", "dtype": str(dtype.__name__),
                      "causal": causal, "skipped": "budget"})
                continue
            b, t, h, d = 2, 512, 4, 64
            q, k, v = mk(b, t, h, d, dtype)

            def loss_flash(q, k, v):
                o = flash_attention(q, k, v, causal=causal, interpret=False)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            def loss_full(q, k, v):
                o = full_attention(q, k, v, causal=causal,
                                   precision="highest")
                return jnp.sum(o.astype(jnp.float32) ** 2)

            t0 = time.time()
            o_fl = jax.jit(functools.partial(
                flash_attention, causal=causal, interpret=False))(q, k, v)
            o_fu = jax.jit(functools.partial(
                full_attention, causal=causal, precision="highest"))(q, k, v)
            g_fl = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
            g_fu = jax.jit(jax.grad(loss_full, argnums=(0, 1, 2)))(q, k, v)
            err_o = float(jnp.max(jnp.abs(o_fl.astype(jnp.float32)
                                          - o_fu.astype(jnp.float32))))
            # grads scale with T; compare relative to the oracle's magnitude
            errs_g = []
            for a, bb in zip(g_fl, g_fu):
                ref = float(jnp.max(jnp.abs(bb.astype(jnp.float32)))) or 1.0
                errs_g.append(float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - bb.astype(jnp.float32)))) / ref)
            emit({
                "test": "parity", "dtype": str(dtype.__name__),
                "causal": causal, "shape": [b, t, h, d],
                "max_abs_err_out": err_o,
                "max_rel_err_grads": max(errs_g),
                "ok": bool(err_o < tol_o * t ** 0.5
                           and max(errs_g) < tol_g),
                "wall_s": round(time.time() - t0, 1),
            })

    # ---- 3: offset-causal (ring contract) ------------------------------ #
    if time.time() < deadline:
        t0 = time.time()
        b, t, h, d = 1, 1024, 2, 64
        q, k, v = mk(b, t, h, d, jnp.float32)
        # second half of q attends to ALL of k with global offsets: oracle is
        # rows [512:] of full causal attention over the whole sequence
        q_hi = q[:, 512:]

        @jax.jit
        def shard(q_hi, k, v):
            return flash_attention(q_hi, k, v, causal=True, q_offset=512,
                                   k_offset=0, interpret=False)

        o_shard = shard(q_hi, k, v)
        o_oracle = jax.jit(functools.partial(full_attention, causal=True,
                                             precision="highest"))(
            q, k, v)[:, 512:]
        err = float(jnp.max(jnp.abs(o_shard - o_oracle)))
        emit({"test": "offset_causal", "max_abs_err": err,
              "ok": bool(err < 1e-3), "wall_s": round(time.time() - t0, 1)})

    # ---- 4: ring/zigzag composition on a 1-device mesh ----------------- #
    if time.time() < deadline:
        from jax.sharding import Mesh, PartitionSpec as P
        from chainermn_tpu.parallel.sequence import (
            ring_flash_attention, zigzag_flash_attention)

        mesh = Mesh(np.array(devs[:1]), ("sp",))
        b, t, h, d = 1, 1024, 2, 64
        q, k, v = mk(b, t, h, d, jnp.float32)
        oracle = jax.jit(functools.partial(full_attention, causal=True,
                                           precision="highest"))(
            q, k, v)
        for name, fn in (("ring_flash", ring_flash_attention),
                         ("zigzag_flash", zigzag_flash_attention)):
            t0 = time.time()
            try:
                def step(q, k, v):
                    def inner(q, k, v):
                        return fn(q, k, v, "sp", causal=True)
                    return jax.shard_map(
                        inner, mesh=mesh,
                        in_specs=(P(None, "sp"),) * 3,
                        out_specs=P(None, "sp"))(q, k, v)

                def loss(q, k, v):
                    return jnp.sum(step(q, k, v) ** 2)

                with mesh:
                    o = jax.jit(step)(q, k, v)
                    g = jax.jit(jax.grad(loss))(q, k, v)
                err = float(jnp.max(jnp.abs(o - oracle)))
                emit({"test": f"{name}_world1", "max_abs_err_vs_full": err,
                      "grad_finite": bool(jnp.all(jnp.isfinite(g))),
                      "ok": bool(err < 1e-3),
                      "wall_s": round(time.time() - t0, 1)})
            except Exception as e:
                emit({"test": f"{name}_world1",
                      "error": f"{type(e).__name__}: {e}"[:400],
                      "wall_s": round(time.time() - t0, 1)})

    # ---- 5: flash vs full wall-clock (fwd+bwd), bf16 ------------------- #
    for t_len in (2048, 4096, 8192):
        if time.time() > deadline:
            emit({"test": "timing", "seq_len": t_len, "skipped": "budget"})
            continue
        b, h, d = 1, 8, 64
        q, k, v = mk(b, t_len, h, d, jnp.bfloat16)
        rec = {"test": "timing", "seq_len": t_len, "shape": [b, t_len, h, d]}
        for name, fn in (
            ("flash", functools.partial(flash_attention, causal=True,
                                        interpret=False)),
            ("full", functools.partial(full_attention, causal=True)),
        ):
            try:
                rec[f"{name}_ms"] = time_grad_step(fn, q, k, v, n=20)
            except Exception as e:
                rec[f"{name}_error"] = f"{type(e).__name__}: {e}"[:300]
        if "flash_ms" in rec and "full_ms" in rec:
            rec["full_over_flash"] = round(rec["full_ms"] / rec["flash_ms"], 3)
        emit(rec)

    # ---- 6: flash-only long-context (post-restructure capability) ------ #
    for t_len in (16384, 32768):
        if time.time() > deadline:
            emit({"test": "timing_long", "seq_len": t_len,
                  "skipped": "budget"})
            continue
        b, h, d = 1, 8, 64
        q, k, v = mk(b, t_len, h, d, jnp.bfloat16)
        rec = {"test": "timing_long", "seq_len": t_len,
               "shape": [b, t_len, h, d]}
        try:
            rec["flash_ms"] = time_grad_step(
                functools.partial(flash_attention, causal=True,
                                  interpret=False), q, k, v, n=10)
            # causal fwd+bwd FLOPs per (b,h): fwd = 2 matmuls x (T^2/2
            # visible pairs) x d x 2 FLOP/MAC = 2*T^2*d; bwd ~ 2.5x fwd
            # (5 matmuls) -> total ~ 7*T^2*d. Same FLOP (not MAC)
            # convention as bench.py / PERF.md vs the 197 TFLOP/s peak.
            flops = 7.0 * b * h * t_len * t_len * d
            rec["achieved_tflops"] = round(
                flops / (rec["flash_ms"] / 1e3) / 1e12, 2)
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        emit(rec)

    emit({"test": "done"})


if __name__ == "__main__":
    main()
