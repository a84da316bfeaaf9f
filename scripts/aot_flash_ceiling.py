#!/usr/bin/env python
"""Chip-free re-validation of the flash long-context ceiling after kernel
changes (round-5: storage-dtype MXU inputs, ce1ad92).

AOT-compiles single-call flash fwd+bwd against the real TPU compiler for an
abstract v5e target at T in {32768, 131072} (the PERF.md ceiling claim), at
the default and the sweep-candidate block sizes. A claim like "compiles to
T = 131072" must be re-proven whenever the kernels change — scoped-VMEM
accounting is exactly what the dtype changes could move.

Emits one JSON record per cell to scripts/aot_flash_ceiling.jsonl.
"""

import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
OUT = os.path.join(_HERE, "aot_flash_ceiling.jsonl")


def emit(rec):
    rec["t"] = round(time.time(), 1)
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")  # host only; target is abstract

    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chainermn_tpu.ops import flash_attention, set_kernels_interpreted

    set_kernels_interpreted(False)  # Mosaic lowering during AOT trace

    # smallest valid v5e topology is 2x2 (chips_per_host_bounds); the
    # ceiling is still a single-device property — the kernel call is
    # wrapped in a fully-replicated shard_map, so every chip runs the
    # complete single-chip program (Mosaic calls cannot be auto-partitioned
    # outside shard_map)
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(4), ("replica",))
    repl = NamedSharding(mesh, P())

    # Opt-in skip of already-recorded cells (AOT_CEILING_SKIP_RECORDED=1):
    # a battery stage with a tight window spends it on the NEW cells (the
    # block-1024 runs backing the new default) instead of re-proving
    # 128/256/512. OFF by default on purpose — this script's job is
    # re-proving the ceiling after kernel changes, and a recorded-ok cell
    # from an OLDER kernel must not masquerade as re-validation (records
    # carry no kernel fingerprint).
    done = set()
    if os.environ.get("AOT_CEILING_SKIP_RECORDED"):
        try:
            with open(OUT) as f:
                for line in f:
                    try:
                        r = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if r.get("ok"):
                        done.add((r.get("seq_len"), r.get("block")))
        except OSError:
            pass

    B, H, D = 1, 8, 64
    for t_len in (32768, 131072):
        for blk in (1024, 512, 256, 128):
            if (t_len, blk) in done:
                emit({"seq_len": t_len, "block": blk, "skipped": "recorded"})
                continue
            aval = jax.ShapeDtypeStruct((B, t_len, H, D), jnp.bfloat16,
                                        sharding=repl)

            def loss(q, k, v):
                def body(q, k, v):
                    o = flash_attention(q, k, v, causal=True,
                                        interpret=False, block_q=blk,
                                        block_k=blk)
                    return jnp.sum(o.astype(jnp.float32) ** 2)

                return jax.shard_map(body, mesh=mesh, in_specs=(P(),) * 3,
                                     out_specs=P(), check_vma=False)(q, k, v)

            rec = {"seq_len": t_len, "block": blk}
            t0 = time.time()
            try:
                c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                    aval, aval, aval).compile()
                rec["ok"] = True
                try:
                    mem = c.memory_analysis()
                    rec["peak_hbm_gb"] = round(
                        (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                         + mem.output_size_in_bytes) / 2**30, 2)
                except Exception:
                    pass
            except Exception as e:
                rec["ok"] = False
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
            rec["compile_s"] = round(time.time() - t0, 1)
            emit(rec)
    emit({"done": True})


if __name__ == "__main__":
    main()
