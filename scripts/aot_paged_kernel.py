#!/usr/bin/env python
"""Chip-free AOT evidence for the fused paged-decode kernel (ISSUE 14).

Lowers + compiles ``paged_attend`` against the real TPU compiler for an
abstract v5e target across the serving decode family — S=1 per-token
decode, the speculative verify window (S=k+1), both ``kv_quant`` modes,
and a serving-sized store — recording Mosaic lowering success and the
executable's peak-bytes analysis per cell. The PERF.md discipline: a
kernel claim that "lowers and fits" must be machine-checked on every
kernel change without burning a chip window; measured tokens/s come
from the benchmark's serving cells (``benchmarks/run.py``) on the chip.

Emits one JSON record per cell to scripts/aot_paged_kernel.jsonl.
"""

import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
OUT = os.path.join(_HERE, "aot_paged_kernel.jsonl")


def emit(rec):
    rec["t"] = round(time.time(), 1)
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")  # host only; target abstract

    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chainermn_tpu.parallel import paged_kernel as pk
    from chainermn_tpu.parallel.sequence import paged_scale_shape

    # smallest valid v5e topology is 2x2; the kernel is a single-device
    # program, so the call is wrapped in a fully-replicated shard_map —
    # every chip runs the complete per-chip kernel (Mosaic calls cannot
    # be auto-partitioned outside shard_map)
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(4), ("replica",))
    repl = NamedSharding(mesh, P())

    # serving-shaped cells: (label, B, S, H, D, block_size, max_blocks)
    # — a 7B-ish decode config and the bench harness's small config,
    # each at S=1 (decode / decode-window body) and S=7 (k=6 verify)
    CELLS = [
        ("7b_decode", 16, 1, 32, 128, 16, 128),
        ("7b_verify_k6", 16, 7, 32, 128, 16, 128),
        ("bench_decode", 12, 1, 4, 16, 8, 8),
        ("bench_verify_k6", 12, 7, 4, 16, 8, 8),
    ]

    for label, b, s, h, d, bs, m in CELLS:
        for quant in ("none", "int8"):
            n_blocks = b * m + 1
            kv_dtype = jnp.int8 if quant == "int8" else jnp.bfloat16
            avals = [
                jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16,
                                     sharding=repl),
                jax.ShapeDtypeStruct((n_blocks, bs, h, d), kv_dtype,
                                     sharding=repl),
                jax.ShapeDtypeStruct((n_blocks, bs, h, d), kv_dtype,
                                     sharding=repl),
                jax.ShapeDtypeStruct((b, m), jnp.int32, sharding=repl),
                jax.ShapeDtypeStruct((b,), jnp.int32, sharding=repl),
            ]
            if quant == "int8":
                avals += [jax.ShapeDtypeStruct(
                    paged_scale_shape(n_blocks, bs, h), jnp.float32,
                    sharding=repl)] * 2

            def fn(q, sk, sv, table, lengths, *scales):
                def body(q, sk, sv, table, lengths, *scales):
                    kw = {}
                    if scales:
                        kw = {"k_scale": scales[0], "v_scale": scales[1]}
                    return pk.paged_attend(q, sk, sv, table, lengths,
                                           interpret=False, **kw)

                return jax.shard_map(
                    body, mesh=mesh, in_specs=(P(),) * len(avals),
                    out_specs=P(), check_vma=False,
                )(q, sk, sv, table, lengths, *scales)

            rec = {"cell": label, "kv_quant": quant, "batch": b,
                   "window": s, "heads": h, "head_dim": d,
                   "block_size": bs, "max_blocks": m}
            if quant == "int8":
                rec["scale_shape"] = list(avals[-1].shape)
            t0 = time.time()
            try:
                c = jax.jit(fn).lower(*avals).compile()
                rec["ok"] = True
                try:
                    mem = c.memory_analysis()
                    rec["peak_hbm_mb"] = round(
                        (mem.temp_size_in_bytes
                         + mem.argument_size_in_bytes
                         + mem.output_size_in_bytes) / 2**20, 2)
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
