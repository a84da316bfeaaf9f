#!/usr/bin/env python
"""Chip-free: does the benchmark's comparison for a serving cell fit one v5e
chip beside what the harness keeps alive?

    python scripts/aot_st21b_reference_fit.py CONFIG TRAFFIC [cache_len ...]
    python scripts/aot_st21b_reference_fit.py smallthinker-21b-a3b-l8 \
        short-and-long 8704 6656
    python scripts/aot_st21b_reference_fit.py laguna-s-2.1-l5-ep2 \
        short-and-long-w512

Compiles ``benchmarks/harness/correct.py:_gap_fn`` (the plain reference of
``benchmarks/configs/CONFIG.json`` over one sequence of ``cache_len``
positions, as ``check_served`` calls it; the default is the ``cache_len`` of
``benchmarks/traffic/TRAFFIC.json``) against the real TPU compiler for an
abstract v5e target, with the weights as shapes, and prints the compiler's
memory analysis: arguments (the bfloat16 weights) + temporaries is what the
chip must hold, of 15.75 GiB, beside the engine's pools, which the harness
keeps alive while the reference runs (PERF.md section 7); a refusal prints
the compiler's message. ISSUE 29 named ``cache_len`` 8704 and gave 6656 as
the fallback if the reference did not fit (PERF.md section 4). Counts only:
no time comes from here.
"""

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "benchmarks"))
sys.path.insert(0, _ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(name, traffic, lengths):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import common, correct, families

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = common.load_json("configs", name + ".json")
    lengths = lengths or [int(common.load_json(
        "traffic", traffic + ".json")["engine"]["cache_len"])]
    shapes = families.init_shapes(cfg, families.build_model(cfg))
    dtype = families.param_dtype(cfg)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dtype, sharding=one),
        jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shapes),
            jax.tree_util.tree_leaves(shapes)))
    for length in lengths:
        fn = correct._gap_fn(name, json.dumps(cfg, sort_keys=True), length,
                             False)
        tokens = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one)
        rec = {"cache_len": length}
        t0 = time.time()
        try:
            ma = fn.lower(params, tokens).compile().memory_analysis()
            rec.update(
                arguments_gb=round(ma.argument_size_in_bytes / 1e9, 3),
                temporaries_gb=round(ma.temp_size_in_bytes / 1e9, 3),
                output_gb=round(ma.output_size_in_bytes / 1e9, 3))
        except Exception as e:          # the compiler's refusal is the answer
            rec["refused"] = str(e).strip().splitlines()[0][:400]
        rec["compile_s"] = round(time.time() - t0, 1)
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    if len(sys.argv) < 3:
        raise SystemExit(__doc__)
    main(sys.argv[1], sys.argv[2], [int(a) for a in sys.argv[3:]])
