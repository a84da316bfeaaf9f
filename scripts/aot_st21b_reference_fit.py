#!/usr/bin/env python
"""Chip-free: does the benchmark's comparison for a serving cell fit one v5e
chip beside what the harness keeps alive?

    python scripts/aot_st21b_reference_fit.py CONFIG TRAFFIC [cache_len ...]
    python scripts/aot_st21b_reference_fit.py smallthinker-21b-a3b-l8 \
        short-and-long 8704 6656
    python scripts/aot_st21b_reference_fit.py laguna-s-2.1-l5-ep2 \
        short-and-long-w512
    python scripts/aot_st21b_reference_fit.py --engine \
        qwen3-next-80b-a3b-l4-ep2 short-and-long-s256

Compiles ``benchmarks/harness/correct.py:_gap_fn`` (the plain reference of
``benchmarks/configs/CONFIG.json`` over one sequence of ``cache_len``
positions, as ``check_served`` calls it; the default is the ``cache_len`` of
``benchmarks/traffic/TRAFFIC.json``) against the real TPU compiler for an
abstract v5e target, with the weights as shapes, and prints the compiler's
memory analysis: arguments (the bfloat16 weights) + temporaries is what the
chip must hold, of 15.75 GiB, beside the engine's pools, which the harness
keeps alive while the reference runs (PERF.md section 7); a refusal prints
the compiler's message. ISSUE 29 named ``cache_len`` 8704 and gave 6656 as
the fallback if the reference did not fit (PERF.md section 4). With
``--engine`` the engine's own programs are compiled first, as the traffic
file's ``engine`` builds them (the decode step and every prefill bucket, the
Mosaic kernels not interpreted, the stores as shapes): what Mosaic or the
compiler refuses at the served widths shows here and costs no chip time.
Counts only: no time comes from here.
"""

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "benchmarks"))
sys.path.insert(0, _ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def engine_programs(cfg, tr, params, one):
    """Compile the decode step and every prefill bucket of the engine that
    the traffic file describes, with everything it holds as shapes."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models import transformer
    from chainermn_tpu.ops import set_kernels_interpreted
    from chainermn_tpu.serving import ServingEngine, engine as engine_mod
    from harness import families

    set_kernels_interpreted(False)
    real_init = transformer.init_paged_kv_caches
    engine_mod.init_paged_kv_caches = lambda *a, **kw: jax.eval_shape(
        lambda: real_init(*a, **kw))
    eng = ServingEngine(families.build_model(cfg), params, **{
        k: (tuple(v) if isinstance(v, list) else v)
        for k, v in tr["engine"].items()})
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    store = on_chip(eng._store)
    keys = lambda n: jax.ShapeDtypeStruct((n, 2), jnp.uint32, sharding=one)
    vec = lambda n, dt: jax.ShapeDtypeStruct((n,), dt, sharding=one)
    n = eng.n_slots
    programs = [("decode", eng._decode_fn, (
        params, store, on_chip(eng._table_args()), vec(n, jnp.int32),
        vec(n, jnp.int32), vec(n, jnp.bool_), keys(n)))]
    for b in eng.prefill_buckets:
        k = eng.prefill_rows(b)
        programs.append((f"prefill_{b}", eng._prefill_fns[b], (
            params, store, on_chip(jax.tree_util.tree_map(
                jnp.asarray, eng._table_args(rows=k))),
            jax.ShapeDtypeStruct((k, b), jnp.int32, sharding=one),
            vec(k, jnp.int32), vec(k, jnp.int32), vec(k, jnp.bool_),
            keys(k))))
    try:
        for name, fn, args in programs:
            rec = {"program": name}
            t0 = time.time()
            try:
                compiled = fn.lower(*args).compile()
                ma = compiled.memory_analysis()
                rec.update(
                    arguments_gb=round(ma.argument_size_in_bytes / 1e9, 3),
                    temporaries_gb=round(ma.temp_size_in_bytes / 1e9, 3),
                    aliased_gb=round(ma.alias_size_in_bytes / 1e9, 3),
                    mosaic_calls=compiled.as_text().count("tpu_custom_call"))
            except Exception as e:      # the refusal is the answer
                rec["refused"] = str(e).strip()[:1500]
            rec["compile_s"] = round(time.time() - t0, 1)
            print(json.dumps(rec), flush=True)
    finally:
        set_kernels_interpreted(None)


def main(name, traffic, lengths, engine=False):
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
    if engine:
        engine_programs(cfg, common.load_json("traffic", traffic + ".json"),
                        params, one)
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
    argv = [a for a in sys.argv[1:] if a != "--engine"]
    if len(argv) < 2:
        raise SystemExit(__doc__)
    main(argv[0], argv[1], [int(a) for a in argv[2:]],
         engine="--engine" in sys.argv)
