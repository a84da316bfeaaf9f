#!/usr/bin/env python
"""AOT FLOPs/bytes accounting for the on-chip LM cells, chip-free.

AOT-compile the EXACT ``jit_lm_train_step`` program for the LM cell shapes
below against an abstract v5e, with the tracing decisions the chip makes
(Mosaic kernels, ``check_vma=True``), and read the compiler's own cost
accounting and memory analysis.

What the numbers cover: XLA's cost analysis cannot see inside a custom call,
so ``flops_xla`` and ``hbm_bytes_xla`` leave out everything the
``mosaic_calls`` Pallas kernels do (all of attention, forward and backward).
``peak_hbm_gb`` (the memory analysis) does include the kernels' operands.
No time, bound or ceiling is derived here: a step time and a roofline share
come from a chip run, and counts that leave attention out cannot even say
which bound the step would hit.

Run chip-free (forces the CPU backend for eager ops; the TPU compiler
is reached through the AOT lowering path only).

Appends one record per cell to scripts/lm_roofline_aot.jsonl.
"""

import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(_HERE, "lm_roofline_aot.jsonl")

# (seq_len, batch, attention, remat[, fused_ce]) — the LM cells plus
# the B=16 T=2048 remat probe (token-batch lever).
CELLS = [
    (2048, 8, "flash", False),
    (2048, 8, "full", False),
    (8192, 2, "flash", False),
    (2048, 16, "flash", True),
    (2048, 32, "flash", True, True),   # fused chunked CE: the champion
]
# Override, e.g. LM_ROOFLINE_CELLS='[[2048,16,"flash",true]]'
if os.environ.get("LM_ROOFLINE_CELLS"):
    CELLS = [tuple(c) for c in json.loads(os.environ["LM_ROOFLINE_CELLS"])]


def emit(rec):
    rec["t"] = round(time.time(), 1)
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def main():
    sys.path.insert(0, os.path.dirname(_HERE))
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import chainermn_tpu
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.ops import set_kernels_interpreted
    from chainermn_tpu.training import jit_lm_train_step

    # The host backend is the CPU, where the kernels would default to the
    # Pallas interpreter and the step to check_vma=False: that lowers a
    # program the chip never runs. Trace as the chip does.
    set_kernels_interpreted(False)

    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    mesh = Mesh(np.array(topo.devices[:1]), ("mn",))
    repl = NamedSharding(mesh, P())
    emit({"test": "target", "device_kind": topo.devices[0].device_kind})

    vocab, d_model, n_layers = 32768, 1024, 12
    n_heads = d_model // 64

    comm = chainermn_tpu.create_communicator("tpu", mesh=mesh)
    opt = chainermn_tpu.create_multi_node_optimizer(optax.adamw(3e-4), comm)

    for cell in CELLS:
        t_len, batch, attn, use_remat = cell[:4]
        fused = bool(cell[4]) if len(cell) > 4 else False
        label = attn + ("+remat" if use_remat else "") + (
            "+fused" if fused else "")
        rec = {"cell": [t_len, batch, label], "seq_len": t_len,
               "batch": batch, "attention": attn, "remat": use_remat,
               "fused_ce": fused}
        t0 = time.time()
        try:
            model = TransformerLM(
                vocab_size=vocab, d_model=d_model, n_heads=n_heads,
                n_layers=n_layers, max_len=max(t_len, 2048),
                attention=attn, compute_dtype=jnp.bfloat16,
                remat=use_remat)
            step = jit_lm_train_step(model, opt, comm, donate=False,
                                     fused_ce=fused)

            var_shapes = jax.eval_shape(
                lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
            to_aval = lambda t: jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                               sharding=repl), t)
            variables = to_aval(var_shapes)
            opt_state = to_aval(jax.eval_shape(opt.init, var_shapes))
            tok = jax.ShapeDtypeStruct((batch, t_len), jnp.int32,
                                       sharding=repl)

            compiled = step.lower(variables, opt_state, tok, tok).compile()
            rec["compile_s"] = round(time.time() - t0, 1)
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            flops = float(ca.get("flops", 0.0))
            byts = float(ca.get("bytes accessed", 0.0))
            rec["mosaic_calls"] = compiled.as_text().count(
                'custom_call_target="tpu_custom_call"')
            # outside the Mosaic calls only (module docstring)
            rec["flops_xla"] = flops
            rec["hbm_bytes_xla"] = byts
            try:
                ma = compiled.memory_analysis()
                rec["peak_hbm_gb"] = round(
                    (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                     + ma.output_size_in_bytes) / 2**30, 2)
            except Exception:
                pass
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        rec["wall_s"] = round(time.time() - t0, 1)
        emit(rec)


if __name__ == "__main__":
    main()
