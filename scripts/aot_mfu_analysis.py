#!/usr/bin/env python
"""AOT FLOPs/bytes accounting for the ResNet-50 train step — no chip needed.

What the compiler says about the step, without a chip:
`jax.experimental.topologies` builds
an abstract **TPU v5e** device, the real XLA TPU compiler AOT-compiles the
actual training step against it, and the compiled module's cost analysis
gives FLOPs, HBM bytes accessed and their ratio (arithmetic intensity) per
(stem, batch) config. This is the COMPILER's own accounting of the exact
program the bench runs (ResNet has no custom call, so nothing is left out).
No time, bound or MFU ceiling is derived from it here: those are roofline
shares, and a roofline share comes from a chip run.

Prints one JSON line per config and a summary table; run result lands in
``scripts/mfu_aot.jsonl``.
"""

import json
import os
import sys
import time

CONFIGS = [
    {"stem": "conv7", "batch": 128},
    {"stem": "conv7", "batch": 192},
    {"stem": "conv7", "batch": 256},
    {"stem": "conv7", "batch": 512},
    {"stem": "space_to_depth", "batch": 128},
    {"stem": "space_to_depth", "batch": 192},
    {"stem": "space_to_depth", "batch": 256},
]


def main() -> None:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import jax

    # nothing here touches a real backend; any accidental eager op
    # goes to CPU, and the AOT path below names its TPU target explicitly
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chainermn_tpu.models import ResNet50

    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    dev = np.array(topo.devices[:1])
    mesh = Mesh(dev, ("x",))
    repl = NamedSharding(mesh, P())
    print(f"# AOT target: {topo.devices[0].device_kind} (abstract, 1 chip)",
          file=sys.stderr)

    out_path = os.path.join(os.path.dirname(__file__), "mfu_aot.jsonl")
    results = []
    for cfg in CONFIGS:
        model = ResNet50(num_classes=1000, stem=cfg["stem"])
        opt = optax.sgd(0.1, momentum=0.9)

        def step(variables, opt_state, images, labels):
            def loss_fn(p):
                logits, updated = model.apply(
                    {"params": p, **{k: v for k, v in variables.items()
                                     if k != "params"}},
                    images, mutable=["batch_stats"], train=True)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels).mean(), updated

            (loss, updated), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(variables["params"])
            updates, opt_state = opt.update(grads, opt_state,
                                            variables["params"])
            params = optax.apply_updates(variables["params"], updates)
            return {"params": params, **updated}, opt_state, loss

        # abstract avals with shardings on the AOT mesh (no real arrays)
        img = jax.ShapeDtypeStruct((cfg["batch"], 224, 224, 3),
                                   jnp.bfloat16, sharding=repl)
        lbl = jax.ShapeDtypeStruct((cfg["batch"],), jnp.int32, sharding=repl)
        # abstract rng too — a concrete PRNGKey would eagerly initialize
        # the default backend
        var_shapes = jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((2, 224, 224, 3), jnp.bfloat16),
                                 train=True),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        to_aval = lambda t: jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=repl), t)
        variables = to_aval(var_shapes)
        opt_state = to_aval(jax.eval_shape(
            opt.init, var_shapes["params"]))

        t0 = time.time()
        compiled = jax.jit(step).lower(variables, opt_state, img, lbl).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0))
        byts = float(ca.get("bytes accessed", 0.0))
        rec = {
            "stem": cfg["stem"],
            "batch": cfg["batch"],
            "step_flops": flops,
            "hbm_bytes": byts,
            "arithmetic_intensity": round(flops / byts, 1) if byts else None,
            "compile_s": round(time.time() - t0, 1),
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)

    with open(out_path, "w") as f:
        for r in results:
            f.write(json.dumps(r) + "\n")
    print(f"\n# {'stem':>16} {'batch':>5} {'TFLOP':>7} {'GB':>7} {'AI':>6}",
          file=sys.stderr)
    for r in results:
        print(f"# {r['stem']:>16} {r['batch']:>5} "
              f"{r['step_flops'] / 1e12:>7.2f} {r['hbm_bytes'] / 1e9:>7.1f} "
              f"{r['arithmetic_intensity']:>6}", file=sys.stderr)


if __name__ == "__main__":
    main()
