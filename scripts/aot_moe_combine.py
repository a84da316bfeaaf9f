#!/usr/bin/env python
"""Chip-free structure of the dropless mixture layer's combine (ISSUE 36).

Compiles, with the chip's own compiler for an abstract v5e (``v5e:2x2``, one
described chip), one :class:`~chainermn_tpu.parallel.moe.DroplessMoE` layer
at the three mixture cells' widths (cell 3: top-6 of 64 ReLU experts of 768
at d 2560, all held; cell 4: top-10 of 256 SiLU experts of 1024 at d 3072,
128 held, a shared expert; cell 5: top-10 of 512 experts of 512 at d 2048,
256 held, a gated shared expert) and at a decode step's rows (128; 256 in
cell 5), a prefill of 1024 and the largest bucket's 6144. From the compiled
entry computation it lists

- every operation traced under ``moe/combine`` (``op_name`` of the
  instruction's metadata), and
- every ``reshape``, ``convert`` or ``copy`` traced under no scope of the
  layer whose result has the expert rows' element count (``t · k · d``):
  the compiler may name the combine's relayout after no scope at all,

each with its result's type, layout and bytes (a tiled layout's padding
counted: ``f32[1024,10,3072]{2,1,0:T(8,128)}`` holds 16 sublanes for the 10).
``f32_expert_row_arrays`` counts those among them whose result is a float32
array of the expert rows' element count, ``[t, k, d]`` or ``[t · k, d]``:
the array the combine must never form.

A count and a structure, never a time: what the combine costs on the chip is
in the ledger's ``breakdown.device_ops`` and PERF.md §5/§6.

Appends one JSON record per program to ``scripts/aot_moe_combine.jsonl``
under ``--label`` (``parent`` from a ``git archive`` of the parent commit
with ``--tree``, ``change`` from the tree); ``--dump`` also prints the
listed operations.
"""

import argparse
import json
import math
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(_HERE, "aot_moe_combine.jsonl")
sys.path.insert(0, _HERE)

from aot_decode_writes import entry_instructions  # noqa: E402

# (name, the layer's fields, the rows of a decode step)
LAYERS = [
    ("cell3", dict(n_experts=64, d_model=2560, d_ff=768, top_k=6,
                   activation="relu"), 128),
    ("cell4", dict(n_experts=256, d_model=3072, d_ff=1024, top_k=10,
                   activation="silu", weight_scale=2.5, held=(0, 128),
                   shared_d_ff=1024), 128),
    ("cell5", dict(n_experts=512, d_model=2048, d_ff=512, top_k=10,
                   activation="silu", held=(0, 256), shared_d_ff=512,
                   shared_gate=True), 256),
]
PREFILLS = (1024, 6144)

_TYPE = re.compile(r"(pred|[a-z]+\d+)\[([\d,]*)\](?:\{([^}]*)\})?")
_TILE = re.compile(r"T\((\d+),(\d+)\)")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}
_UNSCOPED = {"reshape", "convert", "copy"}


def result_arrays(typ: str):
    """``(dtype, dims, bytes)`` of each array of an instruction's type; a
    tiled layout's first tile pads the two minor dimensions in memory."""
    out = []
    for dtype, dims, layout in _TYPE.findall(typ):
        dims = [int(n) for n in dims.split(",") if n]
        held = list(dims)
        tile = _TILE.search(layout or "")
        order = [int(n) for n in (layout or "").split(":")[0].split(",")
                 if n.strip().isdigit()]
        if tile and len(order) >= 2:
            for axis, size in zip(order[:2], reversed(tile.groups())):
                held[axis] = -(-held[axis] // int(size)) * int(size)
        out.append((dtype, dims, math.prod(held) * _BYTES.get(dtype, 4)))
    return out


def combine_ops(hlo: str, rows: int):
    """The entry computation's operations under ``moe/combine``, and its
    unscoped ``reshape``/``convert``/``copy`` results of ``rows`` elements
    (``t · k · d``), as records."""
    found = []
    for name, typ, op, rest in entry_instructions(hlo):
        m = re.search(r'op_name="([^"]*)"', rest)
        scope = m.group(1) if m else ""
        arrays = result_arrays(typ)
        whole = [a for a in arrays if math.prod(a[1]) == rows]
        if "moe/combine" in scope:
            where = scope[scope.index("moe/combine"):]
        elif op in _UNSCOPED and whole and "/moe/" not in scope:
            where = f"(no scope) {scope}".strip()
        else:
            continue
        kind = re.search(r"kind=(k\w+)", rest)
        found.append({
            "op": op + (f" {kind.group(1)}" if kind else ""),
            "scope": where, "result": typ,
            "bytes": sum(a[2] for a in arrays),
            "f32_expert_rows": any(a[0] == "f32" for a in whole)})
    return found


def records(topo, layers=LAYERS, prefills=PREFILLS):
    """Compile each layer at each row count for one chip of the described
    ``topo`` and yield ``(record, listed operations)``. The caller has the
    package to compile on its path and its kernels set to trace as the chip
    does."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chainermn_tpu.parallel.moe import DroplessMoE

    chip = SingleDeviceSharding(topo.devices[0])

    class Block(nn.Module):
        """The layer under the name the models give it, so that its
        operations read ``moe/...`` as in a served program."""
        fields: tuple

        @nn.compact
        def __call__(self, x):
            return DroplessMoE(compute_dtype=jnp.bfloat16, name="moe",
                               **dict(self.fields))(x)

    for name, fields, decode_rows in layers:
        block = Block(tuple(fields.items()))
        d, k = fields["d_model"], fields["top_k"]
        params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                           sharding=chip),
            jax.eval_shape(lambda: block.init(
                jax.random.PRNGKey(0), jnp.zeros((8, d), jnp.bfloat16))))
        for t in (decode_rows,) + tuple(prefills):
            x = jax.ShapeDtypeStruct((t, d), jnp.bfloat16, sharding=chip)
            hlo = jax.jit(block.apply).lower(params, x).compile().as_text()
            ops = combine_ops(hlo, t * k * d)
            yield {"layer": name, "t": t, "k": k, "d": d,
                   "combine_ops": sum("moe/combine" in o["scope"]
                                      for o in ops),
                   "unscoped_expert_row_ops": sum(
                       "moe/combine" not in o["scope"] for o in ops),
                   "f32_expert_row_arrays": sum(
                       o["f32_expert_rows"] for o in ops),
                   "listed_bytes": sum(o["bytes"] for o in ops),
                   "largest": max(ops, key=lambda o: o["bytes"])["result"],
                   }, ops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--label", required=True, help="parent | change")
    ap.add_argument("--tree", default=os.path.dirname(_HERE),
                    help="root of the checkout whose package is compiled")
    ap.add_argument("--dump", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    jax.config.update("jax_platforms", "cpu")  # host only; target abstract

    from jax.experimental import topologies

    from chainermn_tpu import ops

    ops.set_kernels_interpreted(False)  # the program the chip runs
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    for rec, listed in records(topo):
        rec = {"label": args.label, **rec}
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
        if args.dump:
            for o in listed:
                print(f"    {o['op']:<18} {o['bytes']:>13,} B  "
                      f"{o['result'][:70]:<70} {o['scope']}")


if __name__ == "__main__":
    main()
