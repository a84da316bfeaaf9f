#!/usr/bin/env python
"""Chip-free structure of the linear-attention layer's prefill and decode.

Compiles, with the chip's own compiler for an abstract v5e (``v5e:2x2``, one
described chip), one :class:`~chainermn_tpu.models.qwen3_next.GatedDeltaNet`
layer at ``qwen3next-l4-ep2-serve-short-long``'s widths (2048 wide, 16 key
and 32 value heads of 128, a convolution of 4, bfloat16 weights) writing
into that cell's state store (257 rows: 256 slots and the scratch row), as a
prefill program of the cell's buckets holds it: 4 rows of 256 and 1 row of
6144; with ``--decode``, as the decode program holds it instead: one token
for each of the 256 slots, the store donated. From the compiled entry
computation it counts

- ``mosaic_calls``: Mosaic kernels traced under ``gdn/recurrence`` (the
  chunked form as one kernel: 1; the XLA form: 0), and
- ``chunk_arrays``: operations whose result is a float32 array of a chunk's
  ``[64, 64]`` matrices (``[..., N, 64, 64]``, a chunk's matrices of every
  head in HBM: the XLA form's ``decay``, ``lower``, ``nil``, ``inv`` and
  ``local``; none in the kernel's program),

with the compiler's temporaries for the program (``temp_bytes``) and the
largest such array's type; of the decode program

- ``store_ops``: operations other than plumbing whose operand or result is
  the whole ``f32[257, 32, 128, 128]`` state store (each a pass over it: the
  XLA form's two fusions, one that reads it against ``k`` and ``q`` and one
  that reads it again and writes it in place; the kernel's one call), by
  opcode (``store_op_kinds``), and of them ``store_copies``, copies of the
  store (none where it is updated in place).

A count and a structure, never a time: what the layer costs on the chip is
in the ledger's ``breakdown.device_ops`` and PERF.md §5/§6.

Appends one JSON record per program to ``scripts/aot_gdn_prefill.jsonl``
under ``--label`` (``parent`` from a ``git archive`` of the parent commit
with ``--tree``, ``change`` from the tree); ``--dump`` also prints the
operations traced under ``gdn/recurrence`` (with ``--decode``, those that
pass over the store).
"""

import argparse
import json
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(_HERE, "aot_gdn_prefill.jsonl")
sys.path.insert(0, _HERE)

from aot_decode_writes import (  # noqa: E402
    _MOVES_NOTHING,
    entry_instructions,
)
from aot_moe_combine import result_arrays  # noqa: E402

LAYER = dict(d_model=2048, n_k_heads=16, n_v_heads=32, d_k=128, d_v=128,
             conv_kernel=4, rms_norm_eps=1e-6)
STORE_ROWS = 257
# (name, rows, bucket)
PROGRAMS = [("256x4", 4, 256), ("6144x1", 1, 6144)]
DECODE = [("decode", 256, 1)]


def recurrence_ops(hlo: str):
    """The entry computation's operations: those traced under
    ``gdn/recurrence`` and those whose result holds a float32 array of
    ``[..., 64, 64]``, as records."""
    found = []
    for name, typ, op, rest in entry_instructions(hlo):
        m = re.search(r'op_name="([^"]*)"', rest)
        scope = m.group(1) if m else ""
        arrays = result_arrays(typ)
        chunk = any(a[0] == "f32" and a[1][-2:] == [64, 64] and len(a[1]) > 2
                    for a in arrays)
        if "gdn/recurrence" not in scope and not chunk:
            continue
        found.append({
            "op": op, "scope": scope[scope.find("gdn/"):],
            "result": typ, "bytes": sum(a[2] for a in arrays),
            "mosaic": op == "custom-call" and "tpu_custom_call" in rest,
            "chunk_array": chunk})
    return found


def store_ops(hlo: str, shape):
    """The entry computation's operations, plumbing aside, whose operand or
    result is a whole float32 array of ``shape``, as records. The text
    gives an instruction's own type; its operands' are looked up by
    name."""
    instrs = entry_instructions(hlo)
    type_of = {name: typ for name, typ, _, _ in instrs}
    whole = "f32[" + ",".join(map(str, shape)) + "]"
    found = []
    for name, typ, op, rest in instrs:
        operands = re.findall(r"%[\w.\-]+", rest.split(")", 1)[0])
        if op in _MOVES_NOTHING or not any(
                whole in t for t in [typ] + [type_of.get(o, "")
                                            for o in operands]):
            continue
        m = re.search(r'op_name="([^"]*)"', rest)
        scope = m.group(1) if m else ""
        found.append({
            "op": op, "scope": scope[scope.find("gdn/"):], "result": typ,
            "bytes": sum(a[2] for a in result_arrays(typ)),
            "mosaic": op == "custom-call" and "tpu_custom_call" in rest})
    return found


def records(topo, programs=PROGRAMS, decode=False):
    """Compile the layer's prefill (with ``decode``, its decode step) for
    each program for one chip of the described ``topo`` and yield
    ``(record, listed operations)``. The caller has the package to compile
    on its path and its kernels set to trace as the chip does."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chainermn_tpu.models.qwen3_next import GatedDeltaNet

    chip = SingleDeviceSharding(topo.devices[0])
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=chip)

    class Block(nn.Module):
        """The layer under the names the model gives it, so that its
        operations read ``block_0/gdn/...`` as in a served program."""

        @nn.compact
        def __call__(self, a, state=None):
            return GatedDeltaNet(compute_dtype=jnp.bfloat16, name="gdn",
                                 **LAYER)(a, state)

    block = Block()
    d, hk, hv = LAYER["d_model"], LAYER["n_k_heads"], LAYER["n_v_heads"]
    dk, dv, kk = LAYER["d_k"], LAYER["d_v"], LAYER["conv_kernel"]
    params = jax.tree_util.tree_map(
        lambda a: shape(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: block.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, d), jnp.bfloat16))))
    store = {"S": shape((STORE_ROWS, hv, dk, dv), jnp.float32),
             "conv": shape((STORE_ROWS, kk - 1, 2 * hk * dk + hv * dv),
                           jnp.bfloat16)}
    fn = jax.jit(lambda p, a, st: block.apply(p, a, st)[1],
                 donate_argnums=(2,))
    for name, rows, bucket in programs:
        state = dict(store, valid=shape((rows,), jnp.int32))
        if not decode:
            state["slots"] = shape((rows,), jnp.int32)
        compiled = fn.lower(params, shape((rows, bucket, d), jnp.bfloat16),
                            state).compile()
        hlo = compiled.as_text()
        ops = recurrence_ops(hlo)
        chunk = [o for o in ops if o["chunk_array"]]
        rec = {"program": name, "rows": rows, "bucket": bucket,
               "mosaic_calls": sum(o["mosaic"] for o in ops),
               "chunk_arrays": len(chunk),
               "chunk_array_bytes": sum(o["bytes"] for o in chunk),
               "largest_chunk_array": (max(chunk, key=lambda o: o["bytes"])
                                       ["result"] if chunk else None),
               "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}
        if decode:
            passes = store_ops(hlo, store["S"].shape)
            rec.update(store_ops=len(passes),
                       store_op_kinds=sorted(o["op"] for o in passes),
                       store_copies=sum(o["op"].startswith("copy")
                                        for o in passes))
            ops = passes
        yield rec, ops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--label", required=True, help="parent | change")
    ap.add_argument("--tree", default=os.path.dirname(_HERE),
                    help="root of the checkout whose package is compiled")
    ap.add_argument("--dump", action="store_true")
    ap.add_argument("--decode", action="store_true",
                    help="the decode program in place of the prefill ones")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    jax.config.update("jax_platforms", "cpu")  # host only; target abstract

    from jax.experimental import topologies

    from chainermn_tpu import ops

    ops.set_kernels_interpreted(False)  # the program the chip runs
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    for rec, listed in records(topo, DECODE if args.decode else PROGRAMS,
                               decode=args.decode):
        rec = {"label": args.label, **rec}
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
        if args.dump:
            for o in listed:
                print(f"    {o['op']:<14} {o['bytes']:>13,} B  "
                      f"{o['result'][:60]:<60} {o['scope'][:70]}")


if __name__ == "__main__":
    main()
