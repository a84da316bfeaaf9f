#!/usr/bin/env python
"""Chip-free count of whole-array passes over the paged int8 store (ISSUE 32).

Compiles, with the chip's own compiler for an abstract v5e (``v5e:2x2``, a
replicated ``shard_map``, the store donated), one decode layer (``S = 1``,
128 rows through ``paged_update_cache_and_attend`` with the kernel) and one
prefill write (``paged_write_kv``, one row of a bucket) at the serving
cells' store shapes: cell 1 (5,121 blocks of 16 tokens, 16 heads of 128)
and both of cell 3's kinds (27,701 blocks under a table of 416 entries and
21,201 under a ring of 257, 28 query heads on 4 KV heads). From the
compiled entry computation it counts the operations whose operand or result
is a whole scale array (float32 with the store's block count among its
dimensions) or a whole int8 store, other than parameters, the in-place
scatters, the Mosaic calls and ``bitcast``s (and the ``tuple`` /
``get-tuple-element`` plumbing, which moves nothing). A decode step pays
each such operation once a layer and array, whatever it writes. Of the
count, ``async_staging`` are the compiler's own asynchronous copies of an
array into its staging memory and back (``slice-start``/``-done``,
``copy-start``/``-done`` and the ``ConcatBitcast`` that joins the slices):
passes over the array all the same, but beside the program, not in it, and
the compiler's choice program by program.

A count and a structure, never a time: what the passes cost on the chip is
in the ledger's ``breakdown.device_ops`` and PERF.md §5/§6.

Appends one JSON record per program to ``scripts/aot_decode_writes.jsonl``
under ``--label`` (``parent`` from a ``git archive`` of the parent commit,
``change`` from the tree); ``--dump`` also prints the counted lines.
"""

import argparse
import json
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(_HERE, "aot_decode_writes.jsonl")

BS, D = 16, 128
# (name, blocks, table entries, query heads, KV heads, window, prefill rows
# [, head size where it is not 128])
STORES = [
    ("cell1", 5121, 64, 16, 16, None, 512),
    ("cell3_full", 27701, 416, 28, 4, None, 1024),
    ("cell3_window", 21201, 257, 28, 4, 4096, 1024),
    # 2 KV heads of 256 under 16 query heads: an int8 store of fewer than 4
    # heads is held folded (paged_store_shape), or every program relays it
    ("cell5_full", 55401, 416, 16, 2, None, 1024, 256),
]
_SHAPE = re.compile(r"\b(f32|s8)\[([\d,]+)\]")
_INSTR = re.compile(r"^(?:ROOT )?(%[\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
_MOVES_NOTHING = {"parameter", "bitcast", "tuple", "get-tuple-element"}
_STAGING = re.compile(r" (slice|copy)-(start|done)\(|ConcatBitcast")


def entry_instructions(hlo: str):
    """``(name, type, opcode, rest of the line)`` of every instruction of
    the entry computation of an HLO module's text."""
    body = hlo[hlo.index("\nENTRY "):]
    found = (_INSTR.match(ln.strip())
             for ln in body[:body.index("\n}")].splitlines()[1:])
    return [m.groups() for m in found if m]


def calls_scatter(hlo: str, rest: str) -> bool:
    """Whether the computation a fusion instruction calls holds a scatter."""
    m = re.search(r"calls=(%[\w.\-]+)", rest)
    start = hlo.find("\n" + m.group(1) + " ") if m else -1
    return start >= 0 and "scatter(" in hlo[start:hlo.index("\n}", start)]


def whole_array_ops(hlo: str, n_blocks: set):
    """``(scale_lines, store_lines)``: the entry computation's instructions
    that read or write a whole scale array, or a whole int8 store, and are
    neither plumbing, an in-place scatter nor the Mosaic call. ``n_blocks``
    holds the first dimension of the store's arrays (a scale array may
    round the block count up). The text gives an instruction's own type;
    its operands' are looked up by name."""
    instrs = entry_instructions(hlo)
    type_of = {name: typ for name, typ, _, _ in instrs}
    in_memory = lambda typ: re.sub(r"S\(\d\)", "", typ)
    scale, store = [], []
    for name, typ, op, rest in instrs:
        if op in _MOVES_NOTHING or "tpu_custom_call" in rest:
            continue
        operands = re.findall(r"%[\w.\-]+", rest.split(")", 1)[0])
        types = [typ] + [type_of.get(o, "") for o in operands]
        kinds = {t for t, dims in _SHAPE.findall(" ".join(types))
                 if n_blocks & set(map(int, dims.split(",")))}
        if not kinds:
            continue
        if (op == "fusion" and calls_scatter(hlo, rest)
                and in_memory(typ) in map(in_memory, types[1:])):
            continue        # in place: an operand's own type and layout
        line = f"{name} = {typ} {op}({rest}"
        (scale if "f32" in kinds else store).append(line)
    return scale, store


def records(topo, stores=STORES, programs=("decode", "prefill_write")):
    """Compile each program of each store for the described ``topo`` and
    yield ``(record, counted lines)``. The caller has the package to
    compile on its path and its kernels set to trace as the chip does."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chainermn_tpu.models.transformer import (
        KVCacheKind,
        init_paged_kv_caches,
    )
    from chainermn_tpu.parallel.sequence import (
        paged_update_cache_and_attend,
        paged_write_kv,
    )

    mesh = Mesh(np.array(topo.devices).reshape(4), ("replica",))
    repl = NamedSharding(mesh, P())

    class OneKind:
        compute_dtype = jnp.bfloat16

        def __init__(self, kv_heads, head_dim):
            self.kv_heads, self.head_dim = kv_heads, head_dim

        def kv_cache_spec(self):
            return (KVCacheKind("kind", (0,), self.kv_heads,
                                self.head_dim),)

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    def compile_text(body, store, *rest):
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(),) * (1 + len(rest)), out_specs=P(),
                           check_vma=False)
        return jax.jit(fn, donate_argnums=(0,)).lower(
            store, *rest).compile().as_text()

    for name, n_blocks, entries, h, hk, window, s_fill, *wide in stores:
        d = wide[0] if wide else D
        store = jax.eval_shape(lambda: init_paged_kv_caches(
            OneKind(hk, d), n_blocks, BS, quant="int8")[0])
        store = {k: aval(v.shape, v.dtype) for k, v in store.items()}
        static = {} if window is None else {"window": window}

        def decode(store, table, q, k, v, pos):
            cache = dict(store, table=table, use_kernel=True, **static)
            return paged_update_cache_and_attend(cache, q, k, v, pos)

        def fill(store, table, k, v, pos, *valid):
            # the engine caps a prefill row's writes (``valid``) in window
            # layers alone
            cache = dict(store, table=table, **static)
            if valid:
                cache["valid"] = valid[0]
            return paged_write_kv(cache, k, v, pos)

        def rows(b, s, heads):
            return aval((b, s, heads, d), jnp.bfloat16)

        built = {
            "decode": (decode, aval((128, entries), jnp.int32),
                       rows(128, 1, h), rows(128, 1, hk), rows(128, 1, hk),
                       aval((128,), jnp.int32)),
            "prefill_write": (
                fill, aval((1, entries), jnp.int32), rows(1, s_fill, hk),
                rows(1, s_fill, hk),
                *[aval((1,), jnp.int32)] * (1 if window is None else 2)),
        }
        for prog in programs:
            body, *operands = built[prog]
            hlo = compile_text(body, store, *operands)
            scale, rows8 = whole_array_ops(
                hlo, {v.shape[0] for v in store.values()})
            label = prog if prog == "decode" else f"{prog}_1x{s_fill}"
            yield {"store": name, "program": label,
                   "scale_shape": list(store["k_scale"].shape),
                   "whole_scale_array_ops": len(scale),
                   "async_staging": sum(
                       bool(_STAGING.search(ln)) for ln in scale),
                   "whole_int8_store_ops": len(rows8),
                   "mosaic_calls": hlo.count('"tpu_custom_call"')
                   }, scale + rows8


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
    for rec, lines in records(topo):
        rec = {"label": args.label, **rec}
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
        if args.dump:
            for ln in lines:
                print("   ", ln[:260])


if __name__ == "__main__":
    main()
