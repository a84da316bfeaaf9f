#!/usr/bin/env python
"""MFU sweep driver: run bench.py once per (stem, batch) cell, sequentially.

A chip belongs to one process at a time, so cells run one after another, each
as its own ``python bench.py`` (this launcher never imports JAX and so never
holds the chip itself). Each cell gets one attempt under a time limit. Results
append to scripts/mfu_sweep.jsonl as they land, so an interrupted sweep loses
only the remaining cells.

Usage: python scripts/mfu_sweep.py [out.jsonl]
"""

import json
import os
import subprocess
import sys
import time

CELLS = [
    # (stem, batch) ordered by the compiler's chip-free accounting
    # (scripts/mfu_aot.jsonl): FLOPs per byte accessed grow with batch —
    # 84.6 at conv7/512, 75.1 at 256, 64.9 at 128. space_to_depth accesses
    # the same bytes as conv7 (NOT a bandwidth lever); one cell kept as the
    # measured cross-check of that prediction. 512 first: the highest
    # intensity (fits in ~15.3 of 16 GB HBM per the AOT memory analysis of
    # 2026-07). bench.py does NOT halve an explicitly-set
    # batch, so an OOM here fails this cell and the sweep moves on to the
    # next (conv7/256 is measured on purpose, once, under its own label).
    # First three are the MFU_SWEEP_MAX_CELLS=3 priority set.
    ("conv7", 512),
    ("conv7", 256),
    ("space_to_depth", 256),
    ("conv7", 384),
    ("conv7", 192),
]

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "bench.py")


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "mfu_sweep.jsonl")
    cell_timeout = int(os.environ.get("MFU_SWEEP_CELL_TIMEOUT", "2700"))
    max_cells = int(os.environ.get("MFU_SWEEP_MAX_CELLS", str(len(CELLS))))

    for stem, batch in CELLS[:max_cells]:
        env = dict(os.environ,
                   CHAINERMN_TPU_BENCH_STEM=stem,
                   CHAINERMN_TPU_BENCH_BATCH=str(batch),
                   CHAINERMN_TPU_BENCH_SWEEP="0",
                   CHAINERMN_TPU_BENCH_STEPS="50",
                   CHAINERMN_TPU_BENCH_CHILD_BUDGET=str(cell_timeout))
        t0 = time.time()
        print(f"=== cell stem={stem} batch={batch}", file=sys.stderr, flush=True)
        rec = {"stem": stem, "batch": batch}
        try:
            proc = subprocess.run([sys.executable, BENCH], env=env,
                                  stdout=subprocess.PIPE, text=True,
                                  timeout=cell_timeout)
            rec["rc"] = proc.returncode
            line = proc.stdout.strip().splitlines()
        except subprocess.TimeoutExpired as exc:
            rec["rc"] = 124
            line = (exc.stdout or "").strip().splitlines()
        rec["wall_s"] = round(time.time() - t0, 1)
        # only a cell that exited 0 has a result; whatever a failed or
        # timed-out cell printed is kept as raw text, never as a measurement
        if line and rec["rc"] == 0:
            rec["result"] = json.loads(line[-1])
        elif line:
            rec["raw"] = line[-1][:500]
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"=== cell done rc={rec['rc']} "
              f"({rec['wall_s']}s)", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
