#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python3 benchmarks/run.py --workload NAME --seed N --seconds S --trace 0|1

It needs the TPU chips the cell asks for (exit code 3 without them, and no
result), makes weights and load from ``--seed``, warms up, measures for
``--seconds`` and prints one JSON line last on standard output. With
``--trace 0`` the metrics are the cell's end-to-end ones, with ``--trace 1``
its per-layer ones, read from a profiler trace of the window's first seconds.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                      # harness
sys.path.insert(0, os.path.dirname(HERE))     # the program under test


def measure(cell, config, traffic, args, device, tamper=None):
    """Drive one cell once. Returns ``(result, checks)`` as ``emit`` takes
    them; the tests call this with a CPU device and a fault underneath."""
    from harness import common

    driver = common.load_module("harness", traffic["driver"] + ".py")
    run_rec, result, checks = driver.run(
        cell, config, traffic, args, device, T_PROCESS, tamper=tamper)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in common.metric_entries(cell["name"], kind):
        value = common.load_module(
            "metrics", entry["name"] + ".py").read(run_rec)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    device = dict(device, memory_peak_bytes=result.pop("memory_peak_bytes"))
    traced = run_rec.get("trace")
    out = {"correct": all(c["ok"] for c in checks.values()), **result,
           "metrics": metrics, "device": device}
    if traced is not None:
        device["busy_s"] = traced.busy_s
        device["window_s"] = traced.window_s
        out["breakdown"] = traced.breakdown()
    return out, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import common

    cell, config, traffic = common.find_cell(args.workload)
    import chainermn_tpu  # noqa: F401 - no result without the program

    device = common.require_chips(cell["chips"])
    cache = common.enable_compile_cache()
    common.log(f"{args.workload} seed {args.seed} on {device}; "
               f"compile cache {cache}")
    out, checks = measure(cell, config, traffic, args, device)
    common.emit(out, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
