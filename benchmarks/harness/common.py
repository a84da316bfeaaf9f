"""What every run shares: where files are, the device, memory, the result line."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """A Python file under the benchmark's directory, by path: configuration
    and metric names hold ``-`` and ``.``, which ``import`` cannot spell."""
    path = os.path.join(BENCH_DIR, *parts)
    name = "bench_" + "_".join(parts).replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reference(config: str):
    """The plain reference that sits beside ``configs/<config>.json``."""
    return load_module("configs", config + ".py")


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(name: str) -> tuple:
    """``(cell, config dict, traffic dict)`` for a ``workloads`` entry."""
    bj = benchmark_json()
    cells = {w["name"]: w for w in bj["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bj["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def metric_entries(cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    that list it under ``workloads``, and those that list nothing."""
    return [m for m in benchmark_json()[kind]
            if cell_name in m.get("workloads", [cell_name])]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout (the path is
    part of the key), unless ``JAX_COMPILATION_CACHE_DIR`` places it. Every
    program is kept, however quick its compile, so that a second run of a
    cell compiles nothing."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or os.path.join(ROOT, ".jax_cache")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int, allow_cpu: bool = False) -> dict:
    """The device as JAX reports it; leaves with code 3 and no result where
    there is no TPU or fewer chips than the cell asks for. ``allow_cpu`` is
    for the tests under ``benchmarks/tests``, which skip this look."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not allow_cpu and (device["platform"] != "tpu" or len(devs) < n):
        print(f"this cell needs {n} TPU chip(s); JAX found {device}. "
              "Nothing was run.", file=sys.stderr)
        raise SystemExit(3)
    device["count"] = n if not allow_cpu else min(n, len(devs))
    return device


def memory_peak_bytes(devices) -> int:
    """The fullest chip's high-water mark since the process started. On the
    v5e ``peak_bytes_in_use`` counts live arrays only; what a running program
    takes for its temporaries shows under ``peak_bytes_reserved`` (PERF.md
    section 6: their sum is the compiler's own figure for a training step,
    where arrays alone read 0.33 GB of 4.96). So the peak is their sum."""
    best = 0
    for d in devices:
        st = d.memory_stats() or {}
        best = max(best, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return best


class CompileCounter:
    """Counts XLA compilations by JAX's own monitoring event, so that a
    program compiled inside the measured window is seen whatever the program
    under test says about itself."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1


def percentile(values, q: float) -> float:
    """Nearest-rank percentile on the sorted sample (no interpolation beyond
    the data: a p99 of 150 gaps is the second largest)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return float(xs[k])


def emit(result: dict, checks: dict) -> None:
    """The contract's last line, and the numbers compared beside their limits
    as the last lines of standard error and the last key of the line."""
    result = dict(result)
    result["checks"] = checks
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name}: value {c['value']!r} "
              f"{c.get('must_be', '<=')} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
